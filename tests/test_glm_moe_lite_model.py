"""GLM-4.7-Flash through the program (PERF.md section 4, PR 54): latent
attention with a low-rank q and a rotary part beside a non-rotary part of
every head, a sigmoid router with a stored bias and a shared expert behind one
dense layer, the experts HELD here a share of those the router scores, and a
multi-token-prediction module behind the trunk whose cross entropy joins the
objective.  Held to `benchmarks/lib/reference_glm_moe_lite.py` (plain softmax,
its own rope, its own routing, its own module) at tiny widths on the CPU,
seeded weights; on the chip the same comparison decides the cell's `correct`
at the published widths."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import mla_moe_decoder as builder  # noqa: E402
from benchmarks.lib import reference_glm_moe_lite as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, moe  # noqa: E402
from ray_tpu.models import lm, transformer  # noqa: E402
from ray_tpu.models.mixers import MIXERS  # noqa: E402
from ray_tpu.ops.attention import reference_attention  # noqa: E402
from ray_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from ray_tpu.ops.rotary import Rope  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 128
# The configuration file's keys at a tiny size: one dense layer, two expert layers (4 of 8 experts held, the SECOND
# of two shares: one of a token's two choices a share) and the module; v heads wider than the rope part, q/k heads of 16 + 8.
CONFIG = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_scaling": None, "partial_rotary_factor": 1, "num_nextn_predict_layers": 1,
    "hidden_size": 64, "intermediate_size": 96, "vocab_size": 128, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "rope_theta": 10000, "first_k_dense_replace": 1, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8,
    "share": {"num_experts_total": 8, "first_expert_held": 4, "chips_per_layer": 2, "expert_parallel": 2, "vocab_size_total": 256,
              "num_hidden_layers_total": 5},
    "train": {"chips": 1, "mesh": {"data": 1}, "strategy": "dp", "param_dtype": "float32", "compute_dtype": "float32",
              "optimizer": "default_optimizer", "mtp_loss_weight": 0.3, "lr_warmup_steps": 100, "remat_policy": None},
}
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost


def config_of(published=CONFIG, **kw):
    cfg = dataclasses.replace(builder._transformer_config(published, SEQ), remat=False)
    return dataclasses.replace(cfg, **kw)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (norm scales, the router's bias)
    drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm", "norm", "kv_norm", "q_norm", "enorm", "hnorm"):
            leaf = 1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name == "router_bias":
            leaf = 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    return dict(cfg=cfg, params=params, tokens=tokens, targets=targets)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


# -- what the configuration is ----------------------------------------------------------


def test_the_stack_is_a_dense_run_and_an_expert_run_and_the_module_lies_outside(tiny):
    cfg, params = tiny["cfg"], tiny["params"]
    assert cfg.layer_runs() == (("mla", "dense", 0, 1), ("mla", "experts", 0, 2))
    assert set(params) == {"embed", "final_norm", "lm_head", "mla_layers_dense", "mla_layers_experts", "mtp"}
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "norm", "block"}
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    block, stack = params["mtp"]["block"], params["mla_layers_experts"]
    assert jax.tree_util.tree_structure(block) == jax.tree_util.tree_structure(stack)
    assert all(b.shape == s.shape[1:] for b, s in zip(jax.tree_util.tree_leaves(block), jax.tree_util.tree_leaves(stack)))
    assert set(params["mla_layers_dense"]["mla"]) == {"w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "wo"}
    assert block["mlp"]["w_gate"].shape == (4, 64, 32) and block["mlp"]["router"].shape == (64, 8)  # the same share


def test_num_params_counts_the_module_and_the_axes_are_congruent(tiny):
    cfg, params = tiny["cfg"], tiny["params"]
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params)) == builder.total_params(CONFIG)
    without = dataclasses.replace(cfg, mtp_depth=0)
    assert cfg.num_params() - without.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params["mtp"]))
    assert builder.total_params(CONFIG) - builder.total_params(CONFIG, mtp=False) == cfg.num_params() - without.num_params()
    axes = transformer.param_axes(cfg)
    is_axes = lambda t: isinstance(t, tuple)  # noqa: E731
    assert jax.tree_util.tree_structure(axes, is_leaf=is_axes) == jax.tree_util.tree_structure(params)
    for a, p in zip(jax.tree_util.tree_leaves(axes, is_leaf=is_axes), jax.tree_util.tree_leaves(params)):
        assert len(a) == p.ndim
    assert axes["mtp"]["block"]["mla"]["wo"] == ("heads", "head_dim", "embed")
    assert axes["mla_layers_experts"]["mla"]["wo"] == ("layers", "heads", "head_dim", "embed")


def test_the_modules_weights_are_drawn_behind_every_stacks_and_move_none(tiny):
    cfg = tiny["cfg"]
    with_module = transformer.init_params(cfg, jax.random.PRNGKey(0))
    without = transformer.init_params(dataclasses.replace(cfg, mtp_depth=0), jax.random.PRNGKey(0))
    assert "mtp" not in without
    for a, b in zip(jax.tree_util.tree_leaves({k: v for k, v in with_module.items() if k != "mtp"}),
                    jax.tree_util.tree_leaves(without)):
        np.testing.assert_array_equal(a, b)
    block, stack = with_module["mtp"]["block"]["mla"]["w_qa"], with_module["mla_layers_experts"]["mla"]["w_qa"]
    assert not np.allclose(block, stack[0]) and not np.allclose(block, stack[1])  # weights of its own, no copy


# -- the layer against the reference ------------------------------------------------------


@pytest.mark.parametrize("q_rank", [24, None], ids=["q-rank", "one-q-projection"])
@pytest.mark.parametrize("rope", [Rope(theta=10000.0), None], ids=["rope", "nope"])
def test_the_layer_agrees_with_the_reference_with_rope_and_q_rank_on_and_off(rope, q_rank):
    cfg = config_of(n_layers=1, layer_types=("mla",), ffn_types=("dense",), mtp_depth=0, q_lora_rank=q_rank, mla_rope=rope)
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(3)))
    w = jax.tree_util.tree_map(lambda a: a[0], params["mla_layers"])
    assert ("wq" in w["mla"]) == (q_rank is None)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, cfg.d_model))
    got, handed = MIXERS["mla"].mix(x, w, jnp.arange(SEQ), cfg, None)
    assert handed == {}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._mla(xi, w, eps=cfg.norm_eps, theta=None if rope is None else rope.theta) for xi in x])
        other = jnp.stack([ref._mla(xi, w, eps=cfg.norm_eps, theta=10000.0 if rope is None else None) for xi in x])
    assert rel(got - x, want - x) < RTOL
    assert rel(got - x, other - x) > 0.05  # the rotation is no rounding error


def test_only_the_rope_parts_are_rotated_and_one_k_pe_serves_every_head():
    """Position reaches the scores through the 8-wide parts alone: with their
    q weights zeroed a rotated layer is the unrotated one."""
    cfg = config_of(n_layers=1, layer_types=("mla",), ffn_types=("dense",), mtp_depth=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(3))
    w = jax.tree_util.tree_map(lambda a: a[0], params["mla_layers"])
    w["mla"]["w_qb"] = w["mla"]["w_qb"].at[..., cfg.qk_nope_head_dim:].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, SEQ, cfg.d_model))
    rotated, _ = MIXERS["mla"].mix(x, w, jnp.arange(SEQ), cfg, None)
    plain, _ = MIXERS["mla"].mix(x, w, jnp.arange(SEQ), dataclasses.replace(cfg, mla_rope=None), None)
    np.testing.assert_allclose(rotated, plain, atol=1e-5)


# -- the model against the reference ----------------------------------------------------


def test_logits_agree_with_the_reference(tiny):
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(got, want) < RTOL


def test_the_modules_logits_agree_with_the_reference(tiny):
    got = transformer.mtp_forward(tiny["params"], tiny["tokens"], tiny["targets"], tiny["cfg"])
    main, want = ref.both_logits(CONFIG, tiny["params"], tiny["tokens"], tiny["targets"], last=SEQ)
    assert got.shape == want.shape == (2, SEQ, CONFIG["vocab_size"])
    assert rel(got, want) < RTOL
    assert rel(main, want) > 0.5  # another prediction, not the main head's again
    ctx = one_device_ctx(tiny["cfg"])
    np.testing.assert_allclose(ctx.apply_mtp(tiny["params"], tiny["tokens"], tiny["targets"]), got, rtol=1e-5, atol=1e-5)
    assert ctx.apply(tiny["params"], tiny["tokens"]).shape == (2, SEQ, CONFIG["vocab_size"])  # the main head's, as ever


@pytest.mark.parametrize("policy", [None, "attn", "qkv_attn"])
def test_logits_agree_through_the_remat_policies(tiny, policy):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy=policy)
    np.testing.assert_allclose(transformer.forward(tiny["params"], tiny["tokens"], cfg),
                               transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(transformer.mtp_forward(tiny["params"], tiny["tokens"], tiny["targets"], cfg),
                               transformer.mtp_forward(tiny["params"], tiny["tokens"], tiny["targets"], tiny["cfg"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("change, least", [
    (lambda c: dict(c, rope_theta=None), 0.05),  # nothing rotated
    (lambda c: dict(c, routed_scaling_factor=1.0), 0.01),  # the gates' factor left out
    (lambda c: dict(c, first_k_dense_replace=0), None),  # another tree altogether: refused by its layout
], ids=["no-rope", "no-scaling", "no-dense-layer"])
def test_the_comparison_notices_each_mechanism_left_out(tiny, change, least):
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    if least is None:
        with pytest.raises(KeyError):
            ref.logits(change(CONFIG), tiny["params"], tiny["tokens"], last=SEQ)
        return
    assert rel(got, ref.logits(change(CONFIG), tiny["params"], tiny["tokens"], last=SEQ)) > least


def test_the_module_comparison_notices_the_halves_swapped_and_a_norm_left_out(tiny):
    got = transformer.mtp_forward(tiny["params"], tiny["tokens"], tiny["targets"], tiny["cfg"])
    mtp = tiny["params"]["mtp"]
    d = tiny["cfg"].d_model
    swapped = dict(mtp, eh_proj=jnp.concatenate([mtp["eh_proj"][d:], mtp["eh_proj"][:d]]))  # the hidden state's half first
    unnormed = dict(mtp, hnorm=jnp.ones_like(mtp["hnorm"]))
    for other in (swapped, unnormed):
        want = ref.mtp_logits(CONFIG, dict(tiny["params"], mtp=other), tiny["tokens"], tiny["targets"], last=SEQ)
        assert rel(got, want) > 0.05


@pytest.mark.parametrize("part", ref.STATED)
def test_the_references_control_lowers_one_stated_part_and_nothing_else(tiny, part):
    """Each of the three parts the file states float32 for moves the
    reference's two outputs when computed in bfloat16, by far more than the
    float32 comparison allows, and an unknown part is refused."""
    main, module = ref.both_logits(CONFIG, tiny["params"], tiny["tokens"], tiny["targets"], last=SEQ)
    low_main, low_module = ref.both_logits(CONFIG, tiny["params"], tiny["tokens"], tiny["targets"], last=SEQ, lowered=(part,))
    assert rel(low_main, main) > 5 * RTOL and rel(low_module, module) > 5 * RTOL
    with pytest.raises(ValueError, match="lowered names"):
        ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, lowered=("softmax",))


def test_the_references_control_rounds_the_weights_to_float8(tiny):
    """The nearest precision below the file's bfloat16 weights moves both
    outputs by tens of times what bfloat16 in the stated-float32 parts does."""
    main, module = ref.both_logits(CONFIG, tiny["params"], tiny["tokens"], tiny["targets"], last=SEQ)
    low_main, low_module = ref.both_logits(CONFIG, tiny["params"], tiny["tokens"], tiny["targets"], last=SEQ, lowered=(ref.WEIGHTS,))
    assert rel(low_main, main) > 0.03 and rel(low_module, module) > 0.03


def test_the_model_comparison_notices_a_layer_in_bfloat16(tiny):
    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16)
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(transformer.forward(tiny["params"], tiny["tokens"], cfg), want) > 10 * RTOL


# -- the objective and its gradients ----------------------------------------------------


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    ctx = one_device_ctx(cfg)
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    (loss, terms), grads = jax.value_and_grad(ctx._loss, has_aux=True)(tiny["params"], batch)
    (want_loss, want_terms), want_grads = jax.value_and_grad(functools.partial(ref.loss, CONFIG), has_aux=True)(
        tiny["params"], tiny["tokens"], tiny["targets"])
    term_grads = {name: jax.grad(lambda p, name=name: ctx._loss(p, batch)[1][name])(tiny["params"])
                  for name in ("ce_loss", "mtp_loss")}
    return dict(loss=loss, terms=terms, grads=grads, want_loss=want_loss, want_terms=want_terms, want_grads=want_grads,
                term_grads=term_grads)


def test_loss_and_both_terms_agree_with_the_reference(loss_and_grads):
    got, want = loss_and_grads["terms"], loss_and_grads["want_terms"]
    assert abs(float(loss_and_grads["loss"]) - float(loss_and_grads["want_loss"])) < 1e-5
    assert abs(float(got["ce_loss"]) - float(want["ce_loss"])) < 1e-5
    assert abs(float(got["mtp_loss"]) - float(want["mtp_loss"])) < 1e-5
    # no auxiliary router loss: the objective is the two terms, the second at the file's weight
    assert float(loss_and_grads["loss"]) == pytest.approx(float(got["ce_loss"]) + 0.3 * float(got["mtp_loss"]), rel=1e-6)
    assert float(got["mtp_loss"]) > float(got["ce_loss"]) * 0.5  # alive, of the size of a cross entropy


def test_the_modules_last_position_is_masked_and_a_mask_is_shifted_with_the_targets(tiny):
    """The term is the mean over S - 1 positions: the roll's wrap-around
    target at the last position weighs nothing; a batch's mask admits a
    position of the module only where the target after the next is admitted."""
    ctx = one_device_ctx(tiny["cfg"])
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    base = float(ctx._loss(tiny["params"], batch)[1]["mtp_loss"])
    wrapped = dict(batch, targets=tiny["targets"].at[:, 0].set((tiny["targets"][:, 0] + 1) % 128))
    # targets[0] is the module's INPUT at position 0 and its target at position S - 1 alone (the roll's wrap)
    logits = transformer.mtp_forward(tiny["params"], tiny["tokens"], tiny["targets"], tiny["cfg"])
    by_hand = lm.cross_entropy_loss(logits[:, :-1], tiny["targets"][:, 1:])
    assert base == pytest.approx(float(by_hand), rel=1e-5)
    assert float(ctx._loss(tiny["params"], wrapped)[1]["mtp_loss"]) != base  # it is an input
    mask = jnp.ones_like(tiny["tokens"]).at[:, SEQ // 2:].set(0)
    masked = float(ctx._loss(tiny["params"], dict(batch, mask=mask))[1]["mtp_loss"])
    by_hand = lm.cross_entropy_loss(logits[:, :SEQ // 2 - 1], tiny["targets"][:, 1:SEQ // 2])
    assert masked == pytest.approx(float(by_hand), rel=1e-5)


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got if float(jnp.abs(want[p]).max()) > 0}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert len(worst) == len(got) - 2  # every leaf but the router bias of the expert stack and of the module's block


def test_the_shared_embedding_and_head_get_the_sum_of_both_paths(loss_and_grads):
    """The module reads the model's own table and head: each gets the main
    term's gradient plus the weight times the module's, both alive; the
    module's own leaves get the second alone and the main term gives them none."""
    total, ce, mtp = loss_and_grads["grads"], loss_and_grads["term_grads"]["ce_loss"], loss_and_grads["term_grads"]["mtp_loss"]
    for leaf in (lambda g: g["embed"]["tokens"], lambda g: g["lm_head"], lambda g: g["final_norm"],
                 lambda g: g["mla_layers_dense"]["mla"]["w_qa"]):
        assert float(jnp.abs(leaf(ce)).max()) > 0 and float(jnp.abs(leaf(mtp)).max()) > 0
        np.testing.assert_allclose(leaf(total), leaf(ce) + 0.3 * leaf(mtp), rtol=1e-4, atol=1e-7)
    for a, b, c in zip(*(jax.tree_util.tree_leaves(g["mtp"]) for g in (total, ce, mtp))):
        assert float(jnp.abs(b).max()) == 0.0
        np.testing.assert_allclose(a, 0.3 * c, rtol=1e-4, atol=1e-7)


def test_the_router_bias_gets_a_zero_gradient_in_the_stack_and_in_the_module(loss_and_grads):
    grads = loss_and_grads["grads"]
    for mlp in (grads["mla_layers_experts"]["mlp"], grads["mtp"]["block"]["mlp"]):
        assert float(jnp.abs(mlp["router_bias"]).max()) == 0.0
        assert float(jnp.abs(mlp["router"]).max()) > 0.0


# -- the share ----------------------------------------------------------------------------


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: 8 experts in 2 shares of 4 (the file's
    `chips_per_layer`); the shares' routed parts plus the shared expert counted
    ONCE equal the uncut reference's layer output.  Program and reference
    both; the gate values are renormalised over ALL the chosen, held or not."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None, router_share_init=False)
    key = jax.random.PRNGKey(11)
    whole = moe.init_moe_params(cfg, key)
    whole["router_bias"] = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (8,))
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, SEQ, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    # `moe_ffn` takes the normed hidden state and the reference norms its own input: rows of unit
    # RMS under a scale of one and eps 0 make that norm the identity, and `- unit` takes the residual off
    unit = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True))
    ones = jnp.ones(cfg.d_model)
    published = dict(eps=0.0, top_k=2, renormalize=True, scaling=cfg.routed_scaling_factor)
    experts_of = lambda first: {k: (v[first: first + 4] if k in ("w_gate", "w_up", "w_down") else v)  # noqa: E731
                                for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        want = ref._ffn(unit, {"mlp": whole, "ln2": ones}, first=0, **published) - unit
        shared = ref._swiglu(unit, whole["shared"])
        routed_ref, routed_prog = jnp.zeros_like(unit), jnp.zeros_like(unit)
        for first in (0, 4):
            part = experts_of(first)
            routed_ref += ref._ffn(unit, {"mlp": part, "ln2": ones}, first=first, **published) - unit - shared
            share = dataclasses.replace(cfg, n_experts_held=4, first_expert_held=first)
            y, stats = moe.moe_ffn(part, unit.reshape(x.shape), share)
            assert stats["held_rows"].shape == (4,)
            routed_prog += y.reshape(unit.shape) - shared
        whole_prog, _ = moe.moe_ffn(whole, unit.reshape(x.shape), cfg)
    assert float(jnp.abs(routed_ref).max()) > 0.1  # the routed part is no rounding error of the sum
    assert rel(routed_ref + shared, want) < 1e-5
    assert rel(routed_prog + shared, want) < 1e-5
    assert rel(whole_prog.reshape(unit.shape), want) < 1e-5


def test_the_ladder_at_four_choices_and_a_quarter_of_the_experts_starts_at_the_balanced_rung():
    """K = 4 and 16 of 64 held give a token ONE assignment here: the ladder is
    1.25x a uniform share and all (PR 53's rule); an eighth held (half an
    assignment a token) would keep the parent's 2x, 4x, all."""
    assert moe._rungs(8192 * 4, 16, 64, 4) == (10240, 32768)
    assert moe._rungs(8192 * 4, 8, 64, 4) == (8192, 16384, 32768)


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_the_routers_blocks_start_equal_in_the_stack_and_in_the_module_and_every_share_starts_with_one_choice(tiny, seed):
    """`router_share_init` (the file's `assumed.initial_values`): the two blocks
    of four columns start as copies, in the expert layers and in the module's
    block alike, so a token's two choices are its best column once in each
    share: this share starts every layer with exactly T rows whatever the seed."""
    cfg = tiny["cfg"]
    assert cfg.router_share_init
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    routers = [*params["mla_layers_experts"]["mlp"]["router"], params["mtp"]["block"]["mlp"]["router"]]
    for router in routers:
        np.testing.assert_array_equal(router[:, :4], router[:, 4:])
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (SEQ, cfg.d_model))
    mlp = jax.tree_util.tree_map(lambda a: a[0], params["mla_layers_experts"]["mlp"])
    idx, gates, _ = moe._route(mlp, h, cfg)
    assert np.array_equal(np.sort(np.asarray(idx) // 4, axis=1), np.tile([0, 1], (SEQ, 1)))  # once in each share
    np.testing.assert_allclose(gates, 0.5 * cfg.routed_scaling_factor, rtol=1e-6)  # two equal scores, renormalised
    _, terms = one_device_ctx(cfg)._loss(params, {"tokens": tiny["tokens"], "targets": tiny["targets"]})
    assert float(terms["moe_held_rows_mean"]) * 4 == tiny["tokens"].size  # T rows a layer over four held experts


# -- the step's terms, counters and names ------------------------------------------------------


def test_the_terms_and_counters_count_the_modules_block_as_one_more_expert_layer(tiny):
    ctx = one_device_ctx(tiny["cfg"])
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    _, terms = ctx._loss(tiny["params"], batch)
    assert {"ce_loss", "mtp_loss", "moe_lb_loss", "moe_z_loss", "moe_load_max_over_mean", "moe_held_rows_mean",
            "moe_held_rows_max", "moe_rows_moved_share"} <= set(terms)
    seen = []
    real = lm.router_losses
    try:
        lm.router_losses = lambda stats, cfg: seen.append(stats) or real(stats, cfg)
        ctx_seen = one_device_ctx(tiny["cfg"])
        jax.eval_shape(ctx_seen._loss, tiny["params"], batch)
    finally:
        lm.router_losses = real
    assert seen[0]["held_rows"].shape == (3, 4) and seen[0]["choice_share"].shape == (3, 2, 8)  # two layers and the module
    assert "mtp_loss" in lm.STEP_COUNTERS
    state = {"params": tiny["params"], "opt_state": ctx.optimizer.init(tiny["params"]), "step": jnp.zeros((), jnp.int32)}
    _, metrics = ctx._train_step(state, batch)
    assert float(metrics["loss"]) == pytest.approx(float(metrics["ce_loss"]) + 0.3 * float(metrics["mtp_loss"]), rel=1e-5)


def test_the_module_is_named_mtp_in_both_directions(tiny):
    """Every op of the module carries `mtp` in its path: its projection
    (`mtp/proj`), its block's own names, its pass through the head and the
    loss; forward and transposed."""
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    ctx = one_device_ctx(cfg)
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    text = jax.jit(jax.grad(lambda p: ctx._loss(p, batch)[0])).lower(tiny["params"]).as_text(debug_info=True)
    for inner in ("mtp/proj", "layer/attn_proj/mla/proj", "layer/attn_core", "layer/mlp/moe/router", "layer/mlp/moe/shared",
                  "lm_head", "loss", "embed"):
        assert f"jvp(mtp)/{inner}" in text or f"jvp(mtp)/checkpoint/{inner}" in text, inner
    for inner in ("mtp/proj", "lm_head", "loss"):
        assert f"transpose(jvp(mtp))/{inner}" in text, inner
    assert "transpose(jvp(mtp))/jvp(mtp)/checkpoint/rematted_computation/layer/attn_proj/mla/proj" in text


def test_without_the_module_the_program_has_none_of_it(tiny):
    cfg = dataclasses.replace(tiny["cfg"], mtp_depth=0)
    ctx = one_device_ctx(cfg)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    assert "mtp" not in state["params"]
    toks = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(ctx._train_step)(state, {"tokens": toks, "targets": toks})
    assert "mtp" not in str(jaxpr)
    _, terms = jax.eval_shape(ctx._loss, state["params"], {"tokens": toks, "targets": toks})
    assert "mtp_loss" not in terms and "ce_loss" in terms


def test_a_dense_model_takes_a_module_too():
    cfg = TransformerConfig.tiny(mtp_depth=1, mtp_loss_weight=0.5)
    ctx = one_device_ctx(cfg)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["mtp"]["block"]) == {"attn", "mlp", "ln1", "ln2"}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    loss, terms = ctx._loss(params, {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)})
    assert set(terms) == {"ce_loss", "mtp_loss", "attn_causal_steps_copying_pct", "attn_tiles_unmasked_pct"}
    assert float(loss) == pytest.approx(float(terms["ce_loss"]) + 0.5 * float(terms["mtp_loss"]), rel=1e-6)


# -- refusals, by name -------------------------------------------------------------------------


SAMBAY = dict(n_layers=6, rope_theta=None, tie_embeddings=True, norm_kind="layer", attn_bias=True,
              layer_types=("s6", "diff_attention", "s6", "diff_attention", "gmu", "diff_cross"),
              layer_windows=(None, 16, None, None, None, None), s6_inner=128, s6_state=8, s6_dt_rank=8,
              s6_memory_layer=2, kv_source_layer=3)


@pytest.mark.parametrize("kw, match", [
    (dict(mtp_depth=2), "mtp_depth is 0"),
    (dict(mtp_depth=-1), "mtp_depth is 0"),
    (dict(mtp_depth=1, mtp_loss_weight=-0.1), "mtp_loss_weight >= 0"),
    (dict(mtp_depth=1, logits_scaling=8.0), "logits_scaling must be 1.0"),
    (dict(SAMBAY, mtp_depth=1), "crosses layers"),
], ids=["depth-2", "depth-negative", "weight-negative", "logits-scaling", "a-kind-that-crosses-layers"])
def test_the_configuration_refuses_a_module_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig.tiny(**kw)


@pytest.mark.parametrize("kw, match", [
    (dict(q_lora_rank=0), "q_lora_rank is None"),
    (dict(mla_rope=10000.0), "mla_rope is an ops.rotary.Rope"),
    (dict(mla_rope=Rope(theta=10000.0), qk_rope_head_dim=7), "even qk_rope_head_dim"),
], ids=["q-rank-0", "rope-a-number", "odd-rope-part"])
def test_latent_attention_refuses_what_it_cannot_rotate_or_project(kw, match):
    with pytest.raises(ValueError, match=match):
        config_of(**kw)


def test_the_pipeline_and_a_sharded_share_refuse_the_module_when_the_context_is_built(tiny):
    dense = TransformerConfig.tiny(n_layers=4, mtp_depth=1)
    mesh = build_mesh(MeshSpec(data=1, pipeline=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="multi-token-prediction module"):
        LMTrainContext(dense, mesh=mesh, strategy="pp")
    with pytest.raises(ValueError, match="mtp_depth beside n_experts_held"):
        LMTrainContext(tiny["cfg"], mesh=build_mesh(MeshSpec(data=2), devices=jax.devices()[:2]), strategy="dp")


# -- the flash kernels at heads of 256 ------------------------------------------------------------


@pytest.mark.parametrize("sizes, fwd_key_tile", [((64, 64), 1024), ((128, 128), 1024), ((192, 128), 1024), ((256, 256), 512),
                                                 ((256, 192), 1024)],
                         ids=["64", "128", "192-128", "256-256", "256-192"])
def test_the_tiles_follow_from_the_head_sizes_in_one_place(sizes, fwd_key_tile):
    """Heads of 64, 128 and 192 / 128 keep the tiles they had; 256 / 256
    halves the forward's key tile (libtpu refuses 1024 x 1024 there:
    `tests/test_tpu_compiled_step.py` compiles it)."""
    assert fa._head_blocks(*sizes, (1024, 1024, 1024, 512)) == (1024, fwd_key_tile, 1024, 512)
    assert fa._head_blocks(*sizes, (128, 128, 128, 128)) == (128, 128, 128, 128)  # a smaller request stays


def test_flash_kernels_at_heads_of_256_forward_and_backward():
    """Interpret mode, against plain softmax, through the default tiles'
    choice (S = 1024 > the halved key tile: two key tiles a query tile)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v, do = (jax.random.normal(key, (1, 1024, 1, 256)) for key in ks)
    got, want = fa.flash_attention(q, k, v), reference_attention(q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-6)
    grads = jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a) * do), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(reference_attention(*a) * do), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, wants):
        np.testing.assert_allclose(a, b, atol=3e-5)
