"""Mellum 2 through the program (PERF.md section 4, PR 50): ONE kind of layer
(GQA attention with an RMSNorm per head of q and k, then softmax-routed SwiGLU
experts with a renormalised top-k, a share of them held) whose WINDOW and ROPE
are the layer's own, three window layers with the default rope to one full
layer with a YaRN rope.  Held to `benchmarks/lib/reference_mellum.py` (masks
from positions, YaRN by the published construction, its own routing) at tiny
widths that keep the published ratios (8:1 GQA, a head size that is not d /
heads, top-4 of 16 with 4 held as 8 of 64 with 16, `SSSF` twice), on the CPU,
seeded weights; on the chip the same comparison decides the cell's `correct`
at the published widths."""

import dataclasses
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import swa_moe_decoder as builder  # noqa: E402
from benchmarks.lib import reference_mellum as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, moe  # noqa: E402
from ray_tpu.models import lm, transformer  # noqa: E402
from ray_tpu.models.lm import WINDOW_TILES  # noqa: E402
from ray_tpu.models.mixers import MIXERS  # noqa: E402
from ray_tpu.ops import attention as attn_ops  # noqa: E402
from ray_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from ray_tpu.ops.pallas import grouped_matmul as gmm_kernels  # noqa: E402
from ray_tpu.ops.rotary import Rope, apply_rope, rope_frequencies  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from ray_tpu.train import run_record  # noqa: E402

SEQ = 64
with open(os.path.join(ROOT, "benchmarks", "configs", "mellum2-12b-a2.5b-ep4-1chip.json")) as f:
    PUBLISHED = json.load(f)
YARN = PUBLISHED["rope_parameters"]["full_attention"]
# The configuration file's keys at a tiny size: `SSSF` twice (the first eight of the published
# `layer_types`), a window of 8 in 64 positions, a YaRN rope whose ramp (pairs 1..5 of 8) and
# factor are both at work inside 64 positions, 4 of 16 experts held from expert 4.
CONFIG = dict(
    PUBLISHED, hidden_size=64, num_attention_heads=8, num_key_value_heads=1, head_dim=16, vocab_size=128,
    moe_intermediate_size=24, num_experts=4, num_experts_per_tok=4, num_hidden_layers=8, sliding_window=8,
    rope_parameters={
        "full_attention": dict(YARN, rope_theta=100, factor=4, original_max_position_embeddings=64, beta_fast=4,
                               attention_factor=0.1 * math.log(4) + 1),
        "sliding_attention": {"rope_type": "default", "rope_theta": 100},
    },
    share=dict(PUBLISHED["share"], num_experts_total=16, first_expert_held=4),
)
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost


def config_of(published=CONFIG, **kw):
    base = builder.model_kwargs(published, SEQ)
    base.update(dtype=jnp.float32, param_dtype=jnp.float32, remat=False, remat_policy=None)
    base["layer_ropes"] = tuple(Rope(**fields) for fields in base["layer_ropes"])
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (the norms' scales, q_norm and
    k_norm among them) drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = [1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
           if path[-1].key in ("ln1", "ln2", "final_norm", "q_norm", "k_norm") else leaf
           for (path, leaf), key in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg, **kw):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp", **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size)
    return dict(cfg=cfg, params=params, tokens=tokens, targets=jnp.roll(tokens, -1, axis=1))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


# -- one stack, two bodies a period --------------------------------------------------------


def test_sssf_twice_is_four_runs_in_one_stack(tiny):
    cfg = tiny["cfg"]
    assert cfg.layer_runs() == (("attention", "experts", 0, 3), ("attention", "experts", 3, 1),
                                ("attention", "experts", 4, 3), ("attention", "experts", 7, 1))
    assert cfg.run_starts() == (0, 3, 4, 7)
    assert {k: v[2] for k, v in cfg.stacks().items()} == {"layers": 8}
    assert cfg.layer_windows == (8, 8, 8, None) * 2
    window, rope, emit = cfg.layer_variant(3)
    assert (window, emit) == (None, False) and rope.factor == 4 and cfg.layer_variant(0)[1] == Rope(100.0)
    # window and rope change at the same layers: the rope splits no run the window had not split
    assert dataclasses.replace(cfg, layer_ropes=None, rope_theta=100.0).layer_runs() == cfg.layer_runs()
    # the published stack: 14 runs of its 28 layers, 4 of the cut's 8
    full = TransformerConfig(**{**builder.model_kwargs(dict(PUBLISHED, num_hidden_layers=28), 1024),
                                "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16, "layer_ropes": None})
    assert len(full.layer_runs()) == 14
    assert sorted(tiny["params"]["layers"]["attn"]) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert tiny["params"]["layers"]["attn"]["q_norm"].shape == (8, 16)  # [layers, head_dim]: one scale for all heads


def test_the_program_holds_what_the_builder_counts():
    kw = builder.model_kwargs(PUBLISHED, 16384)
    kw["layer_ropes"] = tuple(Rope(**fields) for fields in kw["layer_ropes"])
    cfg = TransformerConfig(**{**kw, "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert cfg.num_params() == builder.total_params(PUBLISHED) == 1_077_059_840
    assert cfg.head_dim == 128 != cfg.d_model // cfg.n_heads
    assert builder.total_params(PUBLISHED, uncut=True) == 12_149_923_072  # the card's "12B"
    # two assignments a token of a four-way share: 1.25x a uniform router's 32,768 rows (PR 53), then all
    assert moe._rungs(16384 * 8, 16, 64, 8) == (40960, 131072)


@pytest.mark.parametrize("cfg", [config_of(), config_of(n_experts_held=None, router_share_init=False)], ids=["share", "whole"])
def test_num_params_counts_the_per_head_norms(cfg):
    params = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()


# -- the rope ----------------------------------------------------------------------------------


def test_yarn_inverse_frequencies_are_the_closed_form_at_the_published_parameters():
    """theta 5e5, factor 16, 8192 original positions, 32 and 1 rotations, heads
    of 128: pairs 0..18 keep their frequency, pairs from 35 on are divided by
    16, linear between; cos and sin times 0.1 ln 16 + 1.  Program, reference
    and the closed form, each computed for itself."""
    rope = Rope(**builder.rope_kwargs(YARN))
    default = 500000.0 ** (-np.arange(64, dtype=np.float64) / 64)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5)))
    assert (low, high) == (18, 35) == rope.correction_range(128)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    want = default * (1 - ramp) + default / 16 * ramp
    got, (got_ref, factor_ref) = np.asarray(rope.inv_freq(128)), ref.inv_freq_of(YARN, 128)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_ref, want, rtol=1e-6)
    np.testing.assert_allclose(got[:19], default[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], default[35:] / 16, rtol=1e-6)
    assert np.all(got[19:35] < default[19:35]) and np.all(got[19:35] > default[19:35] / 16)
    assert rope.scale == factor_ref == pytest.approx(0.1 * math.log(16) + 1) == YARN["attention_factor"]
    assert Rope(5e5, factor=16, original_max_position=8192).scale == pytest.approx(1.2772588722239782)  # None: from s
    # at 16,384 positions, twice the original length, the two ropes really differ
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16384, 1, 128))
    positions = jnp.arange(16384)
    assert rel(apply_rope(x, positions, rope), apply_rope(x, positions, Rope(5e5))) > 0.5


def test_the_default_rope_is_the_rope_it_was_and_the_reference_rotates_alike():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 3, 16))
    positions = jnp.arange(32)

    def rope_of_theta(a):  # the rotation as `apply_rope` wrote it when it knew a base alone
        angles = positions[..., :, None].astype(jnp.float32) * rope_frequencies(a.shape[-1], 100.0)
        cos, sin = jnp.cos(angles)[..., :, None, :], jnp.sin(angles)[..., :, None, :]
        x1, x2 = a[..., 0::2].astype(jnp.float32), a[..., 1::2].astype(jnp.float32)
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(a.shape).astype(a.dtype)

    old = rope_of_theta(x)
    np.testing.assert_array_equal(old, apply_rope(x, positions, Rope(100.0)))
    np.testing.assert_array_equal(Rope(100.0).inv_freq(16), rope_frequencies(16, 100.0))
    # nothing new is traced for a rope without YaRN
    ops = lambda f: sorted(str(e.primitive) for e in jax.make_jaxpr(f)(x).eqns)  # noqa: E731
    assert ops(rope_of_theta) == ops(lambda a: apply_rope(a, positions, Rope(100.0)))
    yarn = Rope(**builder.rope_kwargs(CONFIG["rope_parameters"]["full_attention"]))
    assert yarn.correction_range(16) == (1, 5)
    inv_freq, factor = ref.inv_freq_of(CONFIG["rope_parameters"]["full_attention"], 16)
    want = jax.vmap(lambda a: ref._rotate(a, jnp.asarray(inv_freq), factor))(x)
    assert rel(apply_rope(x, positions, yarn), want) < 1e-6
    assert rel(apply_rope(x, positions, yarn), old) > 0.1
    with pytest.raises(ValueError, match="YaRN rope needs"):
        Rope(100.0, factor=4)


def test_layer_ropes_are_checked_when_the_configuration_is_built():
    with pytest.raises(ValueError, match="layer_ropes needs n_layers=2"):
        TransformerConfig.tiny(layer_ropes=(Rope(100.0),))
    with pytest.raises(ValueError, match="an ops.rotary.Rope"):
        TransformerConfig.tiny(layer_ropes=({"theta": 100.0}, None))
    with pytest.raises(ValueError, match="at a mamba layer"):
        TransformerConfig.tiny(layer_types=("mamba", "attention"), ssm_heads=2, ssm_head_dim=16, ssm_state=8,
                               layer_ropes=(Rope(100.0), None))
    with pytest.raises(ValueError, match="qk_norm is False, True .* or 'per_head'"):
        TransformerConfig.tiny(qk_norm="whole")
    TransformerConfig.tiny(layer_types=("mamba", "attention"), ssm_heads=2, ssm_head_dim=16, ssm_state=8,
                           layer_ropes=(None, Rope(100.0)))


# -- the masks ---------------------------------------------------------------------------------


def test_a_window_layers_and_a_full_layers_masks():
    q = k = np.arange(12)
    full, window = np.asarray(ref.seen(q, k, None)), np.asarray(ref.seen(q, k, 4))
    assert full.sum() == 12 * 13 // 2 and np.array_equal(full, np.tril(np.ones((12, 12), bool)))
    assert [int(n) for n in window.sum(axis=1)] == [1, 2, 3] + [4] * 9  # keys i-3 .. i
    assert window[7].nonzero()[0].tolist() == [4, 5, 6, 7]
    np.testing.assert_array_equal(window, attn_ops._seen(jnp.asarray(q), jnp.asarray(k), 4))  # the program's own
    assert builder.mean_keys_seen(12, 4) == window.sum() / 12
    assert builder.mean_keys_seen(16384, 1024) == pytest.approx(992.03125)
    assert builder.mean_keys_seen(16384, None) == 8192


@pytest.mark.parametrize("kind, reach", [("sliding_attention", 8), ("full_attention", SEQ)])
def test_a_token_reaches_the_positions_its_layers_mask_admits(kind, reach):
    """ONE layer of each kind: a changed token moves the logits at its own
    position and at the `window - 1` behind it (every later one in a full
    layer), and nowhere else."""
    published = dict(CONFIG, num_hidden_layers=1, layer_types=[kind])
    cfg = config_of(published)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, cfg.vocab_size))
    at = 20
    changed = tokens.copy()
    changed[0, at] = (changed[0, at] + 1) % cfg.vocab_size
    moved = np.abs(np.asarray(transformer.forward(params, changed, cfg) - transformer.forward(params, tokens, cfg))).max(-1)[0]
    assert np.all(moved[:at] == 0) and np.all(moved[at: min(at + reach, SEQ)] > 0) and np.all(moved[at + reach:] == 0)
    want = np.asarray(ref.logits(published, params, changed, last=SEQ))
    assert rel(transformer.forward(params, changed, cfg), want) < RTOL


# -- the model against the reference -------------------------------------------------------------


def test_logits_agree_with_the_reference(tiny):
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(got, want) < RTOL


@pytest.mark.parametrize("change, least", [
    (dict(layer_windows=None), 1e-2), (dict(layer_ropes=None, rope_theta=100.0), 1e-2),
    (dict(qk_norm=False), 1e-2), (dict(norm_topk_prob=False), 1e-2),
], ids=["no-window", "one-rope", "no-qk-norm", "gates-not-renormalised"])
def test_the_comparison_notices_each_mechanism_left_out(tiny, change, least):
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    params = tiny["params"]
    if "qk_norm" in change:
        attn = {k: v for k, v in params["layers"]["attn"].items() if k not in ("q_norm", "k_norm")}
        params = {**params, "layers": {**params["layers"], "attn": attn}}
    got = transformer.forward(params, tiny["tokens"], dataclasses.replace(tiny["cfg"], **change))
    assert rel(got, want) > least
    assert rel(ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, causal=False), want) > 0.1


def test_qk_norm_is_one_line_to_turn_off_in_file_and_reference_alike(tiny):
    published = dict(CONFIG, qk_norm=None)
    cfg = config_of(published)
    assert cfg.qk_norm is False
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    assert "q_norm" not in params["layers"]["attn"]
    assert rel(transformer.forward(params, tiny["tokens"], cfg), ref.logits(published, params, tiny["tokens"], last=SEQ)) < RTOL


def test_logits_agree_through_the_remat_policies(tiny):
    want = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    for policy in (None, "attn", "qkv_attn"):
        cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy=policy)
        np.testing.assert_allclose(transformer.forward(tiny["params"], tiny["tokens"], cfg), want, rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    (loss, terms), grads = jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True)(tiny["params"], batch)
    (want_loss, want_terms), want_grads = jax.value_and_grad(functools.partial(ref.objective, CONFIG), has_aux=True)(
        tiny["params"], tiny["tokens"], tiny["targets"])
    return dict(loss=loss, terms=terms, grads=grads, want_loss=want_loss, want_terms=want_terms, want_grads=want_grads)


def test_loss_agrees_with_the_reference(loss_and_grads):
    got, want = loss_and_grads["terms"], loss_and_grads["want_terms"]
    assert abs(float(loss_and_grads["loss"]) - float(loss_and_grads["want_loss"])) < 1e-5
    assert abs(float(got["ce_loss"]) - float(want["ce_loss"])) < 1e-5
    assert abs(float(got["moe_lb_loss"]) - float(want["moe_lb_loss"])) < 1e-5
    assert float(loss_and_grads["loss"]) == pytest.approx(float(got["ce_loss"]) + 0.001 * float(got["moe_lb_loss"]))


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert all(float(jnp.abs(want[p]).max()) > 0 for p in want)  # every leaf has a gradient, q_norm and k_norm too


def test_the_model_comparison_notices_a_layer_in_bfloat16(tiny):
    """The tolerance is tight enough: the program computing in bf16 where
    float32 is stated, from the same weights, lands far over RTOL."""
    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16)
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(transformer.forward(tiny["params"], tiny["tokens"], cfg), want) > 10 * RTOL


@pytest.mark.parametrize("part", ["router", "norms", "rope"])
def test_the_references_control_lowers_one_stated_part_and_nothing_else(tiny, part):
    """`lowered` (scripts/precision_control.py): the reference with ONE of the
    parts the file states float32 for computed in bfloat16 moves off the
    float32 program by far more than RTOL; with none it is the reference."""
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    np.testing.assert_array_equal(ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, lowered=()), want)
    assert rel(ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, lowered=(part,)), want) > 5 * RTOL
    with pytest.raises(ValueError, match="not of"):
        ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, lowered=("stream",))


# -- the share ---------------------------------------------------------------------------------


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: 16 experts in 4 shares of 4, as the deployment's
    four chips hold 64 in shares of 16; the shares' parts equal the uncut
    reference's layer (no shared expert to count once).  The renormalisation
    is over all the chosen, held or not: a share's gate values are the whole
    layer's.  Program and reference both."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None, router_share_init=False)
    key = jax.random.PRNGKey(11)
    whole = moe.init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, SEQ, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    routing = dict(top_k=4, renormalize=True)
    experts_of = lambda first: {k: (v if k == "router" else v[first: first + 4]) for k, v in whole.items()}  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref.expert_part(flat, whole, first=0, **routing)[1]
        routed_ref, routed_prog, rows = jnp.zeros_like(flat), jnp.zeros_like(flat), 0.0
        for first in range(0, 16, 4):
            part = experts_of(first)
            routed_ref += ref.expert_part(flat, part, first=first, **routing)[1]
            share = dataclasses.replace(cfg, n_experts_held=4, first_expert_held=first)
            y, stats = moe.moe_ffn(part, x, share)
            assert stats["held_rows"].shape == (4,)
            rows += float(jnp.sum(stats["held_rows"]))
            routed_prog += y.reshape(flat.shape)
        whole_prog, _ = moe.moe_ffn(whole, x, cfg)
    assert rows == flat.shape[0] * 4  # every assignment is held by exactly one share
    assert float(jnp.abs(want).max()) > 0.01
    assert rel(routed_ref, want) < 1e-5
    assert rel(routed_prog, want) < 1e-5
    assert rel(whole_prog.reshape(flat.shape), want) < 1e-5


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_routers_blocks_start_equal_and_every_share_starts_with_its_even_part(tiny, seed):
    """`router_share_init`: the blocks of the absent shares start as copies of
    the first block, which is the seed's draw and the only leaf that differs
    from independent columns; so each of a token's K choices goes to another
    share, and every share starts with T * K / shares rows on every seed,
    where independent columns give a share what the seed's winners give it."""
    cfg, key = tiny["cfg"], jax.random.PRNGKey(seed)  # 4 of 16 experts held, K = 4: one choice a share
    equal, independent = moe.init_moe_params(cfg, key), moe.init_moe_params(
        dataclasses.replace(cfg, router_share_init=False), key)
    for first in range(0, 16, 4):
        np.testing.assert_array_equal(equal["router"][:, first: first + 4], independent["router"][:, :4])
    assert all(np.array_equal(equal[k], independent[k]) for k in equal if k != "router")
    # a stream as the seeded model's is: one direction shared by every position outweighs a position's own part
    x = jax.random.normal(jax.random.fold_in(key, 3), (cfg.d_model,)) + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 4), (2, SEQ, cfg.d_model))
    rows = {name: [float(jnp.sum(moe.moe_ffn(params, x, dataclasses.replace(cfg, first_expert_held=first))[1]["held_rows"]))
                   for first in range(0, 16, 4)] for name, params in (("equal", equal), ("independent", independent))}
    assert rows["equal"] == [2.0 * SEQ] * 4  # T * K / 4 = T, on every share
    assert sum(rows["independent"]) == 2 * SEQ * 4 and max(rows["independent"]) - min(rows["independent"]) >= SEQ / 2
    stacked = transformer.init_params(cfg, key)["layers"]["mlp"]["router"]  # [layers, d, 16]: each layer's own draw
    np.testing.assert_array_equal(stacked[..., 4:8], stacked[..., :4])
    assert not np.array_equal(stacked[0], stacked[1])


@pytest.mark.parametrize("kw", [dict(n_experts_held=None), dict(first_expert_held=2), dict(experts_per_token=3),
                                dict(n_experts_held=3, first_expert_held=3)],
                         ids=["no share", "share not at a block", "choices not in whole shares", "experts not in whole shares"])
def test_equal_blocks_are_refused_where_the_shares_are_not_whole(kw):
    with pytest.raises(ValueError, match="router_share_init"):
        config_of(**kw)


def test_the_grouped_matmul_tiles_at_the_models_widths():
    tile = gmm_kernels._tile
    assert [tile(d, 1024) for d in (2048, 1024, 2304, 4096, 2688, 1856)] == [1024, 1024, 256, 1024, 896, 1856]  # as they were
    assert tile(896, 1024) == 896  # 7 x 128: one whole block
    for rows in (65536, 131072):  # the two rungs of 16,384 x 8 assignments with 16 of 64 held
        assert gmm_kernels.supported(rows, 2304, 896) and gmm_kernels.supported(rows, 896, 2304)


# -- names, counters and refusals ----------------------------------------------------------------


def test_a_window_layer_and_a_full_layer_are_told_apart_by_name_only_where_there_are_both(tiny):
    lowered = lambda cfg, params: jax.jit(lambda p, t: transformer.forward(p, t, cfg)).lower(  # noqa: E731
        params, tiny["tokens"]).as_text(debug_info=True)
    text = lowered(tiny["cfg"], tiny["params"])
    assert "layer/attn_core/attn/window" in text and "layer/attn_core/attn/full" in text
    plain = TransformerConfig.tiny(n_experts=4, experts_per_token=2)
    text = lowered(plain, transformer.init_params(plain, jax.random.PRNGKey(0)))
    assert "layer/attn_core" in text and "attn/window" not in text and "attn/full" not in text


def test_window_tiles_and_held_rows_reach_the_step_metrics_and_the_run_record(tiny):
    """A model with windows AND experts reports both families of counters
    (the window's was the dense step's alone before PR 50)."""
    assert fa.window_tiles_visited_pct(16384, 1024) == pytest.approx(100 * 31 / 136)  # 2 key tiles a query tile but the first
    cfg = dataclasses.replace(tiny["cfg"], max_seq_len=256, layer_windows=(128,) * 3 + (None,) + (128,) * 3 + (None,))
    ctx = one_device_ctx(cfg)
    run_record.drain_step_counters(), run_record.drain_step_series()
    state = ctx.init_state(seed=0)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, cfg.vocab_size))
    state, metrics = ctx.train_step(state, {"tokens": tokens, "targets": tokens})
    jax.block_until_ready(metrics)
    newest = run_record.drain_step_counters()
    assert newest[WINDOW_TILES] == pytest.approx(fa.window_tiles_visited_pct(256, 128))
    assert {"moe_held_rows_mean", "moe_load_max_over_mean", "moe_rows_moved_share"} <= set(newest)
    assert newest["moe_held_rows_mean"] * 4 * 8 <= 256 * 4 * 8  # rows held of all layers <= assignments of all layers


def test_the_causal_steps_that_copy_reach_the_run_record_and_add_no_equation_to_the_step(tiny):
    """`attn_causal_steps_copying_pct` (PR 55): the forward's value at the
    tiles in use, the mean over the layers without a window; a constant of
    the traced step; absent at a length no tile divides."""
    assert lm.CAUSAL_STEPS in lm.STEP_COUNTERS
    cfg = dataclasses.replace(tiny["cfg"], max_seq_len=1280)
    assert lm._causal_counters(cfg, 16384) == {lm.CAUSAL_STEPS: pytest.approx(100 * 136 / 256)}  # 1024 x 1024
    assert lm._causal_counters(cfg, 1280) == {lm.CAUSAL_STEPS: pytest.approx(100 * 3 / 4)}  # 640 x 640: 2 q tiles
    assert lm._causal_counters(cfg, 256) == {lm.CAUSAL_STEPS: 100.0}  # one q tile: every step runs
    assert lm._causal_counters(cfg, 1100) == {} == lm._window_counters(cfg, 1100)  # no tile divides it: the kernels do not run
    every = dataclasses.replace(cfg, layer_windows=(128,) * 8)
    assert lm._causal_counters(every, 1280) == {}  # no layer without a window
    latent = TransformerConfig.tiny(layer_types=("mla", "attention"), kv_lora_rank=16, qk_nope_head_dim=192, qk_rope_head_dim=64,
                                    v_head_dim=256)
    # heads of 256 / 256 halve the forward's key tile (`_head_blocks`): 1024 x 512 at the one layer, 1024 x 1024 at the other
    assert lm._causal_counters(latent, 8192) == {lm.CAUSAL_STEPS: pytest.approx(100 * (72 / 128 + 36 / 64) / 2)}
    ctx = one_device_ctx(cfg)
    run_record.drain_step_counters(), run_record.drain_step_series()
    state = ctx.init_state(seed=0)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 1280), 0, cfg.vocab_size))
    batch = {"tokens": tokens, "targets": tokens}
    state, metrics = ctx.train_step(state, batch)
    jax.block_until_ready(metrics)
    newest = run_record.drain_step_counters()
    assert newest[lm.CAUSAL_STEPS] == pytest.approx(100 * 3 / 4) and newest[WINDOW_TILES] > 0
    # known when the step is traced: the step's equations are the ones of a step that notes nothing
    equations = lambda: len(jax.make_jaxpr(ctx._train_step.__wrapped__)(state, ctx.make_batch(batch)).jaxpr.eqns)  # noqa: E731
    with_counter = equations()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "_causal_counters", lambda config, seq: {})
        assert equations() == with_counter


def test_the_unmasked_tiles_counter_is_the_mean_over_every_flash_layer_each_under_its_own_window(tiny):
    """`attn_tiles_unmasked_pct` (PR 63): the forward's share of run steps that
    take the body without the mask, at the tiles in use, windowed layers
    counted with the others; a constant of the traced step."""
    assert lm.TILES_UNMASKED in lm.STEP_COUNTERS
    full = dataclasses.replace(tiny["cfg"], layer_windows=None)
    for seq, clear, run in ((16384, 120, 136), (8192, 28, 36), (4096, 6, 10), (1024, 0, 1)):  # the cells' lengths
        assert lm._unmasked_counters(full, seq) == {lm.TILES_UNMASKED: pytest.approx(100 * clear / run)}
    # the stack of `mellum2`: six layers under a window of 1,024 (both visited tiles are boundary tiles), two without
    cell = dataclasses.replace(tiny["cfg"], layer_windows=(1024, 1024, 1024, None) * 2)
    assert lm._unmasked_counters(cell, 16384) == {lm.TILES_UNMASKED: pytest.approx(100 * (120 / 136) * 2 / 8)}
    assert lm._unmasked_counters(cell, 1100) == {}  # no tile divides it: the kernels do not run
    latent = TransformerConfig.tiny(layer_types=("mla", "attention"), kv_lora_rank=16, qk_nope_head_dim=192, qk_rope_head_dim=64,
                                    v_head_dim=256)  # 1024 x 512 at heads of 256 / 256: 56 of 72, the share 1024 x 1024 has
    assert lm._unmasked_counters(latent, 8192) == {lm.TILES_UNMASKED: pytest.approx(100 * 28 / 36)}
    assert lm._unmasked_counters(TransformerConfig.tiny(layer_types=("mamba",) * 2, ssm_heads=4, ssm_head_dim=16, ssm_state=16), 8192) == {}  # no attention call
    cfg = dataclasses.replace(tiny["cfg"], max_seq_len=1280)  # tiles of 640 x 640, and of 128 x 128 under the windows of 8
    ctx = one_device_ctx(cfg)
    run_record.drain_step_counters(), run_record.drain_step_series()
    state = ctx.init_state(seed=0)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 1280), 0, cfg.vocab_size))
    batch = {"tokens": tokens, "targets": tokens}
    state, metrics = ctx.train_step(state, batch)
    jax.block_until_ready(metrics)
    # windows of 8 at tiles of 128: a query tile's two key tiles are both crossed; the two full layers: 1 of 3 run steps
    assert run_record.drain_step_counters()[lm.TILES_UNMASKED] == pytest.approx(100 * (1 / 3) * 2 / 8)
    equations = lambda: len(jax.make_jaxpr(ctx._train_step.__wrapped__)(state, ctx.make_batch(batch)).jaxpr.eqns)  # noqa: E731
    with_counter = equations()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "_unmasked_counters", lambda config, seq: {})
        assert equations() == with_counter


@pytest.mark.parametrize("kw", [
    dict(layer_types=("attention",) * 2),
    dict(layer_types=("mla", "attention"), kv_lora_rank=16, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=64),
    dict(layer_types=("diff_attention", "diff_cross"), kv_source_layer=0, n_heads=4, n_kv_heads=2),
], ids=["attention", "mla", "differential"])
def test_flash_heads_are_the_head_sizes_of_the_call_each_kind_makes(kw):
    """`Mixer.flash_heads` sizes the counter's tiles apart from the call in
    `mix()`: a kind whose call changes its head sizes fails here instead of
    noting tiles its kernel does not use."""
    cfg = TransformerConfig.tiny(n_layers=2, **kw)
    calls = set()  # (kind, q/k head size, v head size): a run of equal layers is traced once

    def noting(kind):
        def call(q, k, v, **kwargs):
            calls.add((kind, q.shape[-1], v.shape[-1]))
            return attn_ops.dot_product_attention(q, k, v, **kwargs)
        return call

    with pytest.MonkeyPatch.context() as patch:
        for kind in set(cfg.layer_types):  # both differential kinds share a module and a core: one name there
            patch.setattr(sys.modules[MIXERS[kind].mix.__module__], "dot_product_attention", noting(MIXERS[kind].mix.__module__))
        tokens = jnp.zeros((1, 32), jnp.int32)
        jax.eval_shape(lambda p: transformer.forward(p, tokens, cfg), transformer.init_params(cfg, jax.random.PRNGKey(0)))
    assert calls == {(MIXERS[kind].mix.__module__, *MIXERS[kind].flash_heads(cfg)) for kind in cfg.layer_types}


def test_the_ring_and_the_pipeline_refuse_what_they_cannot_run_when_the_context_is_built():
    ring = lambda cfg: LMTrainContext(cfg, mesh=build_mesh(MeshSpec(seq=2), devices=jax.devices()[:2]), strategy="sp")  # noqa: E731
    stages = lambda cfg: LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1, pipeline=2), devices=jax.devices()[:2]),  # noqa: E731
                                        strategy="pp")
    for change, named in [(dict(layer_windows=(8, None)), "window"), (dict(layer_ropes=(Rope(100.0), None)), "rope of a layer's own"),
                          (dict(qk_norm="per_head"), "per-head QK-norm"), (dict(attention_scale=0.5), "attention_scale")]:
        cfg = TransformerConfig.tiny(**change)
        with pytest.raises(ValueError, match="ring attention takes no " + named):
            ring(cfg)
        if named != "attention_scale":
            with pytest.raises(ValueError, match="strategy 'pp' runs a homogeneous stack"):
                stages(cfg)
        one_device_ctx(cfg)  # and on one device it is built
    with pytest.raises(ValueError, match="no window .*, no rope of a layer's own .*, no per-head QK-norm"):
        ring(TransformerConfig.tiny(layer_windows=(8, None), layer_ropes=(Rope(100.0), None), qk_norm="per_head"))
    ring(TransformerConfig.tiny()), stages(TransformerConfig.tiny())  # what both run is still built


# -- the programs that were there are as they were -------------------------------------------------


def _equations(jaxpr) -> int:
    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from primitives(inner)

    return sum(1 for _ in primitives(jaxpr))


@pytest.mark.parametrize("family, kw, equations", [
    ("dense", {}, 700),
    ("expert", dict(n_heads=4, n_kv_heads=4, d_ff=32, n_experts=8, experts_per_token=2, qk_norm=True,
                    router_aux_loss_coef=0.01, router_z_loss_coef=0.001), 2894),
])
def test_a_model_without_per_layer_ropes_keeps_its_jaxpr(family, kw, equations):
    """The counts `tests/test_hybrid_model.py` pinned at the parents of PR 30
    and PR 48: `layer_ropes`, a per-head `qk_norm`, the scope of a layer's kind
    and the window counter of an expert step add no equation to a model that
    has none of them; and giving every layer the model's own rope as ITS rope
    is the same program, text for text."""
    def step_jaxpr(cfg):
        ctx = one_device_ctx(cfg)
        state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        return jax.make_jaxpr(ctx._train_step)(state, {"tokens": toks, "targets": toks})

    cfg = TransformerConfig.tiny(**kw)
    jaxpr = step_jaxpr(cfg)
    assert _equations(jaxpr.jaxpr) == equations
    own = dataclasses.replace(cfg, layer_ropes=(Rope(cfg.rope_theta),) * cfg.n_layers)
    blank = lambda text: __import__("re").sub(r"0x[0-9a-f]+", "0x", text)  # noqa: E731
    assert blank(str(step_jaxpr(own))) == blank(str(jaxpr))
