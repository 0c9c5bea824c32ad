"""Nemotron-H through the program (PERF.md section 4, PR 44): blocks that are a
Mamba-2 mixer (B/C in groups), a GQA attention at a head size of its own or an
expert layer alone (two-matrix relu2 experts, a shared expert of a stated
width, a share of the experts held), paired by the program into (mixer, FFN)
layers of which one has NO FFN.  Held to `benchmarks/lib/reference_nemotron_h.py`
(single blocks read from the pattern, token-by-token recurrence, plain softmax,
its own routing) at tiny widths that keep every ratio of the published model
(8 groups -> 4, 16:1 GQA -> 4:1, an expert width that is no multiple of 128 ->
40), on the CPU, seeded weights; on the chip the same comparison decides the
cell's `correct` at the published widths."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import nemotron_h_decoder as builder  # noqa: E402
from benchmarks.lib import reference_hybrid, reference_nemotron_h as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, moe  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm_op  # noqa: E402
from ray_tpu.ops.pallas import grouped_matmul as gmm_kernels  # noqa: E402
from ray_tpu.ops.ssm import ssd_chunked  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from ray_tpu.train import run_record  # noqa: E402

SEQ = 64
with open(os.path.join(ROOT, "benchmarks", "configs", "nemotron-3-nano-30b-a3b-ep8-1chip.json")) as f:
    PUBLISHED = json.load(f)
# The configuration file's keys at a tiny size: eight blocks `MEM*EM*E`, so every pair the
# program can make of them: (mamba, experts), (mamba, none) twice in two runs (the FFN-less
# pair, `M*`), (attention, experts) twice; 4 of 16 experts held from expert 4.
CONFIG = dict(
    PUBLISHED, hidden_size=96, num_attention_heads=8, num_key_value_heads=2, head_dim=16, vocab_size=128,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=4, moe_intermediate_size=40,
    moe_shared_expert_intermediate_size=72, n_routed_experts=4, num_experts_per_tok=3, num_hidden_layers=8,
    hybrid_override_pattern="MEM*EM*E" + "ME" * 4,
    share=dict(PUBLISHED["share"], num_experts_total=16, first_expert_held=4),
)
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost


def config_of(published=CONFIG, **kw):
    base = builder.model_kwargs(published, SEQ)
    base.update(dtype=jnp.float32, param_dtype=jnp.float32, remat=False, remat_policy=None)
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (norm scales, the router's bias,
    the convolution's bias, D) drawn anew, so that a test cannot pass by
    ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm", "norm", "D"):
            leaf = 1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name in ("router_bias", "conv_b"):
            leaf = 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module", autouse=True)
def chunk_of_16():
    """The program's chunk for this module: S = 64 crosses three boundaries."""
    from ray_tpu.ops import ssm

    saved, ssm.CHUNK = ssm.CHUNK, 16
    yield
    ssm.CHUNK = saved


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size)
    return dict(cfg=cfg, params=params, tokens=tokens, targets=jnp.roll(tokens, -1, axis=1))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


# -- blocks into pairs, and a pair without an FFN ------------------------------------------


def test_single_blocks_become_pairs_and_one_pair_has_no_ffn(tiny):
    cfg = tiny["cfg"]
    assert ref.layer_pairs(CONFIG) == [("mamba", "experts"), ("mamba", "none"), ("attention", "experts"),
                                       ("mamba", "none"), ("attention", "experts")]
    assert cfg.layer_runs() == (("mamba", "experts", 0, 1), ("mamba", "none", 0, 1), ("attention", "experts", 0, 1),
                                ("mamba", "none", 1, 1), ("attention", "experts", 1, 1))
    assert {k: v[2] for k, v in cfg.stacks().items()} == {"layers": 2, "mamba_layers_experts": 1, "mamba_layers_none": 2}
    alone = tiny["params"]["mamba_layers_none"]
    assert sorted(alone) == ["ln1", "ssm"]  # no ln2, no mlp: the layer is its mixer
    assert sorted(tiny["params"]["mamba_layers_experts"]) == ["ln1", "ln2", "mlp", "ssm"]
    assert sorted(transformer.param_axes(cfg)["mamba_layers_none"]) == ["ln1", "ssm"]
    # the published period: five pairs in three stacks
    assert builder.layer_pairs(PUBLISHED) == [("mamba", "experts")] * 2 + [("mamba", "none"), ("attention", "experts"),
                                                                           ("mamba", "experts")]


def test_an_ffn_less_layer_traces_nothing_under_layer_mlp(tiny):
    cfg = dataclasses.replace(tiny["cfg"], n_layers=1, layer_types=("mamba",), ffn_types=("none",))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    text = jax.jit(lambda p, t: transformer.forward(p, t, cfg)).lower(params, tiny["tokens"]).as_text(debug_info=True)
    assert "ssm/scan" in text and "layer/mlp" not in text
    paired = dataclasses.replace(cfg, ffn_types=("experts",))
    params = transformer.init_params(paired, jax.random.PRNGKey(0))
    text = jax.jit(lambda p, t: transformer.forward(p, t, paired)).lower(params, tiny["tokens"]).as_text(debug_info=True)
    assert "layer/mlp" in text and "moe/shared" in text


def test_an_expert_block_without_a_mixer_before_it_is_refused():
    with pytest.raises(ValueError, match="no mixer before it"):
        ref.layer_pairs(dict(CONFIG, hybrid_override_pattern="MEEM*EM*E"))
    with pytest.raises(ValueError, match="ffn_types"):
        TransformerConfig.tiny(ffn_types=("dense", "nothing"))


def test_the_head_size_is_the_configurations_own(tiny):
    cfg = tiny["cfg"]
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads  # 96 / 8 = 12
    attn = tiny["params"]["layers"]["attn"]
    assert attn["wq"].shape == (2, 96, 8, 16) and attn["wk"].shape == (2, 96, 2, 16) and attn["wo"].shape == (2, 8, 16, 96)
    assert TransformerConfig.tiny().head_dim == 16  # absent: d_model // n_heads, as it was


@pytest.mark.parametrize("cfg", [
    config_of(),
    config_of(n_experts_held=None),
    TransformerConfig.tiny(n_layers=3, layer_types=("mamba", "attention", "mamba"), ffn_types=("none", "dense", "none"),
                           ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2, attn_head_dim=24),
    TransformerConfig.tiny(n_experts=4, experts_per_token=2, expert_kind="relu2", n_shared_experts=1,
                           shared_expert_d_ff=200, moe_d_ff=24),
], ids=["nemotron-share", "nemotron-whole", "none-beside-dense", "relu2-everywhere"])
def test_num_params_counts_every_pair(cfg):
    params = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()


def test_the_program_holds_what_the_builder_counts():
    cfg = TransformerConfig(**{**builder.model_kwargs(PUBLISHED, 8192), "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert cfg.num_params() == builder.total_params(PUBLISHED) == 986_254_848


# -- the model against the reference -----------------------------------------------------


def test_logits_agree_with_the_reference(tiny):
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(got, want) < RTOL


def test_logits_agree_through_the_remat_policies(tiny):
    want = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    for policy in (None, "attn", "qkv_attn"):
        cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy=policy)
        np.testing.assert_allclose(transformer.forward(tiny["params"], tiny["tokens"], cfg), want, rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    (loss, terms), grads = jax.value_and_grad(ctx._loss, has_aux=True)(tiny["params"], batch)
    want_loss, want_grads = jax.value_and_grad(functools.partial(ref.objective, CONFIG))(
        tiny["params"], tiny["tokens"], tiny["targets"])
    return dict(loss=loss, terms=terms, grads=grads, want_loss=want_loss, want_grads=want_grads)


def test_loss_agrees_with_the_reference(loss_and_grads):
    assert abs(float(loss_and_grads["loss"]) - float(loss_and_grads["want_loss"])) < 1e-5
    assert float(loss_and_grads["terms"]["ce_loss"]) == pytest.approx(float(loss_and_grads["loss"]))  # no auxiliary loss


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    """2e-3: the worst leaf is a small one behind the longest chain (a norm's
    scale ahead of recurrence, router and head), float32 both sides."""
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got if float(jnp.abs(want[p]).max()) > 0}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert len(worst) == len(got) - 2  # every leaf but the two expert stacks' router bias has a gradient


def test_the_model_comparison_notices_a_layer_in_bfloat16(tiny):
    """The tolerance is tight enough: the program computing in bf16 where
    float32 is stated, from the same weights, lands far over RTOL."""
    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16)
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(transformer.forward(tiny["params"], tiny["tokens"], cfg), want) > 10 * RTOL


# -- Mamba-2 with groups -----------------------------------------------------------------


def ssd_inputs(seed, groups, s=96, b=2, h=8, p=8, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    shape = (b, s, n) if groups is None else (b, s, groups, n)
    return x, dt, A, jax.random.normal(ks[3], shape), jax.random.normal(ks[4], shape), jax.random.normal(ks[5], (h,))


@pytest.mark.parametrize("groups", [2, 4, 8], ids=lambda g: f"{g}-groups")
def test_grouped_ssd_is_the_token_by_token_recurrence_forward_and_gradient(groups):
    """1e-5: float32 against float32, the chunked sums against the serial ones."""
    args = ssd_inputs(0, groups)

    def serial(x, dt, A, B, C, D):
        return jax.vmap(lambda xi, dti, bi, ci: ref._recurrence(xi, dti, A, bi, ci, D))(x, dt, B, C)

    with jax.default_matmul_precision("highest"):
        want, got = serial(*args), ssd_chunked(*args, chunk=32)
        assert rel(got, want) < 1e-5
        probe = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        grads = jax.grad(lambda *a: jnp.sum(ssd_chunked(*a, chunk=32) * probe), argnums=range(6))(*args)
        wants = jax.grad(lambda *a: jnp.sum(serial(*a) * probe), argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), grads, wants):
        assert rel(a, b) < 1e-4, name


def test_one_group_is_the_ungrouped_scan():
    """B, C [b, S, N] take the code path the scan had before groups (no
    grouped einsum in its trace: the accepted cells' HLO is the parent's);
    one group said as [b, S, 1, N] is the same numbers to float32's rounding
    of another order of summation, and G equal groups are what one group
    gives every head."""
    x, dt, A, B, C, D = ssd_inputs(1, None)
    want = ssd_chunked(x, dt, A, B, C, D, chunk=32)
    text = str(jax.make_jaxpr(functools.partial(ssd_chunked, chunk=32))(x, dt, A, B, C, D))
    assert "bctgn" not in text and not any(len(eq.outvars[0].aval.shape) > 5 for eq in jax.make_jaxpr(
        functools.partial(ssd_chunked, chunk=32))(x, dt, A, B, C, D).eqns)  # nothing with a group axis
    np.testing.assert_allclose(ssd_chunked(x, dt, A, B[:, :, None], C[:, :, None], D, chunk=32), want, rtol=1e-5, atol=1e-5)
    tiled = ssd_chunked(x, dt, A, *(jnp.repeat(a[:, :, None], 4, axis=2) for a in (B, C)), D, chunk=32)
    np.testing.assert_allclose(tiled, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_chunked(x, dt, A, *(jnp.repeat(a[:, :, None], 3, axis=2) for a in (B, C)), D, chunk=32)


def test_a_one_group_mamba_layer_traces_as_it_did():
    """`ssm_groups` 1 is Granite's layer: the same leaves, and no reshape of B and C into groups."""
    kw = dict(n_layers=1, layer_types=("mamba",), ssm_heads=4, ssm_head_dim=16, ssm_state=8)
    one, two = TransformerConfig.tiny(**kw), TransformerConfig.tiny(ssm_groups=2, **kw)
    shapes = lambda cfg: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.shape, jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))["mamba_layers"]["ssm"])
    assert shapes(one)["in_proj"] == (1, 64, 2 * 64 + 2 * 8 + 4) and shapes(one)["conv_w"] == (1, 64 + 16, 4)
    assert shapes(two)["in_proj"] == (1, 64, 2 * 64 + 4 * 8 + 4) and shapes(two)["conv_w"] == (1, 64 + 32, 4)
    with pytest.raises(ValueError, match="does not divide"):
        TransformerConfig.tiny(ssm_groups=3, **kw)
    # against Granite's reference, which knows one group only
    params = transformer.init_params(one, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, SEQ), 0, one.vocab_size)
    granite = {"layer_types": ["mamba"], "num_hidden_layers": 1, "rms_norm_eps": one.norm_eps, "residual_multiplier": 1.0,
               "position_embedding_type": "nope", "attention_multiplier": 1.0, "embedding_multiplier": 1.0,
               "logits_scaling": 1.0, "tie_word_embeddings": False}
    assert rel(transformer.forward(params, tokens, one), reference_hybrid.logits(granite, params, tokens, last=SEQ)) < RTOL


# -- two-matrix experts, a shared expert of its own width, a share of the experts ---------


def test_relu2_experts_have_two_matrices_and_swiglu_experts_the_three_they_had():
    kw = dict(n_experts=4, experts_per_token=2, moe_d_ff=24, n_shared_experts=2, router_activation="sigmoid")
    swiglu, relu2 = TransformerConfig.tiny(**kw), TransformerConfig.tiny(expert_kind="relu2", shared_expert_d_ff=56, **kw)
    key = jax.random.PRNGKey(7)
    old, new = moe.init_moe_params(swiglu, key), moe.init_moe_params(relu2, key)
    assert list(old) == ["router", "w_gate", "w_up", "w_down", "router_bias", "shared"]
    assert list(old["shared"]) == ["w_gate", "w_up", "w_down"] and old["shared"]["w_up"].shape == (64, 48)  # 2 x 24
    assert list(new) == ["router", "w_up", "w_down", "router_bias", "shared"]
    assert list(new["shared"]) == ["w_up", "w_down"] and new["shared"]["w_up"].shape == (64, 56)  # stated
    for name in ("router", "w_up", "w_down"):  # a leaf keeps its key whatever the form
        np.testing.assert_array_equal(old[name], new[name])
    # `routed_branch_init`: a token's K routed outputs start as ONE residual branch, 1 / sqrt(K) each; no other leaf moves
    branch = moe.init_moe_params(dataclasses.replace(relu2, routed_branch_init=True), key)
    np.testing.assert_allclose(branch["w_down"], new["w_down"] * 2 ** -0.5, rtol=1e-6)
    for name in ("router", "w_up"):
        np.testing.assert_array_equal(branch[name], new[name])
    np.testing.assert_array_equal(branch["shared"]["w_down"], new["shared"]["w_down"])
    for cfg, tree in ((relu2, new), (swiglu, old)):  # one logical-axes tuple a leaf
        axes = jax.tree_util.tree_structure(moe.moe_param_axes(cfg), is_leaf=lambda t: isinstance(t, tuple))
        assert axes == jax.tree_util.tree_structure(tree)
    with pytest.raises(ValueError, match="expert_kind"):
        TransformerConfig.tiny(expert_kind="gelu", **kw)
    # the function: every expert on every row, by hand
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 32, 64))
    with jax.default_matmul_precision("highest"):
        got, _ = moe.moe_ffn(new, x, relu2)
        idx, gates, _ = moe._route(new, x[0], relu2)
        every = jnp.einsum("ntf,nfd->ntd", jnp.square(jax.nn.relu(jnp.einsum("td,ndf->ntf", x[0], new["w_up"]))), new["w_down"])
        weight = jnp.sum(jax.nn.one_hot(idx, 4) * gates[..., None], axis=1)  # [T, E]
        want = jnp.einsum("ntd,tn->td", every, weight) + jnp.square(jax.nn.relu(x[0] @ new["shared"]["w_up"])) @ new["shared"]["w_down"]
    assert rel(got[0], want) < 1e-5


def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_block(tiny):
    """The guide's share test: 16 experts in 8 shares of 2, as the deployment's
    eight chips hold 128 in shares of 16; the shares' routed parts plus the
    shared expert counted ONCE equal the uncut reference's block.  Program and
    reference both."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None)
    key = jax.random.PRNGKey(11)
    whole = moe.init_moe_params(cfg, key)
    whole["router_bias"] = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, SEQ, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    routing = dict(top_k=3, renormalize=True, scaling=cfg.routed_scaling_factor)
    experts_of = lambda first: {k: (v[first: first + 2] if k in ("w_up", "w_down") else v)  # noqa: E731
                                for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        routed_whole, shared = ref.expert_parts(flat, whole, first=0, **routing)
        want = routed_whole + shared
        routed_ref, routed_prog = jnp.zeros_like(flat), jnp.zeros_like(flat)
        for first in range(0, 16, 2):
            part = experts_of(first)
            routed_ref += ref.expert_parts(flat, part, first=first, **routing)[0]
            share = dataclasses.replace(cfg, n_experts_held=2, first_expert_held=first)
            y, stats = moe.moe_ffn(part, x, share)
            assert stats["held_rows"].shape == (2,)
            routed_prog += y.reshape(flat.shape) - shared
        whole_prog, _ = moe.moe_ffn(whole, x, cfg)
    assert float(jnp.abs(routed_ref).max()) > 0.1  # the routed part is no rounding error of the sum
    assert rel(routed_ref + shared, want) < 1e-5
    assert rel(routed_prog + shared, want) < 1e-5
    assert rel(whole_prog.reshape(flat.shape), want) < 1e-5


def test_what_the_experts_were_given_reaches_the_run_record(tiny):
    """`moe_held_rows_*`, `moe_load_max_over_mean` and (PR 48) `moe_rows_moved_share`, newest
    value and step by step (what `relu2_experts_roofline` counts its rows from)."""
    ctx = LMTrainContext(tiny["cfg"], mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    run_record.drain_step_counters(), run_record.drain_step_series()
    state = ctx.init_state(seed=0)
    batch = {"tokens": np.asarray(tiny["tokens"]), "targets": np.asarray(tiny["targets"])}
    for _ in range(3):
        state, metrics = ctx.train_step(state, batch)
    jax.block_until_ready(metrics)
    newest, series = run_record.drain_step_counters(), run_record.drain_step_series()
    assert set(newest) == {"moe_held_rows_mean", "moe_held_rows_max", "moe_load_max_over_mean", "moe_rows_moved_share",
                           "attn_causal_steps_copying_pct", "attn_tiles_unmasked_pct",  # the one attention block's, since PRs 55 and 63
                           "scan_forward_rerun_pct"}  # the Mamba-2 blocks', since PR 64
    assert newest["scan_forward_rerun_pct"] == 0.0  # this step has no checkpoint; under one 100: no kind lists the SSD's names (the cell's reads 100)
    assert newest["moe_rows_moved_share"] == 1.0  # under one row tile of assignments: one rung, all T*K rows
    assert [step for step, _ in series] == [0, 1, 2] and series[-1][1] == newest
    tokens, k, total = tiny["tokens"].size, 3, 16
    assert 0 < newest["moe_held_rows_mean"] <= newest["moe_held_rows_max"] <= tokens
    assert 1.0 <= newest["moe_load_max_over_mean"] <= total / k
    record = run_record.RunRecord({"trace_id": "t"})
    record.add_poll(0, {"reports": [], "step_counters": newest, "step_series": series})
    assert record.to_dict()["step_counter_series"] == series and record.to_dict()["step_counters"] == newest


def test_the_share_runs_sharded_without_its_held_experts(tiny):
    """`fsdp` and `tp` take the new forms (groups replicated like the heads,
    two-matrix experts sharded as three were) where the layer holds ALL its
    experts; a held share is one rank's and refuses a mesh, as Kimi's."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.tile(tiny["tokens"], (2, 1)), "targets": jnp.tile(tiny["targets"], (2, 1))}
    alone = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    want, _ = alone._loss(params, batch)
    for strategy, spec in (("fsdp", MeshSpec(fsdp=4)), ("tp", MeshSpec(data=2, tensor=2))):
        ctx = LMTrainContext(cfg, mesh=build_mesh(spec, devices=jax.devices()[:4]), strategy=strategy)
        with ctx.mesh:
            got, _ = jax.jit(ctx._loss)(params, batch)
        assert abs(float(got) - float(want)) < 1e-4, strategy
    held = LMTrainContext(tiny["cfg"], mesh=build_mesh(MeshSpec(data=2), devices=jax.devices()[:2]), strategy="dp")
    with pytest.raises(ValueError, match="one rank's share"):
        jax.eval_shape(held._loss, tiny["params"], batch)


# -- the grouped matmuls at widths like 2688 and 1856 ---------------------------------------


def test_tiles_stay_for_the_accepted_widths_and_exist_for_the_new_ones():
    tile = gmm_kernels._tile
    assert [tile(d, 1024) for d in (2048, 1024, 2304, 4096, 512, 128)] == [1024, 1024, 256, 1024, 512, 128]  # as they were
    assert tile(2688, 1024) == 896  # 21 x 128: the largest multiple of 128 that divides, where powers of two find 128
    assert tile(384, 1024) == 384
    assert tile(1856, 1024) == 1856  # 29 x 64: one whole block
    assert gmm_kernels.supported(49152, 2688, 1856) and gmm_kernels.supported(49152, 1856, 2688)
    assert not gmm_kernels.supported(49152, 2688, 1850) and not gmm_kernels.supported(49152, 2688, 4104)
    assert not gmm_kernels.supported(100, 256, 256)
    with pytest.raises(ValueError, match="multiples of 128"):
        tile(4104, 1024)


def dense_by_group(lhs, rhs, sizes):
    out, start = [], 0
    for g, size in enumerate(sizes):
        out.append(lhs[start:start + size] @ rhs[g])
        start += size
    return jnp.concatenate(out + [jnp.zeros((lhs.shape[0] - start, rhs.shape[2]), lhs.dtype)])


# k = 384 = 3 x 128 (no power-of-two tile above 128 divides, as 2688), n = 232 = 29 x 8 (no multiple of 128, as 1856)
@pytest.mark.parametrize("form", ["kernels", "xla"])
@pytest.mark.parametrize("shape", [(384, 232), (232, 384)], ids=["k384-n232", "k232-n384"])
@pytest.mark.parametrize("sizes", [[40, 0, 100, 60], [200, 0, 0, 0]], ids=["ragged", "collapsed"])
def test_grouped_matmul_at_unaligned_widths_against_a_dense_loop(form, shape, sizes):
    """Forward and both cotangents; the kernels in interpret mode.  Rows behind
    the last group (the absent experts') are not defined going out and carry
    no gradient coming back.  1e-5: float32 both sides."""
    k, n = shape
    m, groups = 256, len(sizes)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs, rhs = jax.random.normal(ks[0], (m, k)), jax.random.normal(ks[1], (groups, k, n)) * k ** -0.5
    held = (jnp.arange(m) < sum(sizes))[:, None]
    probe = jnp.where(held, jax.random.normal(ks[2], (m, n)), 0)
    gs = jnp.asarray(sizes, jnp.int32)
    assert gmm_kernels.supported(m, k, n)
    mm = gmm_kernels.grouped_matmul if form == "kernels" else gmm_op.grouped_matmul_xla

    def f(lhs, rhs):
        return jnp.sum(jnp.where(held, mm(lhs, rhs, gs), 0) * probe)

    with jax.default_matmul_precision("highest"):
        want = dense_by_group(lhs, rhs, sizes)
        assert rel(jnp.where(held, mm(lhs, rhs, gs), 0), want) < 1e-5
        got_l, got_r = jax.grad(f, argnums=(0, 1))(lhs, rhs)
        want_l, want_r = jax.grad(lambda l, r: jnp.sum(dense_by_group(l, r, sizes) * probe), argnums=(0, 1))(lhs, rhs)
    assert rel(jnp.where(held, got_l, 0), want_l) < 1e-5 and rel(got_r, want_r) < 1e-5


def test_a_shape_the_kernels_refuse_is_never_taken_in_silence(caplog):
    """n = 100 is no multiple of 8: the XLA form runs on every platform, the
    step's HLO says so by name, the shape is counted and logged once."""
    lhs, rhs = jnp.ones((128, 256)), jnp.ones((2, 256, 100))
    gs = jnp.asarray([64, 64], jnp.int32)
    gmm_op.refused_shapes.pop((128, 256, 100), None)
    with caplog.at_level("WARNING", logger=gmm_op.logger.name):
        text = jax.jit(gmm_op.grouped_matmul).lower(lhs, rhs, gs).as_text(debug_info=True)
        jax.jit(lambda *a: gmm_op.grouped_matmul(*a) * 2).lower(lhs, rhs, gs)
    assert gmm_op.REFUSED_SCOPE in text
    assert gmm_op.refused_shapes[128, 256, 100] == 2
    assert sum("refuse" in r.message for r in caplog.records) == 1
    taken = jax.jit(gmm_op.grouped_matmul).lower(lhs, jnp.ones((2, 256, 232)), gs).as_text(debug_info=True)
    assert gmm_op.REFUSED_SCOPE not in taken and (128, 256, 232) not in gmm_op.refused_shapes


# -- the accepted configurations do not move -------------------------------------------------

# num_params and a digest of (path, shape, dtype) of every leaf of `init_params`, from the tree at PR 43
ACCEPTED = {
    "mistral-7b-v0.3-1chip": (1358999552, "e258e8a19aaec3bb"),
    "mistral-7b-v0.3-fsdp4": (5503127552, "6c80bd53b00fbc91"),
    "internlm2-1.8b-1chip": (1385760768, "eff27a737b962d91"),
    "olmoe-1b-7b-0125-1chip": (1464756224, "7e616c16a731a237"),
    "granite-4.0-h-micro-1chip": (951991232, "987298780c0a6ae5"),
    "kimi-linear-48b-a3b-ep16-1chip": (828926848, "ca1b77763ae77360"),
    "phi-4-mini-flash-vp4-1chip": (1176048000, "eb3466f39f331958"),
    "nemotron-3-nano-30b-a3b-ep8-1chip": (986254848, "a6867c21a723571b"),  # new in PR 44
}


@pytest.mark.parametrize("name", list(ACCEPTED))
def test_a_benchmark_configurations_tree_is_what_it_was(name):
    import hashlib
    import importlib

    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        config = json.load(f)
    kw = importlib.import_module("benchmarks.builders." + config["kind"]).model_kwargs(config, 1024)
    cfg = TransformerConfig(**{**kw, "dtype": jnp.dtype(kw["dtype"]), "param_dtype": jnp.dtype(kw["param_dtype"])})
    tree = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    flat = sorted((jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
                  for p, a in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert (cfg.num_params(), hashlib.sha256(repr(flat).encode()).hexdigest()[:16]) == ACCEPTED[name]
