"""Kimi Linear through the program (PERF.md section 4, PR 37): KDA layers 3:1
with NoPE latent attention, a sigmoid router with a stored bias and a shared
expert behind one leading dense layer, the experts HELD here a share of the
experts the router scores.  Held to `benchmarks/lib/reference_kimi_linear.py`
(token-by-token recurrence, plain softmax, its own routing) at tiny widths on
the CPU, seeded weights; on the chip the same comparison decides the cell's
`correct` at the published widths."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import only_the_delta_convolution_runs_its_kernels, without_file_locations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_kimi_linear as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, moe  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.ops.attention import reference_attention  # noqa: E402
from ray_tpu.ops.kda import kda_chunked, kda_recurrent  # noqa: E402
from ray_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 128
# The configuration file's keys at a tiny size: five layers with all three pairs
# (KDA + dense, KDA + experts x3 in two runs, MLA + experts), 4 of 8 experts held from expert 2.
CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "vocab_size": 128, "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "first_k_dense_replace": 1, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 4, "num_experts_per_token": 2,
    "moe_intermediate_size": 32, "num_shared_experts": 1, "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8], "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "share": {"num_experts_total": 8, "first_expert_held": 2},
}
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost


def config_of(published=CONFIG, **kw):
    linear, share = published["linear_attn_config"], published["share"]
    pairs = ref.layer_pairs(published)
    base = dict(
        vocab_size=published["vocab_size"], d_model=published["hidden_size"], n_layers=published["num_hidden_layers"],
        n_heads=published["num_attention_heads"], n_kv_heads=published["num_key_value_heads"],
        d_ff=published["intermediate_size"], max_seq_len=SEQ, rope_theta=None, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, layer_types=tuple(m for m, _ in pairs),
        ffn_types=tuple(f for _, f in pairs), kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"], kv_lora_rank=published["kv_lora_rank"],
        qk_nope_head_dim=published["qk_nope_head_dim"], qk_rope_head_dim=published["qk_rope_head_dim"],
        v_head_dim=published["v_head_dim"], n_experts=share["num_experts_total"],
        n_experts_held=published["num_experts"], first_expert_held=share["first_expert_held"],
        experts_per_token=published["num_experts_per_token"], moe_d_ff=published["moe_intermediate_size"],
        n_shared_experts=published["num_shared_experts"], norm_topk_prob=True, router_activation="sigmoid",
        routed_scaling_factor=published["routed_scaling_factor"],
    )
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (norm scales, the router's bias)
    drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm", "norm", "kv_norm"):
            leaf = 1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name == "router_bias":
            leaf = 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module", autouse=True)
def chunk_of_32():
    """The program's chunk for this module: S = 128 crosses three boundaries."""
    from ray_tpu.ops import kda

    saved, kda.CHUNK = kda.CHUNK, 32
    yield
    kda.CHUNK = saved


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


# -- the model against the reference ----------------------------------------------------


def test_at_heads_of_128_the_layers_convolution_runs_its_kernels_and_no_mamba_2_kernel(monkeypatch):
    """PR 60: at the published head size a KDA layer's q, k and v come from
    `delta_conv`'s kernels (two blocks of positions here), with the same loss
    and gradients as the plain form gives, `conv_w`'s among them: the layer
    hands its weights over by head.  And the step lowered for TPU holds those
    kernels under `kda/conv` and none of `ssm_conv_*`."""
    cfg = config_of(kda_head_dim=128, kda_heads=2, n_layers=2, layer_types=("kda", "kda"), ffn_types=("dense", "experts"),
                    remat=True, remat_policy="qkv_attn")
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    objective = lambda p: ctx._loss(p, batch)[0]  # noqa: E731
    want_loss, want = jax.jit(jax.value_and_grad(objective))(params)
    lowered = jax.jit(jax.grad(objective)).trace(params).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "ssm_conv" not in without_file_locations(lowered)
    assert "kda/conv/cond/branch_0_fun/delta_conv_fwd" in lowered and "delta_conv_bwd" in lowered
    only_the_delta_convolution_runs_its_kernels(monkeypatch)
    loss, got = jax.jit(jax.value_and_grad(objective))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    worst = {jax.tree_util.keystr(path): rel(g, w) for (path, g), w in
             zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)) if float(jnp.abs(w).max()) > 0}
    assert max(worst.values()) < 1e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert any("conv_w" in path for path in worst)


def test_the_stack_is_runs_of_pairs_with_one_parameter_stack_a_pair(tiny):
    cfg = tiny["cfg"]
    assert cfg.layer_runs() == (("kda", "dense", 0, 1), ("kda", "experts", 0, 2), ("mla", "experts", 0, 1),
                                ("kda", "experts", 2, 1))
    assert {k: v[2] for k, v in cfg.stacks().items()} == {"kda_layers_dense": 1, "kda_layers_experts": 3, "mla_layers": 1}
    assert sorted(tiny["params"]) == ["embed", "final_norm", "kda_layers_dense", "kda_layers_experts", "lm_head",
                                      "mla_layers"]
    held = tiny["params"]["kda_layers_experts"]["mlp"]
    assert held["w_gate"].shape[:2] == (3, 4) and held["router"].shape == (3, 64, 8)  # 4 held, the router 8 wide


@pytest.mark.parametrize("pairs", [
    TransformerConfig.tiny(),
    TransformerConfig.tiny(n_experts=4, experts_per_token=2, qk_norm=True),
    TransformerConfig.tiny(n_layers=3, layer_types=("mamba", "attention", "mamba"), ssm_heads=4, ssm_head_dim=16,
                           ssm_state=8, tie_embeddings=True),
    TransformerConfig.tiny(n_layers=3, layer_types=("attention", "mamba", "attention"), ffn_types=("dense", "experts", "experts"),
                           n_experts=4, experts_per_token=2, moe_d_ff=32, n_shared_experts=2, router_activation="sigmoid",
                           ssm_heads=4, ssm_head_dim=16, ssm_state=8),
], ids=["dense", "experts", "hybrid", "attention-with-both-ffns"])
def test_num_params_counts_every_pair(pairs):
    params = jax.eval_shape(lambda: transformer.init_params(pairs, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == pairs.num_params()


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
    assert float(loss_and_grads["terms"]["ce_loss"]) == pytest.approx(float(loss_and_grads["loss"]))


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got if float(jnp.abs(want[p]).max()) > 0}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert len(worst) == len(got) - 2  # every leaf but the two stacks' router bias has a gradient


def test_the_router_bias_gets_a_zero_gradient_and_changes_the_choice_alone(tiny, loss_and_grads):
    for stack in ("kda_layers_experts", "mla_layers"):
        assert float(jnp.abs(loss_and_grads["grads"][stack]["mlp"]["router_bias"]).max()) == 0.0
    cfg = tiny["cfg"]
    mlp = jax.tree_util.tree_map(lambda a: a[0], tiny["params"]["kda_layers_experts"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    idx, gates, _ = moe._route(mlp, h, cfg)
    idx0, gates0, _ = moe._route(dict(mlp, router_bias=jnp.zeros_like(mlp["router_bias"])), h, cfg)
    assert not np.array_equal(np.asarray(idx), np.asarray(idx0))  # the bias moves the choice
    scores = jax.nn.sigmoid(h @ mlp["router"])
    for chosen, value in ((idx, gates), (idx0, gates0)):  # the gate values are the chosen SCORES, bias-free
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        np.testing.assert_allclose(value, picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor, rtol=1e-5)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: 8 experts in 4 shares of 2; the shares' routed
    parts plus the shared expert counted ONCE equal the uncut reference's
    layer output.  Program and reference both."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None)
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
    experts_of = lambda first: {k: (v[first: first + 2] if k in ("w_gate", "w_up", "w_down") else v)  # noqa: E731
                                for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        want = ref._expert_ffn(unit, {"mlp": whole, "ln2": ones}, first=0, **published) - unit
        shared = ref._swiglu(unit, whole["shared"])
        routed_ref, routed_prog = jnp.zeros_like(unit), jnp.zeros_like(unit)
        for first in range(0, 8, 2):
            part = experts_of(first)
            routed_ref += ref._expert_ffn(unit, {"mlp": part, "ln2": ones}, first=first, **published) - unit - shared
            share = dataclasses.replace(cfg, n_experts_held=2, first_expert_held=first)
            y, stats = moe.moe_ffn(part, unit.reshape(x.shape), share)
            assert stats["held_rows"].shape == (2,)
            routed_prog += y.reshape(unit.shape) - shared
        whole_prog, _ = moe.moe_ffn(whole, unit.reshape(x.shape), cfg)
    assert float(jnp.abs(routed_ref).max()) > 0.1  # the routed part is no rounding error of the sum
    assert rel(routed_ref + shared, want) < 1e-5
    assert rel(routed_prog + shared, want) < 1e-5
    assert rel(whole_prog.reshape(unit.shape), want) < 1e-5


def test_held_rows_reach_the_step_metrics(tiny):
    ctx = LMTrainContext(tiny["cfg"], mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    _, terms = ctx._loss(tiny["params"], {"tokens": tiny["tokens"], "targets": tiny["targets"]})
    tokens, k, held, total = tiny["tokens"].size, 2, 4, 8
    assert 0 < float(terms["moe_held_rows_mean"]) <= float(terms["moe_held_rows_max"]) <= tokens
    assert float(terms["moe_held_rows_mean"]) == pytest.approx(tokens * k / total, rel=0.5)  # K*T/E rows an expert


def test_held_experts_refuse_a_mesh_that_shards_the_layer(tiny):
    mesh = build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    ctx = LMTrainContext(tiny["cfg"], mesh=mesh, strategy="dp")
    with pytest.raises(ValueError, match="one rank's share"):
        jax.eval_shape(ctx._loss, tiny["params"], {"tokens": tiny["tokens"], "targets": tiny["targets"]})


# -- the chunked recurrence against the token-by-token one ------------------------------


def kda_inputs(seed, s=192, decay=1.0, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


KDA_TOL = 1e-5


@pytest.mark.parametrize("decay", [1e-3, 1.0, 40.0], ids=["decay-near-1", "decay-mid", "decay-near-0"])
def test_chunked_kda_is_the_recurrence_forward_and_gradient(decay):
    """`decay` scales the log decay: at 1e-3 a channel keeps 99.9% a token, at
    40 it keeps e^-28 (a quotient of exponentials would be 0/0 inside a chunk)."""
    args = kda_inputs(0, decay=decay)
    want = kda_recurrent(*args)
    got = kda_chunked(*args, chunk=64)
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) < KDA_TOL
    probe = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = jax.grad(lambda *a: jnp.sum(kda_chunked(*a, chunk=64) * probe), argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(kda_recurrent(*a) * probe), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, b) < 1e-4, name


def test_chunked_kda_admits_only_whole_power_of_two_chunks():
    args = kda_inputs(1, s=96)
    np.testing.assert_allclose(kda_chunked(*args, chunk=32), kda_recurrent(*args), atol=1e-5)  # 96 = 3 chunks of 32
    short = kda_inputs(1, s=16)
    np.testing.assert_allclose(kda_chunked(*short), kda_recurrent(*short), atol=1e-5)  # the chunk is cut to S
    with pytest.raises(ValueError, match="power-of-two chunk"):
        kda_chunked(*args, chunk=64)  # 96 is not a multiple of 64
    with pytest.raises(ValueError, match="power-of-two chunk"):
        kda_chunked(*args, chunk=48)


def test_the_comparison_notices_decays_in_bfloat16():
    """The precision case: the log decay rounded to bf16 (8 bits) moves the
    output by more than the tolerance the comparisons above use, where
    float32 stays under it by an order of magnitude."""
    q, k, v, g, beta = kda_inputs(3)
    want = kda_recurrent(q, k, v, g, beta)
    assert rel(kda_chunked(q, k, v, g, beta), want) < KDA_TOL / 5
    assert rel(kda_chunked(q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta), want) > 10 * KDA_TOL


def test_the_model_comparison_notices_a_layer_in_bfloat16(tiny):
    """The model-level tolerance is tight enough too: the program computing in
    bf16 from the same weights lands far over RTOL.  (At this toy width a
    flipped choice among 8 experts moves a logit more than bf16's rounding
    does, so the chip's `0.012 * sqrt(L)` is no upper bound here.)"""
    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16)
    want = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)
    assert rel(transformer.forward(tiny["params"], tiny["tokens"], cfg), want) > 10 * RTOL


# -- the flash kernels at two head sizes --------------------------------------------------


def attention_inputs(d_qk, d_v, s=256, b=1, h=2):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    return (jax.random.normal(ks[0], (b, s, h, d_qk)), jax.random.normal(ks[1], (b, s, h, d_qk)),
            jax.random.normal(ks[2], (b, s, h, d_v)), jax.random.normal(ks[3], (b, s, h, d_v)))


def _flash(q, k, v):
    return flash_attention(q, k, v, block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=128)


@pytest.mark.parametrize("sizes", [(192, 128), (64, 128), (128, 128)], ids=["192-128", "64-128", "128-128"])
def test_flash_kernels_take_two_head_sizes_forward_and_backward(sizes):
    """Interpret mode, against plain softmax: q/k heads of one size, v heads
    of another, no padding of v."""
    q, k, v, do = attention_inputs(*sizes)
    got, want = _flash(q, k, v), reference_attention(q, k, v)
    assert got.shape == (*q.shape[:3], sizes[1])
    np.testing.assert_allclose(got, want, atol=2e-6)
    grads = jax.grad(lambda *a: jnp.sum(_flash(*a) * do), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(reference_attention(*a) * do), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, wants):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


def _pallas_calls(jaxpr, found=None):
    """{kernel name: the block shapes of its operands and results} of every
    `pallas_call` in a jaxpr, nested calls and branches included."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            blocks = [tuple(int(getattr(d, "block_size", d)) for d in bm.block_shape) for bm in mapping.block_mappings]
            found.setdefault(eqn.params["name"], blocks)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def test_flash_kernels_at_one_head_size_are_the_calls_they_were():
    """At d_qk == d_v the three `pallas_call`s have the blocks the one-size
    kernels had, operand for operand (q, k, v[, do, lse, delta] then the
    results), and at (192, 128) only v, do, the output and dv change.  (That
    the six accepted cells' compiled steps are the parent's, instruction for
    instruction, is the AOT comparison of PERF.md section 6, PR 37.)"""
    def blocks(d_qk, d_v):
        q, k, v, do = attention_inputs(d_qk, d_v)
        grad = jax.grad(lambda *a: jnp.sum(_flash(*a) * do), argnums=(0, 1, 2))
        return _pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)

    row = lambda d: (1, 1, 128, d)  # noqa: E731
    one = (1, 1, 128, 1)
    assert blocks(128, 128) == {
        "flash_fwd": [row(128)] * 3 + [row(128), one],
        "flash_bwd_dq": [row(128)] * 4 + [one, one, row(128)],
        "flash_bwd_dkv": [row(128)] * 4 + [one, one, row(128), row(128)],
    }
    assert blocks(192, 128) == {
        "flash_fwd": [row(192), row(192), row(128), row(128), one],
        "flash_bwd_dq": [row(192), row(192), row(128), row(128), one, one, row(192)],
        "flash_bwd_dkv": [row(192), row(192), row(128), row(128), one, one, row(192), row(128)],
    }


def test_dispatch_takes_the_kernel_for_latent_attentions_sizes():
    """`dot_product_attention` hands (192, 128) heads to the flash kernel in a
    step lowered for TPU, as it does one size."""
    from ray_tpu.ops.attention import dot_product_attention

    q, k, v, _ = attention_inputs(192, 128)
    lowered = jax.jit(dot_product_attention).trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text()
    assert lowered.count("tpu_custom_call") >= 1 and "flash_fwd" in lowered
