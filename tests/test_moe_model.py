"""The sparse-expert decoder (OLMoE's block) against its plain float32
reference (`benchmarks/lib/reference_moe.py`), at tiny widths on the CPU:
logits, the training objective, every gradient leaf, each loss term, dropless
under total imbalance (the router's gradient too), no gate renormalisation,
the bf16 tolerance shown to bite, what the checkpointed layer's backward
recomputes of the expert block, expert parallelism on a CPU mesh,
optimizer-state shardings by path, the grouped-matmul kernels, and the dense
model's program left as it was.

Weights are seeded through the program's own `init_state`; the norm scales,
which start at one, are then drawn at random so a scale that is never applied
cannot pass.
"""

import collections
import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # `benchmarks.lib` resolves from this checkout

from benchmarks.lib import reference_moe, trace_moe, trace_scopes  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm  # noqa: E402
from ray_tpu.ops.pallas import grouped_matmul as kernels  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

BASE = dict(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=32, max_seq_len=32,
    dtype=jnp.float32, param_dtype=jnp.float32, remat=False, n_experts=8, experts_per_token=2,
    qk_norm=True, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
)
NORM_LEAVES = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def reference_config(cfg: TransformerConfig) -> dict:
    """The published key names `reference_moe` reads, from a TransformerConfig."""
    return {
        "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob, "router_aux_loss_coef": cfg.router_aux_loss_coef,
        "router_z_loss_coef": cfg.router_z_loss_coef,
    }


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


def seeded_params(ctx, seed=0):
    params = ctx.init_state(seed)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        if path[-1].key in NORM_LEAVES:
            return (leaf * (1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape))).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def _state_with(ctx, params):
    """A fresh state holding a COPY of `params` on ctx's mesh (the step donates its state)."""
    copy = jax.device_put(jax.tree_util.tree_map(np.asarray, params), ctx.param_shardings)
    return dict(ctx.init_state(0), params=copy)


def batch_of(cfg, seed=0, batch=2, seq=32):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def tiny():
    """Program and reference on the same float32 weights and batch: logits,
    objective, terms and gradients of both."""
    cfg = TransformerConfig(**BASE)
    ctx = one_device_ctx(cfg)
    params, batch = seeded_params(ctx), batch_of(cfg)
    rcfg = reference_config(cfg)
    (loss, terms), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(params, batch)
    (ref_loss, ref_terms), ref_grads = jax.value_and_grad(
        lambda p: reference_moe.objective(rcfg, p, batch["tokens"], batch["targets"]), has_aux=True)(params)
    chosen = []
    ref_logits = reference_moe.logits(rcfg, params, batch["tokens"], last=32, record=chosen)
    return dict(cfg=cfg, ctx=ctx, params=params, batch=batch, rcfg=rcfg, logits=ctx.apply(params, batch["tokens"]),
                ref_logits=ref_logits, loss=loss, terms=terms, grads=grads, ref_loss=ref_loss,
                ref_terms=ref_terms, ref_grads=ref_grads, chosen=chosen)


# -- forward, objective, gradients ------------------------------------------------------


def test_logits_equal_the_reference(tiny):
    """Same arithmetic in another order (sort-and-group against a mask per
    expert): float32 on both sides, rtol 1e-4 of the logits' scale."""
    want = np.asarray(tiny["ref_logits"])
    np.testing.assert_allclose(np.asarray(tiny["logits"]), want, rtol=1e-4, atol=1e-4 * np.abs(want).max())


def test_objective_equals_the_reference(tiny):
    np.testing.assert_allclose(float(tiny["loss"]), float(tiny["ref_loss"]), rtol=1e-5)


@pytest.mark.parametrize("term", ["ce_loss", "moe_lb_loss", "moe_z_loss"])
def test_each_loss_term_equals_the_reference(tiny, term):
    np.testing.assert_allclose(float(tiny["terms"][term]), float(tiny["ref_terms"][term]), rtol=1e-5)


def test_router_losses_are_in_the_objective_with_their_coefficients(tiny):
    t, cfg = tiny["terms"], tiny["cfg"]
    want = float(t["ce_loss"]) + cfg.router_aux_loss_coef * float(t["moe_lb_loss"]) \
        + cfg.router_z_loss_coef * float(t["moe_z_loss"])
    np.testing.assert_allclose(float(tiny["loss"]), want, rtol=1e-6)
    assert float(t["moe_lb_loss"]) >= cfg.experts_per_token - 1e-4  # K at perfect balance, more otherwise


def test_load_max_over_mean_is_the_busiest_expert_of_the_worst_layer(tiny):
    cfg = tiny["cfg"]
    worst = 0.0
    for chosen in tiny["chosen"]:  # the reference's own routing, [N, S, K] per layer
        load = np.bincount(np.asarray(chosen).reshape(-1), minlength=cfg.n_experts)
        worst = max(worst, load.max() / load.mean())
    np.testing.assert_allclose(float(tiny["terms"]["moe_load_max_over_mean"]), worst, rtol=1e-6)


LEAVES = ["embed/tokens", "layers/attn/wq", "layers/attn/wk", "layers/attn/wv", "layers/attn/wo",
          "layers/attn/q_norm", "layers/attn/k_norm", "layers/mlp/router", "layers/mlp/w_gate",
          "layers/mlp/w_up", "layers/mlp/w_down", "layers/ln1", "layers/ln2", "final_norm", "lm_head"]


def test_the_leaf_list_is_every_leaf(tiny):
    paths = {"/".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(tiny["params"])[0]}
    assert paths == set(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"], tiny["ref_grads"]
    for key in leaf.split("/"):
        got, want = got[key], want[key]
    want = np.asarray(want)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-5 * np.abs(want).max())


def test_train_step_reports_the_terms_and_differentiates_the_objective(tiny):
    ctx = tiny["ctx"]
    _, metrics = ctx.train_step(_state_with(ctx, tiny["params"]), tiny["batch"])
    assert set(metrics) == {"loss", "grad_norm", "step", "ce_loss", "moe_lb_loss", "moe_z_loss",
                            "moe_load_max_over_mean", "attn_causal_steps_copying_pct", "attn_tiles_unmasked_pct"}
    np.testing.assert_allclose(float(metrics["loss"]), float(tiny["ref_loss"]), rtol=1e-5)
    ref_norm = math.sqrt(sum(float(jnp.sum(g ** 2)) for g in jax.tree_util.tree_leaves(tiny["ref_grads"])))
    np.testing.assert_allclose(float(metrics["grad_norm"]), ref_norm, rtol=1e-3)


# -- dropless, and the gates as published ---------------------------------------------------


def totally_imbalanced(cfg):
    """(ctx, params, batch, reference logits) with a router biased so EVERY
    token of every layer picks the same K experts, as the reference routes."""
    ctx = one_device_ctx(cfg)
    params = seeded_params(ctx)
    # one hidden coordinate large and positive for every token, and a router
    # that reads it into the first K experts
    params["embed"]["tokens"] = params["embed"]["tokens"].at[:, 0].set(8.0)
    params["layers"]["ln2"] = params["layers"]["ln2"].at[:, 0].set(1.0)
    params["layers"]["mlp"]["router"] = params["layers"]["mlp"]["router"].at[:, 0, :cfg.experts_per_token].add(4.0)
    batch = batch_of(cfg)
    chosen = []
    want = reference_moe.logits(reference_config(cfg), params, batch["tokens"], last=32, record=chosen)
    for layer in chosen:
        assert set(np.asarray(layer).reshape(-1).tolist()) == set(range(cfg.experts_per_token))
    return ctx, params, batch, want


def test_dropless_under_total_imbalance_equals_the_reference():
    """Nothing is dropped: the logits still equal the reference."""
    ctx, params, batch, want = totally_imbalanced(TransformerConfig(**BASE))
    got = ctx.apply(params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("norm_topk_prob", [False, True], ids=["as-published", "renormalised"])
def test_router_gradient_under_total_imbalance_equals_the_reference(norm_topk_prob):
    """The gate values reach the output through the rows that enter `w_down`,
    in expert order: their gradient comes back through the inverse
    permutation.  Held where two groups hold every row and the other six are empty."""
    cfg = TransformerConfig(**dict(BASE, norm_topk_prob=norm_topk_prob))
    ctx, params, batch, _ = totally_imbalanced(cfg)
    got = jax.jit(jax.grad(lambda p: ctx._loss(p, batch)[0]))(params)["layers"]["mlp"]["router"]
    want = np.asarray(jax.grad(lambda p: reference_moe.objective(
        reference_config(cfg), p, batch["tokens"], batch["targets"])[0])(params)["layers"]["mlp"]["router"])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-5 * np.abs(want).max())


def test_dropless_layer_has_no_zero_rows_when_one_expert_takes_every_token():
    cfg = TransformerConfig(**dict(BASE, experts_per_token=1))
    params = moe.init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model))) + 0.1
    params["router"] = jnp.zeros_like(params["router"]).at[:, 3].set(1.0)  # all 256 tokens to expert 3
    y, stats = jax.jit(lambda p, h: moe.moe_ffn(p, h, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(stats["choice_share"])[0], np.eye(cfg.n_experts)[3], atol=0)
    assert np.all(np.abs(np.asarray(y)).max(axis=-1) > 0)  # the old capacity of 1.25*T*K/E rows would zero 216 of 256
    gate = jax.nn.softmax(x.reshape(-1, cfg.d_model).astype(jnp.float32) @ params["router"], axis=-1)[:, 3:4]
    h = x.reshape(-1, cfg.d_model)
    want = gate * ((jax.nn.silu(h @ params["w_gate"][3]) * (h @ params["w_up"][3])) @ params["w_down"][3])
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), np.asarray(want), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("program_norm,reference_norm,equal", [(False, False, True), (True, True, True),
                                                               (True, False, False)],
                         ids=["as-published", "both-renormalise", "program-renormalises"])
def test_norm_topk_prob_false_means_no_renormalisation(program_norm, reference_norm, equal):
    """`norm_topk_prob` false: the K gate values are NOT renormalised.  A
    program that renormalises is far outside what the comparison allows."""
    cfg = TransformerConfig(**dict(BASE, norm_topk_prob=program_norm))
    ctx = one_device_ctx(cfg)
    params, batch = seeded_params(ctx), batch_of(cfg)
    rcfg = dict(reference_config(cfg), norm_topk_prob=reference_norm)
    err = rel_rms(ctx.apply(params, batch["tokens"]),
                  reference_moe.logits(rcfg, params, batch["tokens"], last=32))
    assert (err < 1e-4) if equal else (err > 1e-2), err


# -- bf16 against the float32 reference: the benchmark's tolerance, shown to bite --------------


@pytest.fixture(scope="module")
def bf16_case():
    """OLMoE's routing (64 experts, top-8) at a toy width, three layers, the
    program in bf16 from bf16 weights as the benchmark's cells run it."""
    cfg = TransformerConfig(**dict(
        BASE, vocab_size=512, d_model=256, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=128, max_seq_len=128,
        n_experts=64, experts_per_token=8, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    ctx = one_device_ctx(cfg)
    params = seeded_params(ctx)
    tokens = batch_of(cfg, batch=2, seq=128)["tokens"]
    want = reference_moe.logits(reference_config(cfg), params, tokens, last=128)
    return cfg, params, tokens, want


def test_bf16_program_is_inside_the_tolerance_of_the_independent_reference(bf16_case):
    cfg, params, tokens, want = bf16_case
    err = rel_rms(one_device_ctx(cfg).apply(params, tokens), want)
    assert err <= reference_moe.tolerance(cfg.n_layers), err


def test_a_program_that_routes_to_seven_experts_is_outside_the_tolerance(bf16_case):
    """The same weights, one expert fewer per token: leaving out part of the
    mathematics does not fit inside what bf16 is allowed."""
    cfg, params, tokens, want = bf16_case
    degraded = dataclasses.replace(cfg, experts_per_token=7)
    err = rel_rms(one_device_ctx(degraded).apply(params, tokens), want)
    assert err > reference_moe.tolerance(cfg.n_layers), err


# -- what the checkpointed layer's backward asks the expert block for again -------------------


def test_the_recompute_holds_two_grouped_products_and_no_un_permute():
    """The gate value scales the rows that ENTER `w_down`, so no residual of
    the layer's backward lies behind the down projection: under `qkv_attn`
    (which saves nothing of the FFN) the recompute runs `gate` and `up` and
    stops, where the forward runs three grouped products and the un-permute
    gather.  Counted in the compiled loss-and-gradient (the CPU form: one
    `dot` per grouped product), by scope and direction from `op_name`; a
    gate multiply after the un-permute gives 3 and 1."""
    cfg = TransformerConfig(**dict(BASE, remat=True, remat_policy="qkv_attn"))
    ctx = one_device_ctx(cfg)
    params = jax.eval_shape(lambda: ctx.init_state(0)["params"])
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True)).lower(
        params, {"tokens": toks, "targets": toks}).compile().as_text()
    ops = collections.Counter()  # by the benchmark's own reading of an `op_name` (PERF.md section 3)
    for opcode, path in re.findall(r' (dot|gather)\(.*op_name="([^"]*)"', text):
        ops[trace_moe.classify(path), trace_scopes.classify(path)[1], opcode] += 1
    assert ops["moe/experts", "fwd", "dot"] == 3 and ops["moe/combine", "fwd", "gather"] == 1  # the counter sees them
    assert ops["moe/experts", "recompute", "dot"] == 2
    assert ops["moe/combine", "recompute", "gather"] == 0


MOVEMENTS = {  # the module's form, the same written as plain indexing, the operand's shape
    "to_expert_order": (lambda x, o, i: moe._to_expert_order(x, o, i, 4), lambda x, o, i: x[o // 4], (16, 8)),
    "to_token_order": (lambda x, o, i: moe._to_token_order(x, o, i, 4),
                       lambda x, o, i: x[i].reshape(16, 4, 8).sum(axis=1), (64, 8)),
    "permuted": (lambda x, o, i: moe._permuted(x, i, o), lambda x, o, i: x[o], (64,)),
}


@pytest.mark.parametrize("name", MOVEMENTS)
def test_each_movement_and_its_declared_transpose_equal_plain_indexing(name):
    """Rows to expert order, rows back (summed over K) and the gate values'
    sort: value and gradient are those of the gather each stands for."""
    ours, plain, shape = MOVEMENTS[name]
    rng = np.random.default_rng(0)
    order = jnp.asarray(rng.permutation(64), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ct = jnp.asarray(rng.standard_normal(plain(x, order, inverse).shape), jnp.float32)
    for f in (lambda x, g: g(x, order, inverse), lambda x, g: jax.grad(lambda v: jnp.sum(g(v, order, inverse) * ct))(x)):
        np.testing.assert_allclose(np.asarray(f(x, ours)), np.asarray(f(x, plain)), rtol=1e-6, atol=1e-6)


# -- across devices -----------------------------------------------------------------------


@pytest.fixture(scope="module")
def ep_ctx():
    cfg = TransformerConfig(**BASE)
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=2, expert=4)), strategy="ep")


def test_ep_train_step_equals_the_single_device_step(tiny, ep_ctx):
    batch = tiny["batch"]
    one = tiny["ctx"]
    _, want = one.train_step(_state_with(one, tiny["params"]), batch)
    _, got = ep_ctx.train_step(_state_with(ep_ctx, tiny["params"]), batch)
    for key in ("loss", "ce_loss", "moe_lb_loss", "moe_z_loss", "moe_load_max_over_mean", "grad_norm"):
        np.testing.assert_allclose(float(got[key]), float(want[key]), rtol=2e-4, err_msg=key)


@pytest.mark.parametrize("routing", ["to-one-rank", "leaning"])
def test_ranks_of_the_expert_axis_in_different_rungs_equal_the_single_device_layer(ep_ctx, small_rungs, routing):
    """Under `shard_map` each rank of the `expert` axis sizes its buffers by
    ITS count, so the switch's index differs by rank (PR 48): 64 tokens a
    data shard, 2 choices, 4 ranks of 2 experts, rungs of 64 and 128 rows.
    With every token choosing experts 0 and 1, rank 0 holds all 128
    assignments (the top rung) and the others none (the lowest); with only
    the FIRST choice fixed rank 0 holds over half and the others share the
    rest.  Output and every gradient equal the layer's with all experts on
    one device."""
    cfg = ep_ctx.config
    assert moe._rungs(64 * 2, 2, 8, 2) == (64, 128)
    params = moe.init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    x = x.at[..., 0].set(4.0)  # one direction every token has: the router's row 0 decides
    params["router"] = params["router"].at[0].set(jnp.zeros(8).at[:2 if routing == "to-one-rank" else 1].set(8.0))
    idx = moe._route(params, x.reshape(-1, cfg.d_model), cfg)[0].reshape(2, 128)  # [data shard, assignments]
    rows = np.stack([np.sum(np.asarray(idx) // 2 == rank, axis=1) for rank in range(4)])  # [rank, data shard]
    rungs_taken = {int(np.searchsorted([64, 128], count)) for count in rows.reshape(-1)}
    assert rungs_taken == {0, 1}
    if routing == "to-one-rank":
        assert rows[0].tolist() == [128, 128] and not rows[1:].any()
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def value_and_grads(**where):
        return jax.jit(jax.value_and_grad(lambda p, v: jnp.sum(moe.moe_ffn(p, v, cfg, **where)[0] * ct), argnums=(0, 1)))(params, x)

    want, got = value_and_grads(), value_and_grads(rules=ep_ctx.rules, mesh=ep_ctx.mesh)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_rows_behind_the_last_group_reach_no_output_and_no_gradient(monkeypatch):
    """A rank of the `expert` axis computes its own experts' groups, and a
    grouped matmul defines nothing behind the last of them (the XLA form
    happens to write zeros there, a kernel need not).  With a grouped matmul
    that writes a large number there, in its output and in the gradient of
    its rows, the rank's output and every gradient, the gate values' too,
    are what they are with zeros."""
    cfg = TransformerConfig(**BASE)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    weights = moe.init_moe_params(cfg, keys[0])
    tokens = jax.random.normal(keys[1], (64, cfg.d_model))
    idx, gates, _ = moe._route(weights, tokens, cfg)
    first, n_local = 2, 2  # the second of four ranks
    local = [weights[k][first:first + n_local] for k in ("w_gate", "w_up", "w_down")]
    ct = jax.random.normal(keys[2], tokens.shape)

    def with_behind(fill):
        def behind(x, sizes):
            return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None], x, fill)

        @jax.custom_vjp
        def grouped(lhs, rhs, sizes):
            return behind(gmm.grouped_matmul_xla(lhs, rhs, sizes), sizes)

        def fwd(lhs, rhs, sizes):
            out, pullback = jax.vjp(lambda l, r: gmm.grouped_matmul_xla(l, r, sizes), lhs, rhs)
            return behind(out, sizes), (pullback, sizes)

        def bwd(res, g):
            d_lhs, d_rhs = res[0](g)
            return behind(d_lhs, res[1]), d_rhs, None

        grouped.defvjp(fwd, bwd)
        monkeypatch.setattr(moe, "grouped_matmul", grouped)
        return jax.value_and_grad(
            lambda t, g, w: jnp.sum(moe._experts(t, idx, g, w, cfg.n_experts, first)[0] * ct), argnums=(0, 1, 2),
        )(tokens, gates, local)

    want, got = with_behind(0.0), with_behind(1e3)
    assert float(jnp.abs(want[1][1]).max()) > 0  # the gate values of this rank's assignments have a gradient
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_every_moment_leaf_has_its_parameters_sharding_under_ep(ep_ctx):
    """Pinned by tree path: `w_gate` and `w_up` share a shape, and a shape
    cannot say whose moment a leaf is."""
    state = ep_ctx.init_state(0)
    params = {path: leaf.sharding for path, leaf in jax.tree_util.tree_flatten_with_path(state["params"])[0]}
    expert_sharded = moments = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state["opt_state"])[0]:
        tail = next((path[i:] for i in range(len(path)) if path[i:] in params), None)
        if tail is None:
            assert leaf.ndim == 0  # a step count
            continue
        moments += 1
        assert leaf.sharding.is_equivalent_to(params[tail], leaf.ndim), (path, leaf.sharding, params[tail])
        expert_sharded += "expert" in str(leaf.sharding.spec)
    assert moments == 2 * len(params) and expert_sharded == 2 * 4  # mu and nu of router, w_gate, w_up, w_down


# -- a share of the experts: buffers sized by the rows held (PR 48) ---------------------------

SHARE = dict(tokens=64, d=32, width=16, k=4, experts=16, held=2)  # T*K = 256 assignments; row tile 8: rungs 64, 128, 256
COUNTS = {"none": 0, "one": 1, "edge": 64, "edge+1": 65, "all": 256}
# PR 53: a share of ONE assignment a token (4 of 16 held, 4 choices): a uniform router gives it 64 rows, rungs 80, 256
FAT_SHARE = dict(SHARE, held=4)
FAT_COUNTS = {"held between 1x and 1.25x of uniform takes the first rung": 70, "held just over 1.25x takes the next rung": 81}


@pytest.fixture
def small_rungs(monkeypatch):
    """The ladder at the tests' sizes: a row tile of 8 where the kernels' is 512 (steered here, not by an option)."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    rungs = moe._rungs(SHARE["tokens"] * SHARE["k"], SHARE["held"], SHARE["experts"], SHARE["k"])
    assert rungs == (64, 128, 256)
    return rungs


def _share_case(kind, rows_held, seed=0, share=SHARE):
    """Tokens, a routing with exactly `rows_held` assignments to the held experts, gate values and one share's weights."""
    c, rng = share, np.random.default_rng(seed)
    n = c["tokens"] * c["k"]
    flat = rng.integers(c["held"], c["experts"], size=n)
    flat[rng.permutation(n)[:rows_held]] = rng.integers(0, c["held"], size=rows_held)
    into = 1 if kind == "relu2" else 2
    weights = [jnp.asarray(0.2 * rng.standard_normal((c["held"], c["d"], c["width"])), jnp.float32) for _ in range(into)]
    weights.append(jnp.asarray(0.2 * rng.standard_normal((c["held"], c["width"], c["d"])), jnp.float32))
    return (jnp.asarray(rng.standard_normal((c["tokens"], c["d"])), jnp.float32), jnp.asarray(flat.reshape(-1, c["k"]), jnp.int32),
            jnp.asarray(rng.random((c["tokens"], c["k"])), jnp.float32), weights)


def _share_value_and_grads(tokens, idx, gates, weights, wrap):
    def objective(t, g, w):
        y, rows, moved = moe._experts(t, idx, g, w, SHARE["experts"], 0)
        return jnp.sum(jnp.sin(y)), (y, rows, moved)

    if wrap == "qkv_attn":
        from ray_tpu.models.transformer import _remat_policy
        objective = jax.checkpoint(objective, policy=_remat_policy(TransformerConfig(**dict(BASE, remat_policy="qkv_attn"))))
    return jax.jit(jax.value_and_grad(objective, argnums=(0, 1, 2), has_aux=True))(tokens, gates, weights)


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("share,rows_held,wrap", [*((SHARE, n, wrap) for wrap in ("plain", "qkv_attn") for n in COUNTS.values()),
                                                  *((FAT_SHARE, n, "qkv_attn") for n in FAT_COUNTS.values())],
                         ids=[*(f"{name}-{wrap}" for wrap in ("plain", "qkv_attn") for name in COUNTS), *FAT_COUNTS])
def test_a_share_sized_by_its_rows_equals_the_full_size_path(small_rungs, monkeypatch, share, rows_held, wrap, kind):
    """Output and every gradient (tokens, gate values, each expert matrix) of
    the share on the rung its count picks, against the same share on ONE rung
    of all T*K rows (the form before PR 48), at no row held, one, a rung's
    edge, one more, and every assignment held (none dropped); both kinds of
    expert; alone and inside a `jax.checkpoint` under `qkv_attn`'s policy.
    And a four-way share of one assignment a token (PR 53) on its 1.25x rung
    and, one row over it, on the next, which for such a share is all T*K
    (under `qkv_attn`, as the cell that has such a share runs it)."""
    rungs = moe._rungs(256, share["held"], share["experts"], share["k"])
    assert rungs == ((64, 128, 256) if share is SHARE else (80, 256))
    case = _share_case(kind, rows_held, share=share)
    (_, (y, rows, moved)), grads = _share_value_and_grads(*case, wrap)
    assert int(rows.sum()) == rows_held
    assert float(moved) == next(r for r in rungs if r >= rows_held) / 256
    monkeypatch.setattr(moe, "_rungs", lambda assignments, *_: (assignments,))
    (_, (want_y, _, all_moved)), want_grads = _share_value_and_grads(*case, wrap)
    assert float(all_moved) == 1.0
    if rows_held:
        assert all(float(jnp.abs(v).max()) > 1e-3 for v in jax.tree_util.tree_leaves((want_y, want_grads)))  # the held rows count
    # ONE case has a limit of its own, on the expert matrices' gradients alone (leaves 3 on: sums over the rung's ROWS): relu2 at
    # 70 rows on the rung of 80.  d w_up reads 4.8e-6 off at an element of 0.014 in a leaf whose largest is 26 (float32's step
    # there is 1.9e-6), seeds 0-3 up to 1.1e-5: the CPU's dot sums 80 rows in other blocks than 256, and both forms stand
    # 1.1e-5 from the same layer fed float64.  Output, tokens' and gate values' gradients keep the limit every other case has
    own = (share is FAT_SHARE, rows_held, kind) == (True, 70, "relu2")
    for leaf, (got, want) in enumerate(zip(jax.tree_util.tree_leaves((y, grads)), jax.tree_util.tree_leaves((want_y, want_grads)))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-5 if own and leaf >= 3 else 1e-6)


SIZED_MOVEMENTS = {  # the module's form, the same written as plain indexing (rows behind `held` zero), the operand's shape
    "rows_of_tokens": (lambda x, o, i, h: moe._rows_of_tokens(x, o, i, h, 4, 24),
                       lambda x, o, i, h: jnp.where((jnp.arange(24) < h)[:, None], x[o[:24] // 4], 0), (16, 8)),
    "tokens_of_rows": (lambda x, o, i, h: moe._tokens_of_rows(x, o, i, h, 4, 24),
                       lambda x, o, i, h: jnp.zeros((16, 8)).at[o[:24] // 4].add(jnp.where((jnp.arange(24) < h)[:, None], x, 0)),
                       (24, 8)),
}


@pytest.mark.parametrize("held", [0, 1, 17, 24])
@pytest.mark.parametrize("name", SIZED_MOVEMENTS)
def test_each_sized_movement_and_its_declared_transpose_equal_plain_indexing(name, held):
    """24 rows of the 64 assignments, of which `held` count: value and
    gradient are those of the masked gather and of the scatter-add it
    transposes to, whatever the rows behind the count hold."""
    ours, plain, shape = SIZED_MOVEMENTS[name]
    rng = np.random.default_rng(0)
    order = jnp.asarray(rng.permutation(64), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ct = jnp.asarray(rng.standard_normal(plain(x, order, inverse, held).shape), jnp.float32)
    for f in (lambda x, g: g(x, order, inverse, held),
              lambda x, g: jax.grad(lambda v: jnp.sum(g(v, order, inverse, held) * ct))(x)):
        np.testing.assert_allclose(np.asarray(f(x, ours)), np.asarray(f(x, plain)), rtol=1e-6, atol=1e-6)


LADDERS = {  # (T*K, held, experts, K) -> the rungs
    "kimi-linear-ep16-1chip.seq16k: half an assignment a token, the parent's ladder": ((131072, 16, 256, 8), (16384, 32768, 65536, 131072)),
    "nemotron3-nano-ep8-1chip.seq8k: 0.75 of an assignment a token, the parent's ladder": ((49152, 16, 128, 6), (12288, 24576, 49152)),
    "mellum2-ep4-1chip.seq16k: two assignments a token, 1.25x the uniform 32,768 in the 2x rung's place": ((131072, 16, 64, 8), (40960, 131072)),
    "half the experts held: 2x is T*K itself, 1.25x alone under it": ((131072, 32, 64, 8), (81920, 131072)),
    "an eighth held, one assignment a token: 1.25x in the 2x rung's place, the rest as it was": ((131072, 8, 64, 8), (20480, 65536, 131072)),
    "a sixteenth held, 16 choices: four rungs stay four": ((131072, 4, 64, 16), (10240, 32768, 65536, 131072)),
    "a thin share: never more than four, the last all T*K": ((65536, 1, 256, 8), (512, 1024, 2048, 65536)),
    "one assignment a token exactly, 1.25x and 2x in one tile: the parent's ladder": ((3200, 1, 16, 16), (512, 1024, 2048, 3200)),
    "1.25x rounds up to T*K: one rung, no switch": ((1024, 8, 16, 2), (1024,)),
    "under one tile: one rung, no switch": ((128, 2, 8, 2), (128,)),
}


@pytest.mark.parametrize("sizes,want", LADDERS.values(), ids=LADDERS.keys())
def test_the_ladder_starts_at_the_balanced_or_twice_the_uniform_share_and_has_at_most_four_rungs(sizes, want):
    """The ladder at the three share cells' sizes and at thin shares: twice the
    uniform share in whole row tiles, doubling, T*K last, four at most; where
    the share is at least one assignment a token the first rung is 1.25x the
    uniform share instead (PR 53), if that is under T*K (that a count takes
    the first rung that holds it: `moved` in the test above)."""
    rungs = moe._rungs(*sizes)
    assert rungs == want
    assert list(rungs) == sorted(set(rungs)) and rungs[-1] == sizes[0] and len(rungs) <= moe._RUNGS
    assert all(r % moe._ROW_TILE == 0 for r in rungs[:-1])


def test_the_all_experts_form_holds_no_switch(small_rungs):
    """The switch is the share's alone: the layer with all its experts lowers
    to no `case` / `conditional` (its program is the parent's), a share to one."""
    tokens, idx, gates, weights = _share_case("swiglu", 40)
    everywhere = [jnp.concatenate([w] * 8) for w in weights]  # 16 experts
    lowered = lambda w, first: jax.jit(lambda t, g, w: moe._experts(t, idx, g, w, 16, first)[0]).lower(tokens, gates, w)  # noqa: E731
    for text in (lowered(everywhere, None).as_text(), lowered(everywhere, None).compile().as_text()):
        assert "case" not in text and "conditional" not in text
    assert len(re.findall(r"stablehlo\.case", lowered(weights, 0).as_text())) == 1
    assert len(re.findall(r" conditional\(", lowered(weights, 0).compile().as_text())) == 1


def test_rows_moved_share_is_one_on_the_top_rung_and_the_lowest_rungs_share_when_nothing_is_held(small_rungs):
    """`moe_rows_moved_share` of the step's terms, through `moe_ffn` and
    `router_losses`: 4 of 16 experts held, 4 choices a token, 256 assignments:
    one assignment a token of a four-way share, so rungs of 80 (1.25x the
    uniform 64; PR 53) and 256.  A router whose every choice is a held expert
    takes the top rung (1.0), one whose choices are all elsewhere the lowest
    (0.3125)."""
    cfg = TransformerConfig(**dict(BASE, d_model=32, d_ff=16, n_experts=16, experts_per_token=4, n_experts_held=4))
    assert moe._rungs(256, 4, 16, 4) == (80, 256)
    params = moe.init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32)).at[..., 0].set(4.0)  # one direction every token has
    for chosen, want_rows, want_share in (([0, 1, 2, 3], 64.0, 1.0), ([4, 5, 6, 7], 0.0, 0.3125)):
        router = params["router"].at[0].set(jnp.zeros(16).at[jnp.asarray(chosen)].set(8.0))
        _, stats = moe.moe_ffn(dict(params, router=router), x, cfg)
        terms = moe.router_losses(jax.tree_util.tree_map(lambda v: jnp.stack([v, v]), stats), cfg)  # two expert layers
        assert float(terms["moe_held_rows_mean"]) == want_rows
        assert float(terms["moe_rows_moved_share"]) == want_share


# -- the grouped matmul: Pallas kernels (interpreted here) against the XLA form ----------------

GROUPS = {"ragged": [700, 0, 300, 28, 1020], "one-takes-all": [0, 0, 2048, 0, 0], "tile-aligned": [512, 512, 512, 256, 256],
          "short-of-m": [100, 0, 0, 200, 0], "straddling": [513, 511, 0, 1000, 24]}


@pytest.mark.parametrize("sizes", GROUPS.values(), ids=GROUPS.keys())
def test_grouped_matmul_kernels_equal_a_loop_over_the_groups(sizes):
    """`moe_gmm`, its transposed form and `moe_tgmm` (through the custom_vjp)
    and the XLA form, against one dense matmul per group."""
    rng = np.random.default_rng(0)
    m, k, n = 2048, 256, 128
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    gs, total = jnp.asarray(sizes, jnp.int32), sum(sizes)
    bounds = np.concatenate([[0], np.cumsum(sizes)])

    def loop(l, r):
        return jnp.concatenate([jnp.dot(l[a:b], r[g], precision="highest")
                                for g, (a, b) in enumerate(zip(bounds, bounds[1:]))])

    def objective(f):
        return lambda l, r: jnp.sum(f(l, r)[:total] * ct[:total])

    want, want_grads = loop(lhs, rhs), jax.grad(objective(loop), argnums=(0, 1))(lhs, rhs)
    for f in (kernels.grouped_matmul, gmm.grouped_matmul_xla):
        got = f(lhs, rhs, gs)[:total]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)
        d_lhs, d_rhs = jax.grad(objective(lambda l, r: f(l, r, gs)), argnums=(0, 1))(lhs, rhs)
        np.testing.assert_allclose(np.asarray(d_lhs)[:total], np.asarray(want_grads[0])[:total], rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(d_rhs), np.asarray(want_grads[1]), rtol=1e-4, atol=1e-3)


# -- the dense model is as it was -------------------------------------------------------------


def _equations(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _equations(inner)
    return total


def test_the_dense_step_is_the_parents_program():
    """`TransformerConfig.tiny()` has no experts and no QK-norm: its train
    step traces to the 709 equations it had at the parent of PR 26 (counted
    there with this function) plus the one `optimization_barrier` of the
    dense FFN's backward (PR 27), less the 10 that the head and the cross
    entropy lost as one function with its own backward (PR 34: no log-softmax
    residual, no scatter-add), with nothing of the expert layer in it."""
    ctx = one_device_ctx(TransformerConfig.tiny())
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(ctx._train_step)(state, {"tokens": toks, "targets": toks})
    assert _equations(jaxpr.jaxpr) == 709 + 1 - 10
    text = str(jaxpr)
    assert "moe" not in text and "top_k" not in text
