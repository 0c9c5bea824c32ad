"""Model + sharded train-step tests on the virtual 8-device CPU mesh.

The multi-strategy matrix (dp/fsdp/tp/fsdp_tp) is the TPU analogue of the
reference's DDP-vs-FSDP wrapper tests (ray: python/ray/train/tests/
test_torch_fsdp.py) — same model, different sharding rules, loss must agree.
"""

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig, forward, init_params, param_axes
from ray_tpu.models.mixers import MIXERS
from ray_tpu.models.mixers.mla import Latent
from ray_tpu.models.transformer import FFN_KINDS
from ray_tpu.parallel import MeshSpec, build_mesh, resolve_rules


CFG = TransformerConfig.tiny()


def _batch(key, b=8, s=32, vocab=CFG.vocab_size):
    toks = jax.random.randint(key, (b, s + 1), 0, vocab)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_forward_shapes():
    params = init_params(CFG, jax.random.PRNGKey(0))
    logits = forward(params, jnp.zeros((2, 16), jnp.int32), CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


# What every kind of mixer and of FFN needs of a configuration, at sizes that differ where the leaves' do.
SIZES = dict(
    norm_kind="layer", qk_norm=True, rope_theta=None, ssm_heads=2, ssm_head_dim=16, ssm_state=8, kda_heads=2,
    kda_head_dim=8, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=32, s6_inner=96,
    gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=16,
)
EXPERTS = dict(n_experts=4, experts_per_token=2, moe_d_ff=48, n_shared_experts=1, router_activation="sigmoid")
# what latent attention's two further kinds read beside SIZES: a low-rank q (the rescale and the indexer read it), the indexer, a second geometry
SPARSE = dict(q_lora_rank=16, index_heads=2, index_head_dim=16, index_topk=8, window_latent=Latent(2, 16, 24, 16, 8, 32))
# what a "cca" layer refuses of SIZES (its q and k are unit-normed, with no learned scale)
CCA = dict(qk_norm=False)
# ZAYA1's FFN half: top-1 behind a router that is a network with a state carried from layer to layer, every join learned
ZAYA = dict(n_experts=4, experts_per_token=1, moe_d_ff=48, router_kind="mlp", router_hidden=16)


@pytest.mark.parametrize("ffn", FFN_KINDS)
@pytest.mark.parametrize("mixer", list(MIXERS))
def test_param_count_matches_config(mixer, ffn):
    """One declaration, three readers, for every (mixer, FFN) pair the
    registry admits: the leaves `init_params` makes sum to `num_params()`, and
    `param_axes` is the same tree with one logical axis per dimension.  A
    kind that reads another layer's values stands behind the kind that hands
    them on."""
    makers = [m for m in MIXERS.values() if set(m.hands) & set(MIXERS[mixer].reads)]
    kinds = (*(m.name for m in makers), mixer, mixer)
    cfg = TransformerConfig.tiny(
        n_layers=len(kinds), layer_types=kinds, ffn_types=(ffn,) * len(kinds), attn_bias=MIXERS[mixer].subtree == "diff",
        **{m.source: i for i, m in enumerate(makers)}, **{**SIZES, **(CCA if mixer == "cca" else {})},
        **(EXPERTS if ffn == "experts" else {}), **(SPARSE if MIXERS[mixer].holds_heads else {}))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()
    axes = param_axes(cfg)
    ranks = jax.tree_util.tree_map(lambda a: a.ndim, params)
    assert ranks == jax.tree_util.tree_map(len, axes, is_leaf=lambda t: isinstance(t, tuple))
    assert params[cfg.stack_name(mixer, ffn)][MIXERS[mixer].subtree]  # the pair's own stack, the mixer's own subtree


@pytest.mark.parametrize("ffn", ["experts", "none"])
def test_param_count_matches_config_with_a_network_router_and_learned_joins(ffn):
    """The same three readers for what ZAYA1 adds to a layer: the router's nine leaves and its stored choice bias in the
    experts' subtree, four residual vectors a sub-block (`res1`, and `res2` where the layer has an FFN)."""
    cfg = TransformerConfig.tiny(n_layers=2, layer_types=("cca", "cca"), ffn_types=(ffn,) * 2, residual_scaling=True, **ZAYA)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()
    ranks = jax.tree_util.tree_map(lambda a: a.ndim, params)
    assert ranks == jax.tree_util.tree_map(len, param_axes(cfg), is_leaf=lambda t: isinstance(t, tuple))
    layer = params["cca_layers"]
    assert ("res2" in layer, "ln2" in layer, "mlp" in layer) == (ffn == "experts",) * 3 and set(layer["res1"]) == {"a_res", "b_res", "a_out", "b_out"}
    if ffn == "experts":
        assert set(layer["mlp"]["router"]) == {"down", "down_b", "gamma", "norm", "w1", "b1", "w2", "b2", "w3"}
        assert layer["mlp"]["router_bias"].shape == (2, 4)


@pytest.mark.parametrize("strategy,spec,kw,match", [
    ("pp", MeshSpec(data=4, pipeline=2), ZAYA, "strategy 'pp' does not carry the router state"),
    ("pp_fsdp", MeshSpec(data=2, fsdp=2, pipeline=2), ZAYA, "strategy 'pp' does not carry the router state"),
    ("tp", MeshSpec(data=2, tensor=4), ZAYA, "the router state .* strategy 'tp'"),
    ("ep", MeshSpec(data=2, expert=4), ZAYA, "the router state .* a mesh's expert axis"),
    ("tp", MeshSpec(data=2, tensor=4), dict(layer_types=("cca", "cca")), "a cca layer runs with its heads and its sequence whole"),
    ("sp", MeshSpec(data=2, seq=4), dict(layer_types=("cca", "cca")), "a cca layer runs with its heads and its sequence whole"),
], ids=["pp-router_state", "pp_fsdp-router_state", "tp-router_state", "ep-router_state", "tp-cca", "sp-cca"])
def test_the_carried_router_state_and_cca_are_refused_by_name_where_they_cannot_run(strategy, spec, kw, match):
    """When configuration, rules and mesh first meet (`check_placement`), not deep inside a trace; `dp` and `fsdp` take both."""
    cfg = TransformerConfig.tiny(**kw)
    with pytest.raises(ValueError, match=match):
        LMTrainContext(cfg, mesh=build_mesh(spec), strategy=strategy)
    LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=2, fsdp=4)), strategy="fsdp")


@pytest.mark.parametrize(
    "strategy,spec",
    [
        # dp stays tier-1 as the fast agreement twin; the sharded
        # strategies (10-20s of XLA CPU compile EACH, and sensitive to
        # host-platform partitioner numerics) run via -m slow.
        ("dp", MeshSpec(data=8)),
        pytest.param("fsdp", MeshSpec(data=2, fsdp=4),
                     marks=pytest.mark.slow),
        pytest.param("tp", MeshSpec(data=2, tensor=4),
                     marks=pytest.mark.slow),
        pytest.param("fsdp_tp", MeshSpec(data=2, fsdp=2, tensor=2),
                     marks=pytest.mark.slow),
        pytest.param("sp", MeshSpec(data=2, seq=4),
                     marks=pytest.mark.slow),
        pytest.param("pp", MeshSpec(data=4, pipeline=2),
                     marks=pytest.mark.slow),
        pytest.param("pp_fsdp", MeshSpec(data=2, fsdp=2, pipeline=2),
                     marks=pytest.mark.slow),
    ],
)
def test_train_step_strategies_agree(strategy, spec):
    """Same seed + batch under every strategy → same loss trajectory."""
    mesh = build_mesh(spec)
    ctx = LMTrainContext(CFG, mesh=mesh, strategy=strategy)
    state = ctx.init_state(seed=0)
    batch = _batch(jax.random.PRNGKey(42))
    losses = []
    for _ in range(2):
        state, metrics = ctx.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[1] < losses[0]  # one step of adam on repeated batch improves
    # Ground truth from single-device run.
    mesh1 = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    ctx1 = LMTrainContext(CFG, mesh=mesh1, strategy="dp")
    state1 = ctx1.init_state(seed=0)
    _, m1 = ctx1.train_step(state1, batch)
    np.testing.assert_allclose(losses[0], float(m1["loss"]), rtol=1e-4)


def test_sequence_parallel_forward():
    """seq-sharded forward w/ ring attention matches unsharded forward."""
    cfg = TransformerConfig.tiny(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    ref = forward(params, toks, cfg)

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rules = resolve_rules("sp")
    with mesh:
        out = jax.jit(lambda p, t: forward(p, t, cfg, rules=rules, mesh=mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4, rtol=1e-4)


def test_sp_actually_runs_ring_attention():
    """The sp strategy must compile to collective-permute KV rotation, NOT
    an all-gather of the sequence (the failure mode VERDICT r1 flagged:
    seq-sharded activations + full attention = silent gather)."""
    cfg = TransformerConfig.tiny(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)

    mesh = build_mesh(MeshSpec(data=2, seq=4))
    rules = resolve_rules("sp")
    with mesh:
        compiled = (
            jax.jit(lambda p, t: forward(p, t, cfg, rules=rules, mesh=mesh))
            .lower(params, toks)
            .compile()
        )
    hlo = compiled.as_text()
    assert "collective-permute" in hlo, "ring attention not dispatched"
    assert hlo.count("all-gather") == 0, "sequence is being all-gathered"


# -- remat policies and the residuals attention names ------------------------


def _sub_jaxprs(eqn):
    """The jaxprs an equation carries: scan, cond, remat, shard_map, pjit."""
    for param in eqn.params.values():
        for sub in param if isinstance(param, (tuple, list)) else (param,):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _eqns(jaxpr):
    """Every equation of a jaxpr, through the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _mesh_kw(strategy, spec):
    """`forward`'s rules= and mesh= for a strategy on the CPU devices."""
    if strategy is None:
        return {}
    mesh = build_mesh(spec, devices=jax.devices()[: spec.size()])
    return dict(rules=resolve_rules(strategy), mesh=mesh)


def _model_jaxpr(cfg, strategy=None, spec=None, seq=128, grad=True):
    """jaxpr of the model's forward, or of its gradient, traced on shapes alone."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    toks = jnp.zeros((4, seq), jnp.int32)
    kw = _mesh_kw(strategy, spec)
    fn = lambda p: forward(p, toks, cfg, **kw).sum()  # noqa: E731
    return jax.make_jaxpr(jax.grad(fn) if grad else fn)(params).jaxpr


@pytest.mark.parametrize(
    "strategy,spec", [(None, None), ("fsdp", MeshSpec(data=1, fsdp=4))], ids=["nomesh", "fsdp4"]
)
@pytest.mark.parametrize("policy,fwd_per_bwd", [(None, 2), ("attn", 1), ("qkv_attn", 1)])
def test_flash_forward_runs_once_when_the_policy_saves_attention(
    policy, fwd_per_bwd, strategy, spec
):
    """The kernel names its backward's residuals (out, log-sum-exp), so a
    policy that saves them leaves ONE forward kernel per backward kernel in
    the gradient; full recompute re-runs it.  (Each kernel counts twice here,
    once per `platform_dependent` branch; the ratio is what matters.)"""
    cfg = TransformerConfig.tiny(attention_impl="pallas", remat=True, remat_policy=policy)
    kernels = collections.Counter(
        e.params["name"]
        for e in _eqns(_model_jaxpr(cfg, strategy, spec))
        if e.primitive.name == "pallas_call"
    )
    assert kernels["flash_bwd_dq"] == kernels["flash_bwd_dkv"] > 0
    assert kernels["flash_fwd"] == fwd_per_bwd * kernels["flash_bwd_dq"], kernels


def test_saved_flash_residuals_give_the_gradients_of_no_remat():
    """Backward from the saved out/lse == backward with nothing rematted, to
    the tolerance test_ops_attention.py holds the kernel's gradients to."""
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, CFG.vocab_size)
    grads = []
    for kw in (dict(remat=True, remat_policy="qkv_attn"), dict(remat=False)):
        cfg = TransformerConfig.tiny(attention_impl="pallas", **kw)
        params = init_params(cfg, jax.random.PRNGKey(0))
        grads.append(jax.jit(jax.grad(lambda p: forward(p, toks, cfg).mean()))(params))
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "impl,strategy,spec",
    [("reference", None, None), ("blockwise", None, None), ("reference", "sp", MeshSpec(data=2, seq=4))],
    ids=["reference", "blockwise", "ring"],
)
def test_xla_and_ring_attention_save_their_output_under_attn(impl, strategy, spec):
    """Off the kernel the op names its own result: under "attn" the forward
    scan hands the backward ONE [L, B, S, H, D] stack and the backward does
    not evaluate the named value again; under full recompute it does."""
    found = {}
    for policy in (None, "attn"):
        cfg = TransformerConfig.tiny(attention_impl=impl, remat=True, remat_policy=policy)
        eqns = list(_eqns(_model_jaxpr(cfg, strategy, spec, seq=64)))
        layers_fwd = next(
            e for e in eqns if e.primitive.name == "scan" and e.params["length"] == cfg.n_layers
        )
        stacks = [v.aval.shape for v in layers_fwd.outvars[layers_fwd.params["num_carry"]:]]
        found[policy] = dict(
            attn_stacks=stacks.count((cfg.n_layers, 4, 64, cfg.n_heads, cfg.head_dim)),
            named=sum(e.primitive.name == "name" and e.params["name"] == "attn" for e in eqns),
            dots=sum(e.primitive.name == "dot_general" for e in eqns),
        )
    assert (found[None]["attn_stacks"], found[None]["named"]) == (0, 2)
    assert (found["attn"]["attn_stacks"], found["attn"]["named"]) == (1, 1)
    if impl == "reference" and strategy is None:
        # the probs @ v einsum is the one matmul the saved output spares (the
        # blockwise scan and the ring loop re-run whole for their own residuals)
        assert found[None]["dots"] - found["attn"]["dots"] == 1


# -- the dense FFN's backward finishes as one unit ---------------------------

MESHES = pytest.mark.parametrize(
    "strategy,spec", [(None, None), ("fsdp", MeshSpec(data=1, fsdp=4))], ids=["nomesh", "fsdp4"]
)
REMAT = {
    "noremat": dict(remat=False),
    "full": dict(remat=True, remat_policy=None),
    "attn": dict(remat=True, remat_policy="attn"),
    "qkv_attn": dict(remat=True, remat_policy="qkv_attn"),
}
MOE = dict(n_experts=4, experts_per_token=2, router_aux_loss_coef=0.01, router_z_loss_coef=0.001)


@MESHES
@pytest.mark.parametrize("remat", ["noremat", "attn", "qkv_attn"])
def test_tied_ffn_gives_the_plain_ffns_loss_and_gradients(remat, strategy, spec, monkeypatch):
    """`_dense_ffn` orders its backward, nothing else: the loss and every
    gradient leaf equal those of `_swiglu` called directly."""
    from ray_tpu.models import transformer

    cfg = TransformerConfig.tiny(**REMAT[remat])
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), b=4)
    kw = _mesh_kw(strategy, spec)

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, batch["tokens"], cfg, **kw))
        return -jnp.take_along_axis(logp, batch["targets"][..., None], -1).mean()

    tied = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(transformer, "_dense_ffn", transformer._swiglu)
    plain = jax.jit(jax.value_and_grad(loss))(params)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (tied, plain))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def _barriers(jaxpr, in_backward=False):
    """(operand count, inside a reverse scan?) of every optimization_barrier."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            found.append((len(eqn.invars), in_backward))
        below = in_backward or (eqn.primitive.name == "scan" and eqn.params["reverse"])
        for sub in _sub_jaxprs(eqn):
            found += _barriers(sub, below)
    return found


@MESHES
@pytest.mark.parametrize("remat", ["noremat", "full", "qkv_attn"])
def test_dense_backward_ties_its_four_cotangents_once_per_layer_body(remat, strategy, spec):
    """(dh, dW_gate, dW_up, dW_down) pass ONE barrier in the backward scan's
    layer body; the forward holds none."""
    cfg = TransformerConfig.tiny(**REMAT[remat])
    assert _barriers(_model_jaxpr(cfg, strategy, spec)) == [(4, True)]
    assert _barriers(_model_jaxpr(cfg, strategy, spec, grad=False)) == []


@pytest.mark.parametrize("remat", ["noremat", "qkv_attn"])
def test_expert_model_bypasses_the_tie(remat):
    """`n_experts` set takes the `moe_ffn` branch: no barrier anywhere, and
    the train step traces to the equations the expert layer itself accounts
    for (counted with `_eqns`: 2786 / 3563 at the parent of PR 27 and after
    it; PR 29 moved the gate multiply before `w_down`, and the checkpointed
    step lost the recompute of the down projection and the un-permute; PR 34
    made head and cross entropy one function: 10 fewer, 2804 / 3374 before)."""
    cfg = TransformerConfig.tiny(**REMAT[remat], **MOE)
    assert _barriers(_model_jaxpr(cfg)) == []
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    step = jax.make_jaxpr(ctx._train_step)(state, {"tokens": toks, "targets": toks})
    assert sum(1 for _ in _eqns(step.jaxpr)) == {"noremat": 2794, "qkv_attn": 3364}[remat]


@pytest.mark.slow  # pp_fsdp compile cost; sharding twins stay via sp tests
def test_pp_fsdp_params_sharded_at_rest():
    """pp_fsdp's point: params + optimizer state occupy 1/(P*F) of the
    model per device (pipeline stages x fsdp shards), not 1/P."""
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, pipeline=2))
    ctx = LMTrainContext(CFG, mesh=mesh, strategy="pp_fsdp")
    state = ctx.init_state(seed=0)
    wq = state["params"]["layers"]["attn"]["wq"]
    total = wq.size * wq.dtype.itemsize
    local = wq.addressable_shards[0].data.size * wq.dtype.itemsize
    # pipeline(2) x fsdp(2) = 4-way sharded; data axis replicates.
    assert local * 4 == total, (local, total)
    spec = wq.sharding.spec
    assert "pipeline" in str(spec) and "fsdp" in str(spec)
    # Adam moments shard identically (optimizer-state sharding is the win).
    mu_wq = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x, state["opt_state"])
    )
    big = [m for m in mu_wq if hasattr(m, "shape") and m.shape == wq.shape]
    assert big and all(
        m.addressable_shards[0].data.size * 4 == m.size for m in big
    )
    # ...and STAY sharded after a step (train_step out_shardings are
    # pinned; propagation was measured to replicate the moments).
    state, _ = ctx.train_step(state, _batch(jax.random.PRNGKey(1)))
    wq2 = state["params"]["layers"]["attn"]["wq"]
    assert wq2.addressable_shards[0].data.size * 4 == wq2.size
    moments = [
        m for m in jax.tree_util.tree_leaves(state["opt_state"])
        if hasattr(m, "shape") and m.shape == wq.shape
    ]
    assert moments and all(
        m.addressable_shards[0].data.size * 4 == m.size for m in moments
    )
