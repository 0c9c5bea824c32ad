"""`head_cross_entropy`: the head's matmul and the cross entropy as one
function with its own backward (models/lm.py), against the plain form it
replaces in the train step: `jax.value_and_grad` of
`cross_entropy_loss(einsum(x, head).astype(f32), targets, mask)`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    LMTrainContext,
    TransformerConfig,
    cross_entropy_loss,
    forward,
    head_cross_entropy,
    init_params,
)
from ray_tpu.models import transformer
from ray_tpu.models.moe import router_losses
from ray_tpu.parallel import MeshSpec, build_mesh

B, S, D, V = 2, 16, 32, 384
# float32: the two forms differ by the order of float32 reductions alone.
# bf16: the same roundings (logits and their cotangent narrowed to bf16 where
# autodiff narrows them), so what is left is again reduction order, now seen
# through one bf16 rounding of a gradient.
TOL = {"float32": dict(atol=1e-5, rtol=1e-5), "bfloat16": dict(atol=1e-2, rtol=2e-2)}


def _identity(h, axes):
    return h


def _masks(key):
    some = (jax.random.uniform(key, (B, S)) > 0.4).astype(jnp.int32).at[1].set(0)  # row 1 masked out whole
    return {"no_mask": None, "masked_rows": some, "all_zero": jnp.zeros((B, S), jnp.int32)}


def _inputs(dtype):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (B, S, D), dtype)
    head = (jax.random.normal(k[1], (D, V)) * 0.3).astype(dtype)
    targets = jax.random.randint(k[2], (B, S), 0, V).at[0, 0].set(0).at[0, 1].set(V - 1)  # the first and the last column
    return x, head, targets, k[3]


def _close(got, want, dtype):
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), **TOL[jnp.dtype(dtype).name])


@pytest.mark.parametrize("mask", ["no_mask", "masked_rows", "all_zero"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_value_and_gradients_equal_the_plain_forms(dtype, mask):
    x, head, targets, key = _inputs(dtype)
    m = _masks(key)[mask]

    def plain(x, head):
        return 3.0 * cross_entropy_loss(jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32), targets, m)

    def fused(x, head):
        return 3.0 * head_cross_entropy(_identity, x, head, targets, m)  # 3.0: a cotangent that is not one

    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(x, head)
    got = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(x, head)
    assert got[0].dtype == jnp.float32 and got[0].shape == ()
    _close(got, want, dtype)
    if mask == "all_zero":
        assert float(got[0]) == 0.0 and not any(np.asarray(g, np.float32).any() for g in got[1])


def test_the_targets_column_is_found_at_both_ends_of_the_vocabulary():
    """One row, its target at column 0 / V-1 and a logit there that towers
    over the rest: the loss is ~0 and the row's gradient vanishes; aimed at
    the other end the loss is the gap."""
    head = jnp.zeros((D, V), jnp.float32).at[0, 0].set(30.0).at[1, V - 1].set(30.0)
    x = jnp.zeros((1, 2, D), jnp.float32).at[0, 0, 0].set(1.0).at[0, 1, 1].set(1.0)
    hit = head_cross_entropy(_identity, x, head, jnp.array([[0, V - 1]]), None)
    miss = head_cross_entropy(_identity, x, head, jnp.array([[V - 1, 0]]), None)
    assert float(hit) < 1e-9 and abs(float(miss) - 30.0) < 1e-5


# -- through the real objective ------------------------------------------------------------


def _ctx(cfg, spec=MeshSpec(data=1), strategy="dp", n_devices=1):
    return LMTrainContext(cfg, mesh=build_mesh(spec, devices=jax.devices()[:n_devices]), strategy=strategy)


def _batch(cfg, key, b=4, s=32, mask=True):
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (b, s + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    if mask:
        batch["mask"] = (jax.random.uniform(k2, (b, s)) > 0.3).astype(jnp.int32)
    return batch


def _plain_objective(ctx, params, batch):
    """The parent's `_loss`: `forward`'s float32 logits into `cross_entropy_loss`."""
    logits = forward(params, batch["tokens"], ctx.config, rules=ctx.rules, mesh=ctx.mesh)
    return cross_entropy_loss(logits, batch["targets"], batch.get("mask"))


@pytest.mark.parametrize("scaling", [1.0, 8.0], ids=["scaling1", "scaling8"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_loss_gives_the_plain_objectives_value_and_every_gradient_leaf(dtype, tied, scaling):
    """Tied: the head's cotangent flows on into `embed.tokens` through `.T`
    and meets the embedding lookup's there."""
    cfg = TransformerConfig.tiny(dtype=dtype, tie_embeddings=tied, logits_scaling=scaling, vocab_size=V)
    ctx = _ctx(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert ("lm_head" in params) != tied
    batch = _batch(cfg, jax.random.PRNGKey(1))
    (loss, terms), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(params, batch)
    # no term of the objective: the attention kernels' two counters alone (PR 55, PR 63)
    assert set(terms) == {"attn_causal_steps_copying_pct", "attn_tiles_unmasked_pct"}
    want = jax.jit(jax.value_and_grad(lambda p: _plain_objective(ctx, p, batch)))(params)
    _close((loss, grads), want, dtype)


def test_expert_objective_still_returns_ce_loss_and_the_router_terms():
    cfg = TransformerConfig.tiny(n_experts=4, experts_per_token=2, router_aux_loss_coef=0.01,
                                 router_z_loss_coef=0.001, vocab_size=V)
    ctx = _ctx(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    (loss, terms), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(params, batch)
    assert set(terms) >= {"ce_loss", "moe_lb_loss", "moe_z_loss"}

    def plain(p):
        _, _, stats = transformer.trunk(p, batch["tokens"], cfg, rules=ctx.rules, mesh=ctx.mesh)
        router = router_losses(stats, cfg)
        return (_plain_objective(ctx, p, batch) + cfg.router_aux_loss_coef * router["moe_lb_loss"]
                + cfg.router_z_loss_coef * router["moe_z_loss"])

    want = jax.jit(jax.value_and_grad(plain))(params)
    _close((loss, grads), want, jnp.float32)
    np.testing.assert_allclose(float(terms["ce_loss"]), float(_plain_objective(ctx, params, batch)), rtol=1e-5)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_apply_returns_float32_logits_bit_equal_to_the_parents_formula(tied):
    """`forward` is the trunk plus the parent's last lines: a matmul in the
    model's dtype, rounded there, then widened."""
    cfg = TransformerConfig.tiny(dtype=jnp.bfloat16, tie_embeddings=tied, logits_scaling=8.0, vocab_size=V)
    ctx = _ctx(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch(cfg, jax.random.PRNGKey(1))["tokens"]

    @jax.jit
    def parents(params, tokens):
        x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
        positions = jnp.arange(tokens.shape[1])
        x, _ = jax.lax.scan(
            lambda h, layer: transformer._layer(h, layer, positions, cfg, None), x, params["layers"])
        x = transformer.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"]["tokens"].T if tied else params["lm_head"]).astype(cfg.dtype)
        x = x / jnp.asarray(cfg.logits_scaling, x.dtype)
        return jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)

    got = ctx.apply(params, tokens)
    assert got.dtype == jnp.float32 and got.shape == tokens.shape + (V,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(parents(params, tokens)))


# -- executed on a mesh of four CPU devices --------------------------------------------------

MESHES = {
    "dp4": (MeshSpec(data=4), "dp"),
    "fsdp4": (MeshSpec(data=1, fsdp=4), "fsdp"),  # head sharded on `embed`, logits on the batch
    "tp4": (MeshSpec(data=2, tensor=2), "tp"),  # the vocabulary over `tensor`: row max and sum are all-reduces
    "fsdp_tp4": (MeshSpec(data=1, fsdp=2, tensor=2), "fsdp_tp"),
}


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("mesh", MESHES)
def test_sharded_loss_and_gradients_equal_the_one_device_ones(mesh, tied):
    cfg = TransformerConfig.tiny(tie_embeddings=tied, vocab_size=V)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    one = _ctx(cfg)
    want = jax.jit(jax.value_and_grad(lambda p: one._loss(p, batch)[0]))(params)
    spec, strategy = MESHES[mesh]
    ctx = _ctx(cfg, spec, strategy, 4)
    with ctx.mesh:
        got = jax.jit(jax.value_and_grad(lambda p: ctx._loss(p, batch)[0]),
                      in_shardings=(ctx.param_shardings,))(params)
    _close(got, want, jnp.float32)


def test_train_step_on_the_fsdp_mesh_follows_the_one_device_losses():
    cfg = TransformerConfig.tiny(vocab_size=V, dtype=jnp.bfloat16)
    batch = jax.tree_util.tree_map(np.asarray, _batch(cfg, jax.random.PRNGKey(1)))
    losses = {}
    for name, (spec, strategy, n) in {"one": (MeshSpec(data=1), "dp", 1), "fsdp4": (*MESHES["fsdp4"], 4)}.items():
        ctx = _ctx(cfg, spec, strategy, n)
        state = ctx.init_state(seed=0)
        losses[name] = []
        for _ in range(3):
            state, metrics = ctx.train_step(state, batch)
            losses[name].append(float(metrics["loss"]))
    assert losses["one"][-1] < losses["one"][0]
    np.testing.assert_allclose(losses["fsdp4"], losses["one"], rtol=2e-2)
