"""Attention kernel correctness: blockwise and pallas (interpret) and ring
attention must all match the O(S^2) reference implementation.

Mirrors the reference's approach of unit-testing each numeric component in
isolation (SURVEY.md §4), adapted: our kernels are JAX/pallas, tested on the
8-device virtual CPU mesh from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (
    blockwise_attention,
    reference_attention,
)


def _qkv(key, b=2, s=256, h=4, kv=None, d=32):
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv or h, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kv or h, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = reference_attention(q, k, v, causal=causal)
    blk = blockwise_attention(q, k, v, causal=causal, block_size=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=2e-5, rtol=2e-5)


def test_blockwise_gqa():
    q, k, v = _qkv(jax.random.PRNGKey(1), h=8, kv=2)
    ref = reference_attention(q, k, v, causal=True)
    blk = blockwise_attention(q, k, v, causal=True, block_size=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_matches_reference(causal):
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(2), s=256, d=64)
    ref = reference_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_pallas_flash_grad():
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=128, h=2, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_flash_at_head_size_64_with_a_callers_scale():
    """Granite 4.0-H's attention: heads of 64, GQA 4:1, and the published
    softmax scale 1/64 (not 64 ** -0.5): forward and all three gradients of
    the kernels in interpret mode against `reference_attention`."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=256, h=4, kv=1, d=64)
    q, k = 4.0 * q, 4.0 * k  # logits of order one at this scale, so the softmax is not flat
    scale = 1 / 64

    def run(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, scale=scale, block_q=128, block_k=64,  # noqa: E731
                                            bwd_block_q=64, bwd_block_k=128)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=True, scale=scale)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    (_, g_flash), (_, g_ref) = run(flash), run(ref)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    # the scale is the caller's: the default one gives another function
    other = reference_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(other) - np.asarray(ref(q, k, v))).max() > 1e-2


def test_auto_dispatch_gives_head_size_64_to_the_kernel_when_lowered_for_tpu():
    """ONE rule for every model: sequence lengths a multiple of 128, head size
    a multiple of 64.  Head size 32 stays with the XLA forms."""
    from ray_tpu.ops.attention import dot_product_attention

    def lowered(d):
        q = jax.ShapeDtypeStruct((1, 256, 2, d), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, scale=1 / 64))
        return fn.trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text()

    assert 'kernel_name = "flash_fwd"' in lowered(64) and 'kernel_name = "flash_fwd"' in lowered(128)
    assert "tpu_custom_call" not in lowered(32)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_grad_noncausal_and_mixed_blocks(causal):
    """Backward kernels with bwd tile sizes differing from fwd tiles."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(5), b=1, s=256, h=2, d=32)
    g = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal,
            block_q=128, block_k=128, bwd_block_q=64, bwd_block_k=128,
        )
        return (out * g).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) * g).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_flash_grad_gqa():
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=128, h=4, kv=2, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, seq=4, tensor=1))
    q, k, v = _qkv(jax.random.PRNGKey(4), b=2, s=64, h=4, d=16)
    ref = reference_attention(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal, head_axis=None)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)
