"""Ring attention: causal attention over a sequence-sharded axis via ICI.

Absent from the reference entirely (SURVEY.md §5.7 — it has no sequence/
context parallelism).  TPU-native design: activations are sharded along a
`seq` mesh axis; KV chunks rotate around the ring with `ppermute` while each
device accumulates online-softmax partials for its local Q chunk.  Compute
(MXU matmuls on the local chunk) overlaps with the next chunk's ICI transfer
under XLA's latency-hiding scheduler.

Used through shard_map; composes with data/fsdp/tensor sharding on the other
mesh axes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import ATTN_OUT, NEG_INF, _repeat_kv


def _partial_attention(q, k, v, q_offset, k_offset, causal, scale):
    """Online-softmax partials (acc, m, l) of q against one KV chunk, f32.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; offsets are absolute positions of
    element 0 along the global sequence.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = (q * scale).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = k_offset + jnp.arange(sk)[None, :]
        logits = jnp.where((qpos >= kpos)[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m[..., None]))
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return acc, m, l


def _combine(a, b):
    """Merge two online-softmax partial triples."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    return acc_a * ca[..., None] + acc_b * cb[..., None], m, l_a * ca + l_b * cb


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Per-shard body (call inside shard_map with seq sharded on axis_name).

    q, k, v: local chunks [B, S_local, H, D]; the global sequence is the
    concatenation over the axis in mesh order.
    """
    h = q.shape[2]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    chunk = q.shape[1]
    q_offset = my * chunk

    b, sq, _, d = q.shape
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        acc, m, l, kc, vc = carry
        src = (my - s) % n
        part = _partial_attention(q, kc, vc, q_offset, src * chunk, causal, scale)
        acc, m, l = _combine((acc, m, l), part)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return acc, m, l, kc, vc

    acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    causal: bool = True,
) -> jax.Array:
    """Convenience wrapper: shard_map ring_attention over a mesh.

    Inputs are global [B, S, H, D] arrays; S is sharded over seq_axis, B over
    batch_axes, heads over head_axis.  The caller must ensure S divides the
    seq-axis size (the model dispatcher checks); batch/head specs are
    shape-fitted — a dim that doesn't divide runs replicated, which is
    correct, just unsharded.
    """
    from ray_tpu.parallel.sharding import _fit_spec

    def fit(x):
        spec = P(batch_axes, seq_axis, head_axis, None)
        fitted = _fit_spec(x.shape, spec, mesh)
        if fitted[1] != seq_axis:
            raise ValueError(
                f"seq length {x.shape[1]} not divisible by mesh axis "
                f"{seq_axis!r} ({mesh.shape[seq_axis]})"
            )
        return fitted

    qspec, kspec = fit(q), fit(k)
    body = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qspec, kspec, kspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v)
    return checkpoint_name(out, ATTN_OUT)
