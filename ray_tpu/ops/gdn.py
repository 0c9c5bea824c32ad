"""The gated delta rule with ONE decay a head (Gated DeltaNet, arXiv:2412.06464,
as Qwen3-Next runs it), chunked: `ops/kda.py`'s recurrence with
`alpha_t = exp(g_t)` a NUMBER per value head, and value head j reading the q
and k of key head `j // (Hv / Hk)`.  `gdn_chunked` is one `jax.custom_vjp`,
declared as a `kernel_pair.KernelPair` (`PAIR`) and run by `ops/kernel_pair.py`'s
scaffold.

A decay per head IS the per-channel rule with equal channels, so everywhere
but on TPU, and at the shapes the kernels refuse, both directions are
`ops/kda.py`'s plain form on q and k repeated over a group's value heads and g
broadcast over the key's channels (`_plain_forward`; `_plain_backward`, JAX's
own differentiation of `_segment`; the group's dq and dk summed, dg summed over
the channels): no gradient in this file is derived by hand.  For TPU, at the
shapes they take, two Pallas kernels take the decay for what it is, a [C, C]
mask on a plain `k k^T` and `q k^T` (`ops/pallas/gdn.py`: `gdn_fwd`, `gdn_bwd`;
the precision is `ops/kda.py`'s, product for product), and read q and k where
they lie, unrepeated.  `gdn/scan`, the scope around all of this, is what the
benchmark reads it by (PERF.md section 3).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kda, kernel_pair


def _per_channel(q, k, v, g, beta, chunk: int):
    """The arguments as the per-channel rule's plain form takes them: q and k
    repeated over the value heads of their key head, g over the key's channels,
    each as `kda.segments` gives it (beta [segments, b, c, H, l, 1])."""
    group = v.shape[2] // k.shape[2]
    q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)
    g = jnp.broadcast_to(g[..., None], k.shape)
    segments = functools.partial(kda.segments, chunk=chunk, per_segment=kda.per_segment(k.shape[1], chunk))
    return tuple(map(segments, (q, k, v, g, beta[..., None])))


def _plain_forward(q, k, v, g, beta, chunk: int):
    """(o [b, S, Hv, V] float32, the state that enters each segment): `kda.plain_forward` on `_per_channel`'s arguments."""
    o, entering = kda.plain_forward(*_per_channel(q, k, v, g, beta, chunk))
    return kda.positions(o), entering


def _plain_backward(q, k, v, g, beta, entering, d_o, chunk: int):
    """`kda.plain_backward` on `_per_channel`'s arguments, what it gives for
    the repeated q and k summed back over a group's value heads and for the
    broadcast g over the key's channels: each cotangent in its argument's shape
    and dtype."""
    hk, group = k.shape[2], v.shape[2] // k.shape[2]
    d_o = kda.segments(d_o, chunk, kda.per_segment(k.shape[1], chunk))
    d = kda.plain_backward(*_per_channel(q, k, v, g, beta, chunk), entering, d_o)
    dq, dk, dv, dg, dbeta = map(kda.positions, d)  # each in its argument's dtype: `_segment` casts inside
    of_group = lambda x: x.reshape(*x.shape[:2], hk, group, x.shape[-1]).sum(axis=3)
    return of_group(dq), of_group(dk), dv, dg.sum(axis=-1), dbeta[..., 0]


def _forward(call, q, k, v, g, beta):
    """(o [b, S, Hv, V] float32, the state that enters each segment
    [segments, b, Hv, K, V], and where the forward runs for a backward at
    shapes the kernels take the state that enters each PAIR of chunks
    [b, S / 128, Hv, K, V], which the backward kernel starts from; else None).
    As in `ops/kda.py` the plain form gives zeros where it has no use for the
    pairs' states."""
    chunk, pair_states = call.chunk, call.residuals and call.takes

    def plain(q, k, v, g, beta):
        o, entering = _plain_forward(q, k, v, g, beta, chunk)
        if not pair_states:
            return o, entering
        return o, entering, jnp.zeros((v.shape[0], v.shape[1] // (2 * chunk), *entering.shape[2:]), jnp.float32)

    kernel = functools.partial(call.kernels.gdn_fwd, per_segment=kda.per_segment(k.shape[1], chunk), pair_states=pair_states)
    o, entering, *pairs = call(kernel, plain, q, k, v, g, beta)
    return o, entering, (pairs[0] if pairs else None)


def _backward(call, q, k, v, g, beta, entering, pairs, do):
    """(dq, dk, dv, dg, dbeta), each in its argument's shape and dtype.  For
    TPU at the shapes the kernels take, `gdn_bwd` from the state that entered
    each pair of chunks; everywhere else `_plain_backward` from the state that
    entered each segment."""

    def plain(q, k, v, g, beta, entering, pairs, d_o):
        return _plain_backward(q, k, v, g, beta, entering, d_o, call.chunk)

    def kernel(q, k, v, g, beta, entering, pairs, d_o):
        return call.kernels.gdn_bwd(q, k, v, g, beta, pairs, d_o, per_segment=kda.per_segment(k.shape[1], call.chunk))

    return call(kernel, plain, q, k, v, g, beta, entering, pairs, do)


PAIR = kernel_pair.KernelPair(
    name="gdn_chunked", scope="gdn/scan", kernels="gdn", power_of_two_chunk=True,
    takes=lambda kernels, q, k, v, g, beta, chunk: kernels.supported(
        k.shape[-1], v.shape[-1], chunk, kda.per_segment(k.shape[1], chunk), v.shape[2], k.shape[2]),
    forward=_forward, backward=_backward,
)


def gdn_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    chunk: Optional[int] = None,
    mesh=None,
    batch_axes=None,
) -> jax.Array:
    """The gated delta rule with one decay a head, chunked.

    q, k [b, S, Hk, K] (already normalised and scaled as the layer has them,
    NOT repeated); v [b, S, Hv, V], Hv a multiple of Hk; g (log decay, <= 0)
    and beta [b, S, Hv].  Returns o [b, S, Hv, V] in float32.  `chunk` (None =
    `kda.CHUNK`, a power of two) is cut to S when S is shorter; S must be a
    multiple of it.

    mesh / batch_axes as `kda_chunked` has them: with a mesh the kernels run
    under shard_map over the batch axes, each device on its own rows with the
    whole sequence and every head."""
    if v.shape[2] % k.shape[2]:
        raise ValueError(f"gdn_chunked: {v.shape[2]} value heads are not whole groups of {k.shape[2]} key heads")
    return kernel_pair.run(PAIR, q, k, v, g, beta, chunk=chunk or kda.CHUNK, mesh=mesh, batch_axes=batch_axes)
