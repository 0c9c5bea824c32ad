"""Mamba-1's selective scan (S6, arXiv:2312.00752) in a chunked form; no
counterpart in the reference (SURVEY.md §5.7).  Plain `jax.numpy`,
differentiated by JAX.  `s6/scan`, the scope around all of this, is what the
benchmark reads it by (PERF.md section 3).

The recurrence, per batch row, per channel c of the mixer's inner width and
per state n, with a positive step `dt_t[c]`, a negative `A[c, n]` and one
group of `B_t`, `C_t` in R^N shared by the channels:

    h_t[c, n] = exp(dt_t[c] * A[c, n]) * h_{t-1}[c, n] + dt_t[c] * B_t[n] * x_t[c]
    y_t[c]    = sum_n C_t[n] * h_t[c, n] + D[c] * x_t[c]

which alone defines the layer (`selective_scan_recurrent`, the token-by-token
form the tests hold this file to).  The decay is per channel AND per state, so
there is no matrix form: Mamba-2's `ssd_chunked` turns ONE scalar decay a head
into a [chunk, chunk] mask on `C B^T`; here that mask would be
[chunk, chunk, channels, N].  The work is elementwise, on [positions, N,
channels] arrays, behind a serial chain of S positions.

`selective_scan` cuts the sequence into chunks of `chunk` positions and runs
one `lax.scan` over them with the state [b, N, channels] as its carry.  Inside
a chunk the recurrence is a first-order linear one, `h_t = a_t h_{t-1} + u_t`,
and `jax.lax.associative_scan` solves it in log2(chunk) levels with the
operator `(a, u) . (a', u') = (a a', a' u + u')`.  The entering state joins
the first position's `u`.

A cumulative decay is only ever a PRODUCT of factors in (0, 1], never a
quotient: `exp(cum_t) / exp(cum_s)` is inf/inf or 0/0 once the running sum of
`dt * A` passes -88 in float32, which `dt * |A|` = 0.1 * 16 a position does in
55 positions.  A product that underflows to 0 is the right answer to float32.

The chunk body is a `jax.checkpoint`: the backward keeps the state that
ENTERS each chunk ([S / chunk, b, N, channels] float32; 84 MB a layer at one
8,192-token sequence, 5,120 channels, N 16 and a chunk of 32) and runs a
chunk's forward again before its backward; the [S, channels, N] states (2.7 GB
there) never exist at once.

Layout: [.., N, channels], the channels in the lanes (N = 16 there would pad
to 128).

Precision: `dt`, `dt * A`, the exponentials, `u`, the states and the sum over
n are float32 whatever the inputs are; y leaves in x's dtype.

The chunk is 32 positions because XLA's fusions fall off a cliff above it on
the v5e: at the benchmark's shapes (1 x 8,192 x 5,120 channels, N 16) a
layer's forward takes 9.2 / 10.3 / 10.3 ms at chunks of 8 / 16 / 32 and 10.7 /
52.9 / 83.0 / 94.5 ms at 64 / 128 / 256 / 512, forward + backward 38.0 / 36.0 /
37.9 ms against 116 / 244 / 309 / 391 (my chip runs, PR 40: the levels of a
[32, 16, 5120] float32 chunk, 10 MB, stay fused; larger ones are written out
level by level).  A Pallas kernel that keeps a chunk's levels in VMEM is what
a `perf_opt` issue on this scan would write: its bytes need 1.5 ms a layer and
direction.

Sharding: nothing here names a mesh axis; batch sharding is GSPMD's to
propagate through the elementwise work.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Positions a chunk holds: log2 levels of the associative scan inside, S / CHUNK serial steps outside
# (module docstring: why 32).
CHUNK = 32


def _combine(left, right):
    """Two stretches of `h_t = a_t h_{t-1} + u_t`, the left one first."""
    a_l, u_l = left
    a_r, u_r = right
    return a_l * a_r, a_r * u_l + u_r


def _chunk_body(A_t, D, carry, inp):
    """One chunk: the state that enters [b, N, C] -> (the state that leaves,
    y [b, L, C] float32).  A_t [N, C] float32 (negative)."""
    x, dt, B, C = inp  # [b, L, C], [b, L, C] f32, [b, L, N], [b, L, N]
    f32 = jnp.float32
    xf = x.astype(f32)
    a = jnp.exp(dt[:, :, None, :] * A_t)  # [b, L, N, C], in (0, 1]
    u = (dt * xf)[:, :, None, :] * B.astype(f32)[..., None]  # [b, L, N, C]
    u = u.at[:, 0].add(a[:, 0] * carry)
    _, h = jax.lax.associative_scan(_combine, (a, u), axis=1)
    y = jnp.sum(h * C.astype(f32)[..., None], axis=2) + D.astype(f32) * xf
    return h[:, -1], y


def selective_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    chunk: Optional[int] = None,
) -> jax.Array:
    """The selective scan of the module docstring, chunked.

    x [b, S, C]; dt [b, S, C] (after softplus, positive); A [C, N]
    (negative); B, C [b, S, N] (one group); D [C].  Returns y [b, S, C] in
    x's dtype.  `chunk` (None = `CHUNK`) is cut to S when S is shorter; S must
    be a multiple of it."""
    b, s, c = x.shape
    n = B.shape[-1]
    chunk = min(chunk or CHUNK, s)
    if s % chunk:
        raise ValueError(f"selective_scan: sequence length {s} is not a multiple of the chunk {chunk}")
    nc = s // chunk
    f32 = jnp.float32
    with jax.named_scope("s6/scan"):
        def chunks(arr):  # [b, S, F] -> [nc, b, chunk, F]
            return arr.reshape(b, nc, chunk, arr.shape[-1]).swapaxes(0, 1)

        A_t = A.astype(f32).T  # [N, C]
        body = jax.checkpoint(lambda carry, inp: _chunk_body(A_t, D, carry, inp))
        _, y = jax.lax.scan(
            body, jnp.zeros((b, n, c), f32), (chunks(x), chunks(dt.astype(f32)), chunks(B), chunks(C)))
        return y.swapaxes(0, 1).reshape(b, s, c).astype(x.dtype)


def selective_scan_recurrent(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence itself, one `lax.scan` step a position, float32: (y
    [b, S, C] float32, the last state [b, C, N]).  What `selective_scan` is
    held to; no training step runs it."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (t.astype(f32) for t in (x, dt, A, B, C, D))

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp  # [b, C], [b, C], [b, N], [b, N]
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1) + D * x_t

    h0 = jnp.zeros((x.shape[0], x.shape[2], B.shape[-1]), f32)
    h, y = jax.lax.scan(step, h0, tuple(t.swapaxes(0, 1) for t in (x, dt, B, C)))
    return y.swapaxes(0, 1), h
