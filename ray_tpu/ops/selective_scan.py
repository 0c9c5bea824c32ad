"""Mamba-1's selective scan (S6, arXiv:2312.00752) in a chunked form; no
counterpart in the reference (SURVEY.md §5.7).  `selective_scan` is one
`jax.custom_vjp`, declared as a `kernel_pair.KernelPair` (`PAIR`) and run by
`ops/kernel_pair.py`'s scaffold.  Both directions are plain `jax.numpy` everywhere but on TPU
(`_plain_forward`, a scan over `_chunk_body`; `_plain_backward`, JAX's own
differentiation of `_chunk_body` a chunk at a time from the state that entered
it: no gradient in this file is derived by hand); for TPU, at the shapes they
take, two Pallas kernels walk the recurrence itself and its adjoint with the
state in vector registers (`ops/pallas/selective_scan.py`: `s6_scan_fwd`,
PR 42, and `s6_scan_bwd`, PR 51, the same arithmetic at the same precision).
Either backward starts from the entering states either forward writes.
`s6/scan`, the scope around all of this, is what the benchmark reads it by
(PERF.md section 3).

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

The plain form cuts the sequence into chunks of `chunk` positions and runs
one `lax.scan` over them with the state [b, N, channels] as its carry.  Inside
a chunk the recurrence is a first-order linear one, `h_t = a_t h_{t-1} + u_t`,
and `jax.lax.associative_scan` solves it in log2(chunk) levels with the
operator `(a, u) . (a', u') = (a a', a' u + u')`.  The entering state joins
the first position's `u`.  (The kernel has no levels: a position at a time,
which is fewer operations when nothing has to leave the registers.)

A cumulative decay is only ever a PRODUCT of factors in (0, 1], never a
quotient: `exp(cum_t) / exp(cum_s)` is inf/inf or 0/0 once the running sum of
`dt * A` passes -88 in float32, which `dt * |A|` = 0.1 * 16 a position does in
55 positions.  A product that underflows to 0 is the right answer to float32.

What the backward keeps is the state that ENTERS each chunk ([S / chunk, b,
N, channels] float32; 84 MB a layer at one 8,192-token sequence, 5,120
channels, N 16 and a chunk of 32), beside the inputs: it runs a chunk's
forward again before its backward, from the last chunk to the first with the
state's cotangent carried along (the plain form: `_chunk_body` behind a
`jax.checkpoint` in one reverse `lax.scan`; the kernel: the chunk's positions
once more with their states in VMEM, then the adjoint a position at a time);
the [S, channels, N] states (2.7 GB there) never exist at once, and the serial
pass over the chunks is not run a second time.

Layout: [.., N, channels], the channels in the lanes (N = 16 there would pad
to 128).

Precision: `dt`, `dt * A`, the exponentials, `u`, the states and the sum over
n are float32 whatever the inputs are; y leaves in x's dtype.

The chunk is 32 positions because XLA's fusions fall off a cliff above it on
the v5e: at the benchmark's shapes (1 x 8,192 x 5,120 channels, N 16) a
layer's plain forward takes 9.2 / 10.3 / 10.3 ms at chunks of 8 / 16 / 32 and
10.7 / 52.9 / 83.0 / 94.5 ms at 64 / 128 / 256 / 512, forward + backward 38.0 /
36.0 / 37.9 ms against 116 / 244 / 309 / 391 (my chip runs, PR 40: the levels
of a [32, 16, 5120] float32 chunk, 10 MB, stay fused; larger ones are written
out level by level).  The kernels have no levels and take 1.56 ms forward (my
chip runs, PR 42) and 5.8 ms backward (my chip runs, PR 51; plain 32.9) there;
for them the chunk sizes the residual alone (the entering states, 84 MB a
layer) and the states a program keeps in VMEM (1 MB), so it stays.

Sharding: the plain form names no mesh axis; batch sharding is GSPMD's to
propagate through the elementwise work.  A Mosaic kernel GSPMD cannot
partition, so on a mesh `selective_scan` runs under shard_map over the batch
axes where the kernels take the shapes, forward and backward.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_pair

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


def _chunks(arr, chunk: int):
    """[b, S, F] -> [S / chunk, b, chunk, F]: what the scans over `_chunk_body` walk."""
    b, s, f = arr.shape
    return arr.reshape(b, s // chunk, chunk, f).swapaxes(0, 1)


def _positions(arr):
    """Back: [S / chunk, b, chunk, F] -> [b, S, F]."""
    nc, b, chunk, f = arr.shape
    return arr.swapaxes(0, 1).reshape(b, nc * chunk, f)


def _plain_forward(x, dt, A_t, B, C, D, chunk: int):
    """One `lax.scan` over `_chunk_body`: (y [b, S, C] in x's dtype, the state
    that enters each chunk [S / chunk, b, N, C] float32).  The forward off TPU
    and at shapes the kernel refuses."""
    body = jax.checkpoint(lambda carry, inp: _chunk_body(A_t, D, carry, inp))

    def step(carry, inp):
        left, y = body(carry, inp)
        return left, (y, carry)

    start = jnp.zeros((x.shape[0], *A_t.shape), jnp.float32)
    _, (y, entering) = jax.lax.scan(step, start, tuple(_chunks(t, chunk) for t in (x, dt, B, C)))
    return _positions(y).astype(x.dtype), entering


def _forward(call, x, dt, A, B, C, D):
    """(y, the state that enters each chunk)."""
    f32 = jnp.float32
    kernel = functools.partial(call.kernels.s6_scan_fwd, chunk=call.chunk)
    plain = functools.partial(_plain_forward, chunk=call.chunk)
    return call(kernel, plain, x, dt.astype(f32), A.astype(f32).T, B, C, D)


def _plain_backward(x, dt, A_t, B, C, D32, entering, dy, chunk: int):
    """JAX's own differentiation of `_chunk_body`, a chunk at a time from the
    last to the first: the chunk's forward once more FROM THE STATE THAT
    ENTERED IT, which the forward wrote (kernel or plain), then its backward,
    the state's cotangent carried along with the sums for A and D.  The serial
    pass over the chunk states is not run again.  The backward off TPU and at
    shapes the kernel refuses: the cotangents of (x, dt, A_t, B, C, D), dx, dB
    and dC in their arguments' dtypes, the others float32."""
    f32 = jnp.float32

    def step(carry, xs):
        d_state, d_A, d_D = carry
        state, inp, dy = xs
        # a checkpoint, so that what `pull` holds is the chunk's arguments and forward and backward run in one piece
        _, pull = jax.vjp(jax.checkpoint(_chunk_body), A_t, D32, state, inp)
        g_A, g_D, d_state, d_inp = pull((d_state, dy.astype(f32)))
        return (d_state, d_A + g_A, d_D + g_D), d_inp

    chunks = functools.partial(_chunks, chunk=chunk)
    start = (jnp.zeros_like(entering[0]), jnp.zeros_like(A_t), jnp.zeros_like(D32))
    inputs = (chunks(x), chunks(dt.astype(f32)), chunks(B), chunks(C))
    (_, d_A, d_D), d_inputs = jax.lax.scan(step, start, (entering, inputs, chunks(dy)), reverse=True)
    dx, d_dt, dB, dC = map(_positions, d_inputs)
    return dx, d_dt, d_A, dB, dC, d_D


def _backward(call, x, dt, A, B, C, D, entering, dy):
    """From the entering states either forward wrote: for TPU, at the shapes
    the kernel takes, the recurrence's own adjoint a position at a time
    (`s6_scan_bwd`); everywhere else `_plain_backward`.  Cotangents in their
    arguments' dtypes."""
    f32 = jnp.float32
    kernel = functools.partial(call.kernels.s6_scan_bwd, chunk=call.chunk)
    plain = functools.partial(_plain_backward, chunk=call.chunk)
    dx, d_dt, d_A, dB, dC, d_D = call(kernel, plain, x, dt, A.astype(f32).T, B, C, D.astype(f32), entering, dy)
    return dx, d_dt.astype(dt.dtype), d_A.T.astype(A.dtype), dB, dC, d_D.astype(D.dtype)


PAIR = kernel_pair.KernelPair(
    name="selective_scan", scope="s6/scan", kernels="selective_scan",
    takes=lambda kernels, x, dt, A, B, C, D, chunk: kernels.supported(x.shape[2], B.shape[-1], x.shape[1], chunk),
    forward=_forward, backward=_backward, replicated=(2, 5),  # A and D
)


def selective_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    chunk: Optional[int] = None,
    mesh=None,
    batch_axes=None,
) -> jax.Array:
    """The selective scan of the module docstring, chunked.

    x [b, S, C]; dt [b, S, C] (after softplus, positive); A [C, N]
    (negative); B, C [b, S, N] (one group); D [C].  Returns y [b, S, C] in
    x's dtype.  `chunk` (None = `CHUNK`) is cut to S when S is shorter; S must
    be a multiple of it.

    mesh / batch_axes say how the arguments are sharded (A and D are
    replicated).  GSPMD partitions the plain form by itself; a Mosaic kernel it
    cannot, so with a mesh the kernel runs under shard_map over the batch axes,
    each device on its own rows with the whole sequence and every channel."""
    return kernel_pair.run(PAIR, x, dt, A, B, C, D, chunk=chunk or CHUNK, mesh=mesh, batch_axes=batch_axes)


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
