"""The forward of the chunked KDA recurrence in Pallas for TPU (`ops/kda.py`
has the recurrence, its chunked form and the plain `jax.numpy` code this
equals; the backward stays that plain code, differentiated by JAX).

One program takes `rows` positions of one head (a multiple of 128: two chunks
of 64) and keeps in VMEM what the plain form writes to HBM a level at a time:
the running sums G, the by-halves levels of both [chunk, chunk] matrices, the
inverse of the `k.k` system, `exp(G)`, `u_bar`, `w`, `q_in`, `k_out`.  The
sequence is the grid's LAST, sequential axis and the head's state [K, V] rides
it in a VMEM scratch, so the chain of chunk states never leaves the chip
either; the state that enters each `segment` of positions is the one thing
written beside o (the backward's only residual).

Inside a program the positions are worked on 128 at a time, every array
[128, 128] float32 (16 vector registers, the MXU's own shape); the pairs of
chunks are independent until the state chain, so the unrolled code lets the
scheduler interleave their ~90 dependent matrix products.  Per 128 positions:

- G, the inclusive running sum of g inside each chunk: one product with a
  block-diagonal triangle of ones.
- the six levels of `_decayed_lower`, sizes 1, 2, .., 32.  `H[t] = G[t - t %
  size]`, the first row of t's block of `size`, grows from level to level by
  one sublane roll and one select.  A lower-half row carries
  `exp(G[t] - H[t])`, an upper-half row `exp(H[t + size] - G[t])`: both
  measured from the lower half's first row, both <= 0 (and clamped there:
  the rounding of G may not turn one positive).  One product `lower x upper^T`
  for q and one for k, kept where row and column share a pair; the inverse of
  `I + beta A` rides along as `X - X M X`.
- `[u_bar | w] = X [beta v | beta k exp(G)]`, `q_in = q exp(G)`, `k_out = k
  exp(G_end - G)`, then for each of the two chunks, with the state S that
  enters it: `u = u_bar - w S`, `o = q_in S + QK u`, `S <- exp(G_end) S +
  k_out^T u`.  `k_out` and G are transposed once per 128 positions, in float32,
  so the state stays [K, V] and the chunk's decay is a column.

PRECISION, the contract with `ops/kda.py` (its `EXACT` is three bf16 passes):
every operand is float32 and every product here is three bf16 passes with
float32 accumulation, split by hand (`_split`, `_dot`): `hi.hi + (hi.lo +
lo.hi)`, what XLA's `Precision.HIGH` is.  Mosaic's own dot takes `DEFAULT`
(ONE pass for float32 operands) or `HIGHEST` (six) only, so a float32 dot
without a precision would pass every interpret-mode test and be coarser on
the chip: no dot in this file takes float32 operands.  The one exception in
the other direction is G: the triangle of ones is exact in bf16, so g is split
in THREE and its running sums are exact to float32's own rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64  # the kernel's chunk: two of them are one [128, 128] tile
_PAIR = 2 * CHUNK
_LANES = 128  # K and V: one lane tile each
_ROWS = 512  # positions per program, see PERF.md section 6, PR 39

_NN = (((1,), (0,)), ((), ()))  # a b
_NT = (((1,), (1,)), ((), ()))  # a b^T


def chunks_per_program(per_segment: int) -> int:
    """The largest even number of chunks up to `_ROWS` positions that divides a
    segment's (so a segment starts at a program's first chunk)."""
    n = min(_ROWS // CHUNK, per_segment) // 2 * 2
    while n and per_segment % n:
        n -= 2
    return n


def supported(dk: int, dv: int, chunk: int, per_segment: int) -> bool:
    """Whether the kernel takes these shapes (else `ops/kda.py` runs the plain
    form): chunks of 64, heads of one lane tile, segments of whole pairs of chunks."""
    return chunk == CHUNK and dk == _LANES and dv == _LANES and chunks_per_program(per_segment) > 0


def _split(x):
    """float32 -> its two bf16 halves: x = hi + lo to 16 bits."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(a, b, dims=_NN):
    """The float32 product of two `_split` operands in three bf16 passes."""
    mm = lambda x, y: jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)
    return mm(a[0], b[0]) + (mm(a[0], b[1]) + mm(a[1], b[0]))


def _pair(q, k, v, g, beta, state):
    """128 positions of one head: q, k, v, g [128, 128] float32, beta [128, 1],
    the state [K, V] that enters -> (o [128, V], the state that leaves)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n = _PAIR
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (row == col).astype(f32)

    # G: ones are exact in bf16, so g in three parts gives float32's own sums
    tril = ((row // CHUNK == col // CHUNK) & (col <= row)).astype(bf16)
    g_hi = g.astype(bf16)
    g_lo = (g - g_hi.astype(f32)).astype(bf16)
    g_rest = (g - g_hi.astype(f32) - g_lo.astype(f32)).astype(bf16)
    cumsum = lambda part: jax.lax.dot_general(tril, part, _NN, preferred_element_type=f32)
    G = cumsum(g_hi) + (cumsum(g_lo) + cumsum(g_rest))

    qk = jnp.sum(q * k, axis=1, keepdims=True) * eye
    X = eye
    H = G
    for level in range(CHUNK.bit_length() - 1):
        size = 1 << level
        if level:
            H = jnp.where((row >> (level - 1)) & 1 == 1, pltpu.roll(H, size // 2, 0), H)
        in_lower = (row >> level) & 1 == 1  # [n, n]: lanes stand for channels here (K = n)
        rel = jnp.where(in_lower, G - H, pltpu.roll(H, n - size, 0) - G)
        decay = jnp.exp(jnp.minimum(rel, 0.0))
        kd = k * decay
        upper = _split(jnp.where(in_lower, 0.0, kd))
        # lower-half row, upper-half column, one pair (a pair never crosses a chunk)
        keep = ((row >> level) - (col >> level) == 1) & in_lower
        cross_q = _dot(_split(jnp.where(in_lower, q * decay, 0.0)), upper, _NT)
        cross_k = _dot(_split(jnp.where(in_lower, kd, 0.0)), upper, _NT)
        qk = qk + jnp.where(keep, cross_q, 0.0)
        M = jnp.where(keep, cross_k, 0.0) * beta
        if level == 0:
            X = X - M
        else:
            Xs = _split(X)
            X = X - _dot(Xs, _split(_dot(_split(M), Xs)))

    from_start = jnp.exp(jnp.minimum(G, 0.0))
    Xs = _split(X)
    u_bar = _dot(Xs, _split(beta * v))
    w = _dot(Xs, _split(beta * (k * from_start)))
    q_in = _split(q * from_start)
    ends = [jnp.broadcast_to(G[c * CHUNK + CHUNK - 1: (c + 1) * CHUNK], (CHUNK, n)) for c in range(n // CHUNK)]
    k_out_t = (k * jnp.exp(jnp.minimum(jnp.concatenate(ends, axis=0) - G, 0.0))).T  # [K, positions]
    through = jnp.exp(jnp.minimum(G.T, 0.0))  # [K, positions]: column 63 of a chunk is its whole decay

    k_out_t = _split(k_out_t)
    us, o_state = [], []
    for c in range(n // CHUNK):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        S = _split(state)
        u = u_bar[rows] - _dot(_split(w[rows]), S)
        o_state.append(_dot((q_in[0][rows], q_in[1][rows]), S))
        us.append(u)
        # k_out^T [K, 128 positions] against this chunk's u alone: the other chunk's rows are zeros
        u_rows = jnp.concatenate([u if i == c else jnp.zeros_like(u) for i in range(n // CHUNK)], axis=0)
        last = c * CHUNK + CHUNK - 1
        state = state * through[:, last: last + 1] + _dot(k_out_t, _split(u_rows))
    o = jnp.concatenate(o_state, axis=0) + _dot(_split(qk), _split(jnp.concatenate(us, axis=0)))
    return o, state


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, entering_ref, state_ref):
    f32 = jnp.float32
    h, first_of_segment = pl.program_id(1), pl.program_id(3) == 0

    @pl.when((pl.program_id(2) == 0) & first_of_segment)
    def _zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(first_of_segment)
    def _keep():
        entering_ref[...] = state_ref[...]

    betas = beta_ref[...]  # [positions, H]: this head's column
    lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    beta = jnp.sum(jnp.where(lane == h, betas.astype(f32), 0.0), axis=1, keepdims=True)
    state = state_ref[...]
    for j in range(q_ref.shape[0] // 2):
        two = lambda ref: ref[2 * j: 2 * j + 2].reshape(_PAIR, _LANES).astype(f32)
        o, state = _pair(two(q_ref), two(k_ref), two(v_ref), two(g_ref), beta[j * _PAIR: (j + 1) * _PAIR], state)
        o_ref[2 * j: 2 * j + 2] = o.reshape(2, CHUNK, _LANES)
    state_ref[...] = state


def kda_fwd(q, k, v, g, beta, *, interpret=False):
    """q, k, v, g [segments, b, c, H, 64, 128] in any float dtype (`ops/kda.py:_segments`:
    c chunks a segment, the layout the backward reads too, so the step holds
    one copy), beta [b, S, H] -> (o [segments, b, c, H, 64, 128] float32, the
    state that enters each segment [segments, b, H, K, V] float32)."""
    n, b, c, h, l, dk = k.shape
    dv = v.shape[-1]
    if not supported(dk, dv, l, c):
        raise ValueError(f"kda_fwd: unsupported shapes {k.shape}, {v.shape}")
    per = chunks_per_program(c)
    block = pl.BlockSpec((None, None, per, None, CHUNK, _LANES), lambda bi, hi, si, i: (si, bi, i, hi, 0, 0))
    call = pl.pallas_call(
        _fwd_kernel,
        name="kda_fwd",
        interpret=interpret,
        grid=(b, h, n, c // per),
        in_specs=[block, block, block, block,
                  pl.BlockSpec((None, per * CHUNK, h), lambda bi, hi, si, i: (bi, si * (c // per) + i, 0))],
        out_specs=[block, pl.BlockSpec((None, None, None, dk, dv), lambda bi, hi, si, i: (si, bi, hi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32), jax.ShapeDtypeStruct((n, b, h, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
    )
    with jax.named_scope("kda_fwd"):
        return call(q, k, v, g, beta)
