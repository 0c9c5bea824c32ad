"""The chunked KDA recurrence in Pallas for TPU, forward (`kda_fwd`, PR 39)
and backward (`kda_bwd`, PR 41).  `ops/kda.py` has the recurrence, its chunked
form and the plain `jax.numpy` code these equal: `_segment` and JAX's own
differentiation of it, which stay the path for every other platform and shape
and are what the tests hold both kernels to.

The forward.  One program takes `rows` positions of one head (a multiple of 128: two chunks
of 64) and keeps in VMEM what the plain form writes to HBM a level at a time:
the running sums G, the by-halves levels of both [chunk, chunk] matrices, the
inverse of the `k.k` system, `exp(G)`, `u_bar`, `w`, `q_in`, `k_out`.  The
sequence is the grid's LAST, sequential axis and the head's state [K, V] rides
it in a VMEM scratch, so the chain of chunk states never leaves the chip
either; the state that enters each `segment` of positions is the one thing
written beside o, and for a backward (`pair_states`) the state that enters
each pair of chunks.

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

The backward.  The forward's grid with the sequence walked from its END, the
state's cotangent [K, V] in the VMEM scratch, the same segmented arrays read
(v in bf16; the cotangents leave in their arguments' dtypes), and per 128
positions `_pair_bwd`: the forward's arithmetic again from the state that
entered the pair (nothing of the forward's is read back but that state), then
the cotangents by hand, which needs no level of the inverse differentiated
(`dM = -X^T dX X^T`), the by-halves levels once more for the decayed products
(`d lower = P upper`, `d upper = P^T lower`), and dg as the transposed
triangle-of-ones product.  About 200 three-pass products a pair where the
forward has 90, and nine float32 transposes.  The same precision contract: no
dot with float32 operands, no bf16 where the forward has float32, every
exponent clamped at 0 (and differentiated as the identity the clamp is on
exact values; the plain form has no clamp), the running sums of g and of dG
exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

CHUNK = 64  # the kernel's chunk: two of them are one [128, 128] tile
_PAIR = 2 * CHUNK
_LANES = 128  # K and V: one lane tile each
_ROWS = 512  # positions per program, both directions: PERF.md section 6, PRs 39 and 41

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


def _head_column(beta_ref, h):
    """beta_ref [positions, H] -> head h's column [positions, 1] float32."""
    betas = beta_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    return jnp.sum(jnp.where(lane == h, betas.astype(jnp.float32), 0.0), axis=1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, entering_ref, *rest):
    *pairs_ref, state_ref = rest  # with `pair_states`, one more output: the state that enters each pair of chunks
    f32 = jnp.float32
    h, first_of_segment = pl.program_id(1), pl.program_id(3) == 0

    @pl.when((pl.program_id(2) == 0) & first_of_segment)
    def _zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(first_of_segment)
    def _keep():
        entering_ref[...] = state_ref[...]

    beta = _head_column(beta_ref, h)
    state = state_ref[...]
    for j in range(q_ref.shape[0] // 2):
        two = lambda ref: ref[2 * j: 2 * j + 2].reshape(_PAIR, _LANES).astype(f32)
        if pairs_ref:
            pairs_ref[0][j] = state
        o, state = _pair(two(q_ref), two(k_ref), two(v_ref), two(g_ref), beta[j * _PAIR: (j + 1) * _PAIR], state)
        o_ref[2 * j: 2 * j + 2] = o.reshape(2, CHUNK, _LANES)
    state_ref[...] = state


def kda_fwd(q, k, v, g, beta, *, pair_states=False, interpret=False):
    """q, k, v, g [segments, b, c, H, 64, 128] in any float dtype (`ops/kda.py:_segments`:
    c chunks a segment, the layout the backward reads too, so the step holds
    one copy), beta [b, S, H] -> (o [segments, b, c, H, 64, 128] float32, the
    state that enters each segment [segments, b, H, K, V] float32) and, with
    `pair_states`, the state that enters each pair of chunks
    [segments, b, c / 2, H, K, V] float32: what `kda_bwd` starts each 128
    positions from (64 KB a head and pair, written where the forward runs for
    its backward)."""
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
        out_specs=[block, pl.BlockSpec((None, None, None, dk, dv), lambda bi, hi, si, i: (si, bi, hi, 0, 0))]
        + [pl.BlockSpec((None, None, per // 2, None, dk, dv), lambda bi, hi, si, i: (si, bi, i, hi, 0, 0))]
        * pair_states,
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32), jax.ShapeDtypeStruct((n, b, h, dk, dv), jnp.float32)]
        + [jax.ShapeDtypeStruct((n, b, c // 2, h, dk, dv), jnp.float32)] * pair_states,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
    )
    with tracing.scope("kda_fwd", kernel=True):
        return call(q, k, v, g, beta)

def _thirds(x):
    """float32 -> three bf16 parts that sum to it exactly: for a product with
    ones (exact in bf16), whose sums are then float32's own."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    lo = (x - hi.astype(f32)).astype(bf16)
    return hi, lo, (x - hi.astype(f32) - lo.astype(f32)).astype(bf16)


def _alone(x, c):
    """x [64, d], the rows of chunk c -> [128, d] with every other chunk's rows
    zero: a product that contracts the 128 positions then sums over c's alone."""
    return jnp.concatenate([x if i == c else jnp.zeros_like(x) for i in range(_PAIR // CHUNK)], axis=0)


def _pair_bwd(q, k, v, g, beta, state, d_o, d_state):
    """`_pair` backwards: its arguments, the cotangent of its o [128, V] and of
    the state that leaves [K, V] -> (dq, dk, dv, dg [128, 128], dbeta as a ROW
    [1, 128], the cotangent of the state that enters).

    Three phases.  (A) `_pair`'s forward again, keeping each level's decayed
    operands, and the state that enters the second chunk.  (B) The state chain
    backwards, chunk 1 then chunk 0, which gives the cotangents of u, QK, q_in,
    k_out, w and, through `[u_bar | w] = X rhs`, of X and rhs.  With
    `X = (I + M)^-1`, `dM = -X^T dX X^T` kept where M has entries: no level of
    the inverse is differentiated.  (C) Level by level, `d lower = P upper`,
    `d upper = P^T lower` with P the level's entries of dQK (for q) and of
    dM beta (for k); the decays' cotangents go to G directly (`rel = G - H` on a
    lower-half row, `H' - G` on an upper-half one) and through H, whose chain
    of rolls and selects is walked backwards once, after the last level.
    `dg` is the transposed triangle-of-ones product of dG.  The clamps
    `min(.., 0)` are the identity on every exact value, and are differentiated
    as that (the plain form has none)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n = _PAIR
    chunks = n // CHUNK
    levels = CHUNK.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (row == col).astype(f32)
    same_chunk = row // CHUNK == col // CHUNK
    ones_dot = lambda ones, x: sum(jax.lax.dot_general(ones, part, _NN, preferred_element_type=f32)
                                   for part in _thirds(x)[::-1])  # exact: `_pair`'s G

    # (A) the forward again
    G = ones_dot((same_chunk & (col <= row)).astype(bf16), g)
    qk = jnp.sum(q * k, axis=1, keepdims=True) * eye
    A = jnp.zeros((n, n), f32)  # M = A * beta
    X = eye
    H = G
    kept = []
    for level in range(levels):
        size = 1 << level
        if level:
            H = jnp.where((row >> (level - 1)) & 1 == 1, pltpu.roll(H, size // 2, 0), H)
        in_lower = (row >> level) & 1 == 1
        rel = jnp.where(in_lower, G - H, pltpu.roll(H, n - size, 0) - G)
        decay = jnp.exp(jnp.minimum(rel, 0.0))
        kd = k * decay
        upper = _split(jnp.where(in_lower, 0.0, kd))
        lower_q = _split(jnp.where(in_lower, q * decay, 0.0))
        lower_k = _split(jnp.where(in_lower, kd, 0.0))
        keep = ((row >> level) - (col >> level) == 1) & in_lower
        qk = qk + jnp.where(keep, _dot(lower_q, upper, _NT), 0.0)
        cross_k = jnp.where(keep, _dot(lower_k, upper, _NT), 0.0)
        A = A + cross_k
        M = cross_k * beta
        if level == 0:
            X = X - M
        else:
            Xs = _split(X)
            X = X - _dot(Xs, _split(_dot(_split(M), Xs)))
        kept.append((decay, lower_q, lower_k, upper))

    from_start = jnp.exp(jnp.minimum(G, 0.0))
    Xs = _split(X)
    rhs_v = _split(beta * v)
    k_from_start = k * from_start
    rhs_k = _split(beta * k_from_start)
    u_bar = _dot(Xs, rhs_v)
    w = _dot(Xs, rhs_k)
    q_in = q * from_start
    ends = [G[c * CHUNK + CHUNK - 1: (c + 1) * CHUNK] for c in range(chunks)]  # [1, K] each: the chunk's last row
    to_end = jnp.exp(jnp.minimum(jnp.concatenate([jnp.broadcast_to(e, (CHUNK, n)) for e in ends], axis=0) - G, 0.0))
    k_out = k * to_end
    through_t = jnp.exp(jnp.minimum(G.T, 0.0))  # [K, positions]
    k_out_t = _split(k_out.T)
    states, us = [state], []
    for c in range(chunks):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        us.append(u_bar[rows] - _dot(_split(w[rows]), _split(states[c])))
        if c + 1 < chunks:
            last = c * CHUNK + CHUNK - 1
            states.append(states[c] * through_t[:, last: last + 1] + _dot(k_out_t, _split(_alone(us[c], c))))
    u = jnp.concatenate(us, axis=0)

    # (B) the state chain backwards
    d_os = _split(d_o)
    from_qk = _dot(_split(qk.T), d_os)  # QK^T do
    q_in_t, w_t = _split(q_in.T), _split(w.T)
    by_chunk = []
    for c in reversed(range(chunks)):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        last = c * CHUNK + CHUNK - 1
        S, d_left = _split(states[c]), _split(d_state)
        d_u = from_qk[rows] + _dot(_split(k_out[rows]), d_left)
        d_k_out = _dot(_split(us[c]), d_left, _NT)
        d_q_in = _dot((d_os[0][rows], d_os[1][rows]), S, _NT)
        d_w = -_dot(_split(d_u), S, _NT)
        # the chunk's whole decay, as a ROW over K: what its cotangent adds to every dg of the chunk
        d_end = jnp.sum((states[c] * d_state).T, axis=0, keepdims=True) * jnp.exp(jnp.minimum(ends[c], 0.0))
        by_chunk.insert(0, (d_u, d_w, d_q_in, d_k_out, d_end))
        d_state = (d_state * through_t[:, last: last + 1] + _dot(q_in_t, _split(_alone(d_o[rows], c)))
                   - _dot(w_t, _split(_alone(d_u, c))))
    *whole, d_ends = zip(*by_chunk)
    d_u, d_w, d_q_in, d_k_out = (jnp.concatenate(x, axis=0) for x in whole)
    d_qk = jnp.where(same_chunk & (col <= row), _dot(d_os, _split(u), _NT), 0.0)

    d_u, d_w = _split(d_u), _split(d_w)  # u = u_bar - w S: d_u is u_bar's cotangent too
    Xt = _split(X.T)
    d_rhs_v, d_rhs_k = _dot(Xt, d_u), _dot(Xt, d_w)
    d_X = _dot(d_u, rhs_v, _NT) + _dot(d_w, rhs_k, _NT)
    d_M = jnp.where(same_chunk & (col < row), -_dot(_split(_dot(Xt, _split(d_X))), Xt), 0.0)
    d_A = d_M * beta
    d_to_end = d_k_out * k_out  # d(G_end - G)
    d_from_start = (d_q_in * q + beta * d_rhs_k * k) * from_start  # dG through exp(G)
    on_diagonal = jnp.sum(d_qk * eye, axis=1, keepdims=True)
    dq = d_q_in * from_start + on_diagonal * k
    dk = d_k_out * to_end + beta * d_rhs_k * from_start + on_diagonal * q
    dv = beta * d_rhs_v
    for_beta = d_rhs_v * v + d_rhs_k * k_from_start + d_M * A  # its row sums are dbeta
    d_G = d_from_start - d_to_end
    d_ends = [e + jnp.sum(d_to_end[c * CHUNK: (c + 1) * CHUNK], axis=0, keepdims=True) for c, e in enumerate(d_ends)]

    # (C) the levels
    d_qk_t, d_A_t = d_qk.T, d_A.T
    d_H = []
    for level, (decay, lower_q, lower_k, upper) in enumerate(kept):
        size = 1 << level
        in_lower = (row >> level) & 1 == 1
        keep = ((row >> level) - (col >> level) == 1) & in_lower
        keep_t = ((col >> level) - (row >> level) == 1) & ((col >> level) & 1 == 1)
        d_lower_q = _dot(_split(jnp.where(keep, d_qk, 0.0)), upper)  # zero on the rows of an upper half
        # of k * decay: the rows of a lower half from the first product, those of an upper half from the other two
        d_kd = (_dot(_split(jnp.where(keep, d_A, 0.0)), upper)
                + _dot(_split(jnp.where(keep_t, d_qk_t, 0.0)), lower_q)
                + _dot(_split(jnp.where(keep_t, d_A_t, 0.0)), lower_k))
        dq = dq + d_lower_q * decay
        dk = dk + d_kd * decay
        d_rel = (d_lower_q * q + d_kd * k) * decay
        d_G = d_G + jnp.where(in_lower, d_rel, -d_rel)
        d_H.append(pltpu.roll(jnp.where(in_lower, 0.0, d_rel), size, 0) - jnp.where(in_lower, d_rel, 0.0))
    into_H = d_H[-1]
    for level in range(levels - 1, 0, -1):  # H of `level` is H of `level - 1`, rolled on the rows whose bit is set
        moved = (row >> (level - 1)) & 1 == 1
        into_H = (jnp.where(moved, 0.0, into_H) + pltpu.roll(jnp.where(moved, into_H, 0.0), n - (1 << (level - 1)), 0)
                  + d_H[level - 1])
    d_G = d_G + into_H
    dg = ones_dot((same_chunk & (col >= row)).astype(bf16), d_G)
    dg = dg + jnp.concatenate([jnp.broadcast_to(e, (CHUNK, n)) for e in d_ends], axis=0)
    dbeta = jnp.sum(for_beta.T, axis=0, keepdims=True)
    return dq, dk, dv, dg, dbeta, d_state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, d_o_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state_ref):
    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _zero():  # the grid walks the sequence from its END: nothing leaves the last chunk
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    beta = _head_column(beta_ref, pl.program_id(1))
    d_state = d_state_ref[...]
    for j in reversed(range(q_ref.shape[0] // 2)):
        two = lambda ref: ref[2 * j: 2 * j + 2].reshape(_PAIR, _LANES).astype(jnp.float32)
        *d_inputs, dbeta, d_state = _pair_bwd(two(q_ref), two(k_ref), two(v_ref), two(g_ref),
                                              beta[j * _PAIR: (j + 1) * _PAIR], states_ref[j], two(d_o_ref), d_state)
        for ref, d in zip((dq_ref, dk_ref, dv_ref, dg_ref), d_inputs):
            ref[2 * j: 2 * j + 2] = d.reshape(2, CHUNK, _LANES).astype(ref.dtype)
        dbeta_ref[:, j * _PAIR: (j + 1) * _PAIR] = dbeta
    d_state_ref[...] = d_state


def kda_bwd(q, k, v, g, beta, states, d_o, *, interpret=False):
    """`kda_fwd`'s arguments, the state that enters each PAIR of chunks
    [segments, b, c / 2, H, K, V] (`kda_fwd(.., pair_states=True)`) and the
    cotangent of o [segments, b, c, H, 64, 128] -> (dq, dk, dv, dg in the
    layout and dtype of q, k, v, g; dbeta [b, S, H] in beta's dtype).  The
    forward's grid and chunks a program, the sequence walked from its END, the
    state's cotangent [K, V] in the VMEM scratch.  A program writes its head's
    dbeta as a row of [b, H, 1, S]: lanes, where beta's own layout would be one
    lane of 32 a store."""
    n, b, c, h, l, dk = k.shape
    dv = v.shape[-1]
    if not supported(dk, dv, l, c):
        raise ValueError(f"kda_bwd: unsupported shapes {k.shape}, {v.shape}")
    per = chunks_per_program(c)
    programs = c // per

    def blocks(bi, hi, si, i):  # segments and programs from the END
        return n - 1 - si, bi, programs - 1 - i, hi, 0, 0

    def position(si, i):  # the program's place in the whole sequence
        return (n - 1 - si) * programs + programs - 1 - i

    block = pl.BlockSpec((None, None, per, None, CHUNK, _LANES), blocks)
    call = pl.pallas_call(
        _bwd_kernel,
        name="kda_bwd",
        interpret=interpret,
        grid=(b, h, n, programs),
        in_specs=[block, block, block, block,
                  pl.BlockSpec((None, per * CHUNK, h), lambda bi, hi, si, i: (bi, position(si, i), 0)),
                  pl.BlockSpec((None, None, per // 2, None, dk, dv), blocks),
                  block],
        out_specs=[block, block, block, block,
                   pl.BlockSpec((None, None, 1, per * CHUNK), lambda bi, hi, si, i: (bi, hi, 0, position(si, i)))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g)]
        + [jax.ShapeDtypeStruct((b, h, 1, n * c * l), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
    )
    with tracing.scope("kda_bwd", kernel=True):
        *d_inputs, dbeta = call(q, k, v, g, beta, states, d_o)
        return (*d_inputs, jnp.moveaxis(dbeta[:, :, 0], 1, 2).astype(beta.dtype))
