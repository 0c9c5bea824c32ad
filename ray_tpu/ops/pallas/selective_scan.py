"""Mamba-1's selective scan in Pallas for TPU, both directions
(`ops/selective_scan.py` has the contract, the plain form and the dispatch).

Forward (`s6_scan_fwd`, PR 42).  One program holds a block of channels in the
lanes and walks a block of the sequence a position at a time, the recurrence
itself:

    h = exp(dt_t * A) * h + (dt_t * x_t) * B_t        [N, channels] float32
    y_t = sum_n C_t[n] * h[n] + D * x_t

The state lives in a VMEM scratch across the sequence's blocks (the last grid
axis, `arbitrary`; zeroed at the first) and in vector registers inside one; N
is in the sublanes, so the sum over n is an add of sublane tiles and one
reduction of 8 sublanes, which eight positions share (`_rows_of_sums`).  The
full-size arrays cross HBM once: x and y in x's dtype, dt in float32, and the
state that ENTERS each chunk, which is what the backward starts from.

Backward (`s6_scan_bwd`, PR 51).  The same blocks, the sequence walked FROM
THE END with the state's cotangent `dh` [N, channels] in the scratch (zeroed
at the last block).  A chunk at a time: the recurrence once more from the
chunk's entering state, its `chunk` states kept in VMEM ([32, 16, 512]
float32: 1 MB), then the positions in reverse:

    dh   += C_t * dy_t
    dx_t  = D * dy_t + dt_t * sum_n dh * B_t
    ddt_t = sum_n dh * (A * a_t * h_{t-1} + x_t * B_t)      a_t = exp(dt_t * A)
    dA   += dh * dt_t * a_t * h_{t-1};   dD += dy_t * x_t
    dB_t  = sum_c dh * dt_t * x_t;       dC_t = sum_c h_t * dy_t
    dh    = a_t * dh

The sums over n are the forward's; the sums over the channels of a block fold
the lane tiles by addition and then the 128 lanes with the positions of a
chunk sharing the levels (`_lanes_of_sums`), and leave as one float32 partial
a channel block, which XLA adds.  `dA` and `dD` accumulate in their output
blocks along the sequential axis, a row of the batch each.

`B_t` and `C_t` enter as columns: the caller hands them over as
[b, S / chunk, N, chunk] float32 (two small arrays), a chunk's tile is read
once and a position's column is broadcast along the lanes.

Precision is the plain form's (`ops/selective_scan.py`): every value float32,
every exponent `dt_t * A` <= 0, no quotient of exponentials, no matmul; y and
dx are rounded to x's dtype once, at the store.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.ssm_conv import _tile
from ray_tpu.ops.selective_scan import CHUNK
from ray_tpu.util import tracing

_LANES = 128
_SUBLANES = 8
# Positions read and written at once: one packed bf16 tile of x and of y.
_GROUP = 16
# Channels and positions of a program's block: PERF.md section 6, PR 42 has the sweep.
_BLOCK_C, _BLOCK_S = 512, 256


def supported(c: int, n: int, s: int, chunk: int) -> bool:
    """Whether the kernels take these shapes (else `ops/selective_scan.py`
    runs the plain form in both directions): whole lane tiles of channels,
    whole sublane tiles of states, chunks of whole `_GROUP`s whose positions
    share a lane tile's levels (`_lanes_of_sums`) and a sequence of whole
    blocks."""
    return (c % _LANES == 0 and n % _SUBLANES == 0 and chunk % _GROUP == 0 and _LANES % chunk == 0
            and _BLOCK_S % chunk == 0 and s % _BLOCK_S == 0)


def _rows_of_sums(s, row):
    """Eight [8, L] arrays -> one whose row j is the sum of s[j]'s rows: three
    levels, each folding the arrays by pairs into the halves, quarters and
    single rows of one (a reduction of each array alone costs three rolls and
    leaves one useful row in eight)."""
    roll = pltpu.roll
    low = (row & 4) == 0
    s = [jnp.where(low, s[j], s[j + 4]) + roll(jnp.where(low, s[j + 4], s[j]), 4, 0) for j in range(4)]
    low = (row & 2) == 0
    s = [jnp.where(low, s[j] + roll(s[j], 6, 0), s[j + 2] + roll(s[j + 2], 2, 0)) for j in range(2)]
    return jnp.where((row & 1) == 0, s[0] + roll(s[0], 7, 0), s[1] + roll(s[1], 1, 0))


def _lanes_of_sums(s, lane):
    """`chunk` arrays [N, 128] -> one whose lane p * (128 // chunk) + 128 // chunk - 1
    is the sum of s[p]'s lanes: `_rows_of_sums` along the lanes.  Each level folds
    the arrays by pairs into the halves of one, the first (which has most of the
    work) by one roll of half a tile; the last levels, inside the one array left,
    are a roll and an add each."""
    roll = pltpu.roll
    shift = _LANES // 2
    low = (lane & shift) == 0
    half = len(s) // 2
    s = [jnp.where(low, s[j], s[j + half]) + roll(jnp.where(low, s[j + half], s[j]), shift, 1) for j in range(half)]
    while len(s) > 1:
        shift //= 2
        low = (lane & shift) == 0
        half = len(s) // 2
        s = [jnp.where(low, s[j] + roll(s[j], _LANES - shift, 1), s[j + half] + roll(s[j + half], shift, 1))
             for j in range(half)]
    s = s[0]
    while shift > 1:
        shift //= 2
        s = s + roll(s, shift, 1)
    return s


def _decay(dt, k: int, A):
    """a_t [N, lanes] of position k of a group: the one exponential of this file, of a product that is <= 0."""
    return jnp.exp(dt[k: k + 1] * A)


def _fold(v, axis: int, tile: int):
    """The sum of v's tiles of `tile` rows (axis 0) or lanes (axis 1): vector adds."""
    cut = (lambda i: v[i: i + tile]) if axis == 0 else (lambda i: v[:, i: i + tile])
    return functools.reduce(jnp.add, [cut(i) for i in range(0, v.shape[axis], tile)])


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, entering_ref, h_ref, *, chunk: int):
    f32 = jnp.float32
    n, lanes = a_ref.shape
    positions = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...]
    D = d_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, lanes), 0)

    def one_chunk(ci, h):
        entering_ref[ci, 0] = h
        b_cols, c_cols = b_ref[0, ci], c_ref[0, ci]  # [N, chunk]
        for g in range(chunk // _GROUP):
            at = pl.ds(pl.multiple_of(ci * chunk + g * _GROUP, _GROUP), _GROUP)
            x = x_ref[0, at, :].astype(f32)
            dt = dt_ref[0, at, :]
            dtx = dt * x
            tiles = []
            for half in range(_GROUP // _SUBLANES):
                sums = []
                for j in range(_SUBLANES):
                    k = half * _SUBLANES + j
                    p = g * _GROUP + k
                    h = _decay(dt, k, A) * h + dtx[k: k + 1] * b_cols[:, p: p + 1]
                    sums.append(_fold(h * c_cols[:, p: p + 1], 0, _SUBLANES))
                tiles.append(_rows_of_sums(sums, row))
            y = jnp.concatenate(tiles, axis=0) + D * x
            y_ref[0, at, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, positions // chunk, one_chunk, h_ref[...])


def _bwd_kernel(x_ref, dt_ref, dy_ref, a_ref, b_ref, c_ref, d_ref, entering_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref, dh_ref, hs_ref, *, chunk: int):
    f32 = jnp.float32
    n, lanes = a_ref.shape
    per = x_ref.shape[1] // chunk

    @pl.when(pl.program_id(2) == 0)  # the sequence's LAST block
    def _start():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    A = a_ref[...]
    D = d_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, lanes), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)

    def one_chunk(i, dh):
        ci = per - 1 - i
        b_cols, c_cols = b_ref[0, ci], c_ref[0, ci]  # [N, chunk]
        groups = [pl.ds(pl.multiple_of(ci * chunk + g * _GROUP, _GROUP), _GROUP) for g in range(chunk // _GROUP)]

        # the recurrence once more, from the state that entered the chunk
        h = entering_ref[ci, 0]
        for g, at in enumerate(groups):
            dt = dt_ref[0, at, :]
            dtx = dt * x_ref[0, at, :].astype(f32)
            for k in range(_GROUP):
                p = g * _GROUP + k
                h = _decay(dt, k, A) * h + dtx[k: k + 1] * b_cols[:, p: p + 1]
                hs_ref[p] = h

        # and its positions from the last to the first
        dA = jnp.zeros_like(A)
        dD = jnp.zeros((_GROUP, lanes), f32)
        of_b, of_c = [None] * chunk, [None] * chunk
        for g, at in reversed(list(enumerate(groups))):
            x = x_ref[0, at, :].astype(f32)
            dt = dt_ref[0, at, :]
            dy = dy_ref[0, at, :].astype(f32)
            dtx = dt * x
            dD = dD + dy * x
            halves = _GROUP // _SUBLANES
            of_x, of_dt = [None] * halves, [None] * halves
            for half in reversed(range(halves)):
                sums_x, sums_dt = [None] * _SUBLANES, [None] * _SUBLANES
                for j in reversed(range(_SUBLANES)):
                    k = half * _SUBLANES + j
                    p = g * _GROUP + k
                    before = hs_ref[p - 1] if p else entering_ref[ci, 0]
                    of_c[p] = _fold(h * dy[k: k + 1], 1, _LANES)
                    dh = dh + c_cols[:, p: p + 1] * dy[k: k + 1]
                    of_b[p] = _fold(dh * dtx[k: k + 1], 1, _LANES)
                    sums_x[j] = _fold(dh * b_cols[:, p: p + 1], 0, _SUBLANES)
                    dh = _decay(dt, k, A) * dh  # h_{t-1}'s cotangent, before C_{t-1} * dy_{t-1} joins it
                    through = dh * before  # dh_t * a_t * h_{t-1}: what dt_t * A moves
                    dA = dA + dt[k: k + 1] * through
                    sums_dt[j] = _fold(A * through, 0, _SUBLANES)
                    h = before
                of_x[half] = _rows_of_sums(sums_x, row)
                of_dt[half] = _rows_of_sums(sums_dt, row)
            of_x = jnp.concatenate(of_x, axis=0)  # [_GROUP, lanes]: sum_n dh * B_t
            dx_ref[0, at, :] = (D * dy + dt * of_x).astype(dx_ref.dtype)
            ddt_ref[0, at, :] = jnp.concatenate(of_dt, axis=0) + x * of_x
        da_ref[0] += dA
        dd_ref[0] += jnp.sum(dD, axis=0, keepdims=True)
        db_ref[0, 0, ci] = _lanes_of_sums(of_b, lane)
        dc_ref[0, 0, ci] = _lanes_of_sums(of_c, lane)
        return dh

    dh_ref[...] = jax.lax.fori_loop(0, per, one_chunk, dh_ref[...])


def columns(m: jax.Array, chunk: int) -> jax.Array:
    """B or C [b, S, N] -> [b, S / chunk, N, chunk] float32: a position's
    vector as a column of its chunk's tile."""
    b, s, n = m.shape
    return m.astype(jnp.float32).reshape(b, s // chunk, chunk, n).swapaxes(2, 3)


def _rows(partials: jax.Array, chunk: int) -> jax.Array:
    """`columns` back, from what `_lanes_of_sums` left a channel block:
    [blocks, b, S / chunk, N, 128] -> [b, S, N] float32, the blocks added."""
    spread = _LANES // chunk
    cols = jnp.sum(partials, axis=0)[..., spread - 1:: spread]
    b, chunks, n, _ = cols.shape
    return cols.swapaxes(2, 3).reshape(b, chunks * chunk, n)


def s6_scan_fwd(x, dt, A_t, B, C, D, *, chunk: int = CHUNK, block_c=None, block_s=None, interpret=False):
    """x [b, S, C]; dt [b, S, C] float32 (positive); A_t [N, C] float32
    (negative); B, C [b, S, N]; D [C] -> (y [b, S, C] in x's dtype, the state
    that enters each chunk [S / chunk, b, N, C] float32)."""
    f32 = jnp.float32
    b, s, c = x.shape
    n = A_t.shape[0]
    lanes = _tile(c, block_c or _BLOCK_C, _LANES)
    positions = block_s or _BLOCK_S
    per = positions // chunk
    main = pl.BlockSpec((1, positions, lanes), lambda bi, j, i: (bi, i, j))
    cols = pl.BlockSpec((1, per, n, chunk), lambda bi, j, i: (bi, i, 0, 0))
    call = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        name="s6_scan_fwd",
        interpret=interpret,
        grid=(b, c // lanes, s // positions),
        in_specs=[
            main, main,
            pl.BlockSpec((n, lanes), lambda bi, j, i: (0, j)),
            cols, cols,
            pl.BlockSpec((1, lanes), lambda bi, j, i: (0, j)),
        ],
        out_specs=[main, pl.BlockSpec((per, 1, n, lanes), lambda bi, j, i: (i, bi, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((s // chunk, b, n, c), f32)],
        scratch_shapes=[pltpu.VMEM((n, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=9 * x.size * n, transcendentals=x.size * n,
            bytes_accessed=x.size * (2 * x.dtype.itemsize + 4) + 4 * x.size * n // chunk),
    )
    with tracing.scope("s6_scan_fwd", kernel=True):
        return call(x, dt.astype(f32), A_t.astype(f32), columns(B, chunk), columns(C, chunk),
                    D.astype(f32)[None, :])


def s6_scan_bwd(x, dt, A_t, B, C, D, entering, dy, *, chunk: int = CHUNK, block_c=None, block_s=None,
                interpret=False):
    """`s6_scan_fwd`'s arguments, the entering states it wrote and y's
    cotangent [b, S, C] -> the cotangents of (x, dt, A_t, B, C, D): dx and dB,
    dC in their arguments' dtypes, the others float32."""
    f32 = jnp.float32
    b, s, c = x.shape
    n = A_t.shape[0]
    lanes = _tile(c, block_c or _BLOCK_C, _LANES)
    positions = block_s or _BLOCK_S
    per, last = positions // chunk, s // positions - 1
    main = pl.BlockSpec((1, positions, lanes), lambda bi, j, i: (bi, last - i, j))
    cols = pl.BlockSpec((1, per, n, chunk), lambda bi, j, i: (bi, last - i, 0, 0))
    partial = pl.BlockSpec((1, 1, per, n, _LANES), lambda bi, j, i: (j, bi, last - i, 0, 0))
    partials = jax.ShapeDtypeStruct((c // lanes, b, s // chunk, n, _LANES), f32)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        name="s6_scan_bwd",
        interpret=interpret,
        grid=(b, c // lanes, s // positions),
        in_specs=[
            main, main, main,
            pl.BlockSpec((n, lanes), lambda bi, j, i: (0, j)),
            cols, cols,
            pl.BlockSpec((1, lanes), lambda bi, j, i: (0, j)),
            pl.BlockSpec((per, 1, n, lanes), lambda bi, j, i: (last - i, bi, 0, j)),
        ],
        out_specs=[
            main, main,
            pl.BlockSpec((1, n, lanes), lambda bi, j, i: (bi, 0, j)),
            pl.BlockSpec((1, 1, lanes), lambda bi, j, i: (bi, 0, j)),
            partial, partial,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct((b, n, c), f32), jax.ShapeDtypeStruct((b, 1, c), f32),
            partials, partials,
        ],
        scratch_shapes=[pltpu.VMEM((n, lanes), f32), pltpu.VMEM((chunk, n, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=30 * x.size * n, transcendentals=2 * x.size * n,
            bytes_accessed=x.size * (3 * x.dtype.itemsize + 8) + 4 * x.size * n // chunk + 8 * partials.size),
    )
    with tracing.scope("s6_scan_bwd", kernel=True):
        dx, ddt, dA, dD, dB, dC = call(x, dt.astype(f32), dy, A_t.astype(f32), columns(B, chunk),
                                       columns(C, chunk), D.astype(f32)[None, :], entering)
        return (dx, ddt, jnp.sum(dA, axis=0), _rows(dB, chunk).astype(B.dtype), _rows(dC, chunk).astype(C.dtype),
                jnp.sum(dD, axis=(0, 1)))
