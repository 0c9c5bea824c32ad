"""The forward of Mamba-1's selective scan in Pallas for TPU
(`ops/selective_scan.py` has the contract, the plain form and the backward).

One program holds a block of channels in the lanes and walks a block of the
sequence a position at a time, the recurrence itself:

    h = exp(dt_t * A) * h + (dt_t * x_t) * B_t        [N, channels] float32
    y_t = sum_n C_t[n] * h[n] + D * x_t

The state lives in a VMEM scratch across the sequence's blocks (the last grid
axis, `arbitrary`; zeroed at the first) and in vector registers inside one; N
is in the sublanes, so the sum over n is an add of sublane tiles and one
reduction of 8 sublanes, which eight positions share (`_rows_of_sums`).  The
full-size arrays cross HBM once: x and y in x's dtype, dt in float32, and the
state that ENTERS each chunk, which is what the backward starts from.

`B_t` and `C_t` enter as columns: the caller hands them over as
[b, S / chunk, N, chunk] float32 (two small arrays), a chunk's tile is read
once and a position's column is broadcast along the lanes.

Precision is the plain form's (`ops/selective_scan.py`): every value float32,
every exponent `dt_t * A` <= 0, no quotient of exponentials, no matmul; y is
rounded to x's dtype once, at the store.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.ssm_conv import _tile
from ray_tpu.ops.selective_scan import CHUNK

_LANES = 128
_SUBLANES = 8
# Positions read and written at once: one packed bf16 tile of x and of y.
_GROUP = 16
# Channels and positions of a program's block: PERF.md section 6, PR 42 has the sweep.
_BLOCK_C, _BLOCK_S = 512, 256


def supported(c: int, n: int, s: int, chunk: int) -> bool:
    """Whether the kernel takes these shapes (else `ops/selective_scan.py`
    runs the plain form): whole lane tiles of channels, whole sublane tiles of
    states, chunks of whole `_GROUP`s and a sequence of whole blocks."""
    return (c % _LANES == 0 and n % _SUBLANES == 0 and chunk % _GROUP == 0
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
                    h = jnp.exp(dt[k: k + 1] * A) * h + dtx[k: k + 1] * b_cols[:, p: p + 1]
                    ch = h * c_cols[:, p: p + 1]
                    sums.append(functools.reduce(
                        jnp.add, [ch[i: i + _SUBLANES] for i in range(0, n, _SUBLANES)]))
                tiles.append(_rows_of_sums(sums, row))
            y = jnp.concatenate(tiles, axis=0) + D * x
            y_ref[0, at, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, positions // chunk, one_chunk, h_ref[...])


def columns(m: jax.Array, chunk: int) -> jax.Array:
    """B or C [b, S, N] -> [b, S / chunk, N, chunk] float32: a position's
    vector as a column of its chunk's tile."""
    b, s, n = m.shape
    return m.astype(jnp.float32).reshape(b, s // chunk, chunk, n).swapaxes(2, 3)


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
    with jax.named_scope("s6_scan_fwd"):
        return call(x, dt.astype(f32), A_t.astype(f32), columns(B, chunk), columns(C, chunk),
                    D.astype(f32)[None, :])
