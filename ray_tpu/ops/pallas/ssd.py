"""Mamba-2's chunked scan (SSD) in Pallas for TPU, forward (`ssd_fwd`) and
backward (`ssd_bwd`).  `ops/ssm.py` has the recurrence, its chunked form and
the plain `jax.numpy` code these equal (`_plain_forward` and JAX's own
differentiation of it), which stay the path for every other platform and shape
and are what the tests hold both kernels to.

What the plain form writes to HBM and these keep in VMEM: `C B^T`, the decay
masks `exp(cum_t - cum_s)`, the scores and, backwards, their cotangents, all
[chunk, chunk] per head and chunk; the float32 `dt x`, the read-out and the
three terms of y before their one rounding.  What crosses HBM is x, y, B, C,
their cotangents, the small per-(position, head) arrays (dt, the running sum
`cum` of `dt A`, which XLA computes as the plain form does, and their
cotangents) and the state that ENTERS each chunk, [N, heads x head size]
float32, which the forward writes once and the backward starts each chunk from.

One program is one chunk of one batch row and one block of `heads` heads that
read the SAME group of B and C (`head_block`).  The chunks are the grid's
last, sequential axis and the block's state [N, heads x P] rides it in a VMEM
scratch (the backward walks the chunks from the END with the state's
cotangent there), so the pass over chunk states never leaves the chip.  The
heads' x, y, dy, dx are the lanes of [chunk, heads x P] tiles straight from
[b, S, H x P]: two heads of 64 (four of 32) share a 128-lane tile, and a product that is
one head's alone zeroes the other head's lanes of an operand (a [.., 64]
operand would fill the same 128 columns of the MXU).  The [chunk, chunk]
matrices are worked on in blocks of 128 x 128, the blocks above the diagonal
never (a quarter of the work at a chunk of 256): the forward holds them as
[t, s], the backward as [s, t], so every product with them is a plain or an
`a b^T` matmul and only B (C backwards) and the group's summed `d(C B^T)` are
transposed, once a program.  A per-(position, head) factor is a [chunk, 1]
column broadcast along its head's lanes.

Backwards, with W = d(scores) * (C B^T * decay): `cum_t` gets W's sum over s,
`cum_s` minus its sum over t (a row and a column of W [s, t]: both are
written, XLA adds them); `d(C B^T)` is summed over the program's heads before its two products
with B and C.  dB and dC leave as one float32 partial a program and are summed
over a group's programs by XLA; dD as a [1, heads x P] row summed over the
chunks in place; the reverse running sum that turns `d cum` into `d(dt A)`,
and dA, are XLA's on [b, S, H] arrays.

PRECISION, the contract with `ops/ssm.py`: dt, `dt A`, the running sums, every
exponential (always of a difference taken first, never a quotient), the chunk
states and every accumulation are float32; a matmul's operands are the inputs'
dtype (bf16 in a training cell) exactly where the plain form's are: C, B, the
scores, `dt x` and `exp(cum_last - cum) dt x` rounded where it rounds them, y
rounded once.  A float32 operand the plain form hands to a matmul at XLA's
default precision (the entering state in the read-out; backwards, the
cotangents) is rounded to the inputs' dtype at the product, which is what one
pass of the MXU does to it (PERF.md section 6, PR 49: read off the chip).
Every cotangent is float32 until it is such an operand or leaves, where the
plain form's backward rounds `d scores`, `d(dt x)` and the partial dB, dC to
bf16 between its products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

_LANES = 128
_SUBLANES = 8
_TILE = 128  # rows and columns of a block of the [chunk, chunk] matrices
_HEADS = 16  # heads a program, at most: PERF.md section 6, PR 49

_NN = (((1,), (0,)), ((), ()))  # a b
_NT = (((1,), (1,)), ((), ()))  # a b^T


def head_block(heads_per_group: int, p: int) -> int:
    """Heads of one program: the largest divisor of a group's heads up to
    `_HEADS` that fills whole lane tiles; 0 if none does."""
    for hb in range(min(_HEADS, heads_per_group), 0, -1):
        if heads_per_group % hb == 0 and (hb * p) % _LANES == 0:
            return hb
    return 0


def supported(h: int, p: int, n: int, groups: int, s: int, chunk: int) -> bool:
    """Whether the kernels take these shapes (else `ops/ssm.py` runs the plain
    form): whole chunks of whole 128-blocks, heads of a half or a quarter of a
    lane tile (a head of a whole tile would need its per-head factors broadcast
    along sublanes AND lanes at once, which Mosaic refuses), states of whole
    lane tiles, groups that divide the heads into blocks of
    whole lane tiles."""
    return (s % chunk == 0 and chunk % _TILE == 0 and p in (_LANES // 4, _LANES // 2) and n % _LANES == 0
            and groups > 0 and h % groups == 0 and head_block(h // groups, p) > 0)


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _decay(minuend, subtrahend, keep=None):
    """exp(minuend - subtrahend) as a block (a column against a row, either
    way round); in a diagonal block `keep` = "lower" / "upper" is the triangle
    that is causal, the rest exp(-inf) = 0."""
    diff = minuend - subtrahend
    if keep:
        row = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
        diff = jnp.where(row >= col if keep == "lower" else col >= row, diff, -jnp.inf)
    return jnp.exp(diff)


def _lanes_of(p: int):
    """A tile's lanes by head: [mask of head i's lanes [1, 128]]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return [lane // p == i for i in range(_LANES // p)]


def _along(per_head, first: int, masks):
    """per_head [rows, heads]: the columns of a tile's heads, each broadcast
    along its head's lanes -> [rows, 128]."""
    out = per_head[:, first: first + 1]
    for i, mask in enumerate(masks[1:], 1):
        out = jnp.where(mask, per_head[:, first + i: first + i + 1], out)
    return out


def _only(x, mask):
    return jnp.where(mask, x, jnp.zeros_like(x))


def _fwd_kernel(x_ref, dt_ref, cum_ref, cum_row_ref, d_ref, b_ref, c_ref, y_ref, entering_ref, state_ref, *, p: int):
    f32 = jnp.float32
    dtype = x_ref.dtype
    L = x_ref.shape[0]
    blocks = L // _TILE
    masks = _lanes_of(p)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = jnp.zeros_like(state_ref)

    Cm, Bm = c_ref[...], b_ref[...]  # [L, N]
    B_t = Bm.astype(f32).T.astype(dtype)  # [N, L]
    # C B^T, the rows of block tb against the columns up to its diagonal block
    cb = [_mm(Cm[tb * _TILE: (tb + 1) * _TILE], Bm[: (tb + 1) * _TILE], _NT) for tb in range(blocks)]
    dt, cum, cum_row = dt_ref[...], cum_ref[...], cum_row_ref[...]  # [L, heads] x 2, [heads, L]
    from_start = jnp.exp(cum)
    to_end = jnp.exp(cum[L - 1:] - cum)

    for tile in range(x_ref.shape[1] // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        first = tile * len(masks)
        xf = x_ref[:, lanes].astype(f32)
        dtx = _along(dt, first, masks) * xf
        mine = [_only(dtx, mask).astype(dtype) for mask in masks]  # dt x with the other head's lanes zero
        state = state_ref[:, lanes]  # [N, 128]
        entering_ref[:, lanes] = state

        within = []
        for tb in range(blocks):
            rows = slice(tb * _TILE, (tb + 1) * _TILE)
            acc = None
            for i in range(len(masks)):
                j = first + i
                decay = jnp.concatenate(
                    [_decay(cum[rows, j: j + 1], cum_row[j: j + 1, sb * _TILE: (sb + 1) * _TILE], sb == tb and "lower")
                     for sb in range(tb + 1)], axis=1)
                scores = (cb[tb] * decay).astype(dtype)
                part = _mm(scores, mine[i][: (tb + 1) * _TILE])
                acc = part if acc is None else acc + part
            within.append(acc)
        y = jnp.concatenate(within, axis=0)
        from_start_l = _along(from_start, first, masks)
        y = y + from_start_l * _mm(Cm, state.astype(dtype))
        y = y + d_ref[:, lanes] * xf
        y_ref[:, lanes] = y.astype(dtype)

        dtx_end = (_along(to_end, first, masks) * dtx).astype(dtype)
        state_ref[:, lanes] = state * from_start_l[L - 1:] + _mm(B_t, dtx_end)  # the whole chunk's decay


def _bwd_kernel(x_ref, dt_ref, cum_ref, cum_row_ref, d_ref, b_ref, c_ref, entering_ref, dy_ref,
                dx_ref, ddt_ref, dcum_ref, dcum_row_ref, db_ref, dc_ref, dd_ref, d_state_ref, *, p: int):
    f32 = jnp.float32
    dtype = x_ref.dtype
    L, heads = dt_ref.shape
    blocks = L // _TILE
    masks = _lanes_of(p)
    op = lambda a: a.astype(dtype)  # a float32 value as a matmul's operand (module docstring)

    @pl.when(pl.program_id(2) == 0)  # the grid walks the chunks from the END: nothing leaves the last
    def _start():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Cm, Bm = c_ref[...], b_ref[...]  # [L, N]
    C_t = Cm.astype(f32).T.astype(dtype)  # [N, L]
    # (C B^T)^T = B C^T, the rows s of block sb against the columns t from its diagonal block on
    cb_t = [_mm(Bm[sb * _TILE: (sb + 1) * _TILE], Cm[sb * _TILE:], _NT) for sb in range(blocks)]
    d_cb_t = [jnp.zeros_like(c) for c in cb_t]  # summed over the program's heads
    dt, cum, cum_row = dt_ref[...], cum_ref[...], cum_row_ref[...]
    from_start = jnp.exp(cum)
    to_end = jnp.exp(cum[L - 1:] - cum)
    dB = jnp.zeros(Bm.shape, f32)
    dC = jnp.zeros(Cm.shape, f32)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (L, heads), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, cum_row.shape, 0)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    ddt = jnp.zeros((L, heads), f32)
    dcum = jnp.zeros((L, heads), f32)
    dcum_row = jnp.zeros(cum_row.shape, f32)

    for tile in range(x_ref.shape[1] // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        first = tile * len(masks)
        xf = x_ref[:, lanes].astype(f32)
        dy_in = dy_ref[:, lanes]
        dyf = dy_in.astype(f32)
        dt_l = _along(dt, first, masks)
        dtx = dt_l * xf
        to_end_l = _along(to_end, first, masks)
        dtx_end = to_end_l * dtx
        from_start_l = _along(from_start, first, masks)
        through = from_start_l[L - 1:]  # [1, 128]: the whole chunk's decay
        state = entering_ref[:, lanes]  # [N, 128]
        d_state = d_state_ref[:, lanes]  # of the state that LEAVES the chunk

        # y += exp(cum) * (C state)
        read = _mm(Cm, op(state))
        d_read = op(from_start_l * dyf)
        dC = dC + _mm(d_read, op(state), _NT)
        # the state that leaves = through * state + B^T dtx_end
        d_state_ref[:, lanes] = _mm(C_t, d_read) + through * d_state
        d_dtx_end = _mm(Bm, op(d_state))  # [L, 128]
        dB = dB + _mm(op(dtx_end), op(d_state), _NT)

        # within the chunk, as [s, t]
        d_dtx = []  # d(dt x) through the scores, per block of s: a head's lanes are its own
        minus = []  # per head: W's sum over t, a column
        for i, mask in enumerate(masks):
            j = first + i
            mine, dy_mine = _only(dtx, mask).astype(dtype), _only(dyf, mask).astype(dtype)
            plus = jnp.zeros((1, L), f32)  # W's sum over s, a row
            columns = []
            for sb in range(blocks):
                rows = slice(sb * _TILE, (sb + 1) * _TILE)
                decay = jnp.concatenate(
                    [_decay(cum_row[j: j + 1, tb * _TILE: (tb + 1) * _TILE], cum[rows, j: j + 1], tb == sb and "upper")
                     for tb in range(sb, blocks)], axis=1)
                scores = cb_t[sb] * decay
                d_scores = _mm(mine[rows], dy_in[sb * _TILE:], _NT)  # [128 s, t from the diagonal block on]
                part = _mm(scores.astype(dtype), dy_mine[sb * _TILE:])
                if i == 0:
                    d_dtx.append(part)
                else:
                    d_dtx[sb] = d_dtx[sb] + part
                d_cb_t[sb] = d_cb_t[sb] + d_scores * decay
                w = d_scores * scores
                columns.append(jnp.sum(w, axis=1, keepdims=True))
                across = jnp.sum(w, axis=0, keepdims=True)
                plus = plus + (across if sb == 0 else
                               jnp.concatenate([jnp.zeros((1, sb * _TILE), f32), across], axis=1))
            minus.append(jnp.concatenate(columns, axis=0))
            dcum_row = jnp.where(head_row == j, plus, dcum_row)

        d_dtx = jnp.concatenate(d_dtx, axis=0) + to_end_l * d_dtx_end
        dx_ref[:, lanes] = (d_ref[:, lanes] * dyf + dt_l * d_dtx).astype(dx_ref.dtype)
        dd_ref[:, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        for_dt = d_dtx * xf
        ended = d_dtx_end * dtx_end  # d(exp(cum_last - cum)) * exp(cum_last - cum), per position and lane
        for_cum = dyf * (from_start_l * read) - ended
        # into cum_last: the chunk's whole decay on the state, and every position's exp(cum_last - cum)
        for_last = jnp.sum(state * d_state, axis=0, keepdims=True) * through + jnp.sum(ended, axis=0, keepdims=True)
        for i, mask in enumerate(masks):
            j = first + i
            over = lambda a: jnp.sum(_only(a, mask), axis=1, keepdims=True)  # a head's lanes alone
            column = over(for_cum) - minus[i] + jnp.where(last_row, over(for_last), 0.0)
            dcum = jnp.where(head_lane == j, column, dcum)
            ddt = jnp.where(head_lane == j, over(for_dt), ddt)

    # d(C B^T), summed over the heads: against C for dB as it is, against B for dC transposed
    d_b = [dB[sb * _TILE: (sb + 1) * _TILE] + _mm(op(d_cb_t[sb]), Cm[sb * _TILE:]) for sb in range(blocks)]
    d_c = []
    for tb in range(blocks):
        d_cb = jnp.concatenate([d_cb_t[sb][:, (tb - sb) * _TILE: (tb - sb + 1) * _TILE].T for sb in range(tb + 1)], axis=1)
        d_c.append(dC[tb * _TILE: (tb + 1) * _TILE] + _mm(op(d_cb), Bm[: (tb + 1) * _TILE]))
    db_ref[...] = jnp.concatenate(d_b, axis=0)
    dc_ref[...] = jnp.concatenate(d_c, axis=0)
    ddt_ref[...] = ddt
    dcum_ref[...] = dcum
    dcum_row_ref[...] = dcum_row


def _plan(who: str, x, dt, cum, B, C, D, chunk: int, reverse: bool):
    """What both kernels share: (heads a program, the grid, the operands as the
    kernels view them, their block specs by name).  x as [b, S, H P]; dt and
    cum [b, S, H] float32 as columns [b, programs, S, heads], cum also as rows
    [b, programs, heads (whole sublane tiles), S]; D along its head's lanes
    [1, H P]; B and C as [b, S, G N]: free reshapes but the three small
    transposes.  `reverse` walks the chunks from the end."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    groups = B.shape[2] if B.ndim == 4 else 1
    if not supported(h, p, n, groups, s, chunk):
        raise ValueError(f"{who}: unsupported shapes {x.shape}, {B.shape}, chunk {chunk}")
    hb = head_block(h // groups, p)
    nc, programs = s // chunk, h // hb
    columns = lambda a: a.reshape(b, s, programs, hb).transpose(0, 2, 1, 3)
    rows = jnp.pad(columns(cum).swapaxes(2, 3), ((0, 0), (0, 0), (0, -hb % _SUBLANES), (0, 0)))
    operands = (x.reshape(b, s, h * p), columns(dt), columns(cum), rows, jnp.repeat(D.astype(jnp.float32), p)[None, :],
                B.reshape(b, s, groups * n), C.reshape(b, s, groups * n))
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    specs = dict(
        main=pl.BlockSpec((None, chunk, hb * p), lambda bi, k, ci: (bi, at(ci), k)),
        column=pl.BlockSpec((None, None, chunk, hb), lambda bi, k, ci: (bi, k, at(ci), 0)),
        row=pl.BlockSpec((None, None, rows.shape[2], chunk), lambda bi, k, ci: (bi, k, 0, at(ci))),
        d=pl.BlockSpec((1, hb * p), lambda bi, k, ci: (0, k)),
        group=pl.BlockSpec((None, chunk, n), lambda bi, k, ci: (bi, at(ci), k // (programs // groups))),
        state=pl.BlockSpec((None, None, n, hb * p), lambda bi, k, ci: (bi, at(ci), 0, k)),
        partial=pl.BlockSpec((None, None, chunk, n), lambda bi, k, ci: (bi, k, at(ci), 0)),
    )
    return hb, (b, programs, nc), operands, specs


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                               vmem_limit_bytes=64 * 1024 * 1024)


def ssd_fwd(x, dt, cum, B, C, D, *, chunk: int, interpret=False):
    """x [b, S, H, P]; dt, cum [b, S, H] float32 (cum: the running sum of
    `dt A` inside each chunk); B, C [b, S, N] or [b, S, G, N]; D [H] ->
    (y [b, S, H, P] in x's dtype, the state that enters each chunk
    [b, S / chunk, N, H P] float32)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    hb, grid, operands, spec = _plan("ssd_fwd", x, dt, cum, B, C, D, chunk, reverse=False)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        name="ssd_fwd",
        interpret=interpret,
        grid=grid,
        in_specs=[spec[name] for name in ("main", "column", "column", "row", "d", "group", "group")],
        out_specs=[spec["main"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, s // chunk, n, h * p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, hb * p), jnp.float32)],
        compiler_params=_PARAMS,
    )
    with tracing.scope("ssd_fwd", kernel=True):
        y, entering = call(*operands)
        return y.reshape(x.shape), entering


def ssd_bwd(x, dt, cum, B, C, D, entering, dy, *, chunk: int, interpret=False):
    """`ssd_fwd`'s arguments, the states it wrote and the cotangent of y ->
    (dx in x's dtype, the cotangent of dt as a FACTOR of `dt x` [b, S, H]
    float32, that of cum [b, S, H] float32, dB and dC float32 in B's shape, dD
    [H] float32).  The chain from cum to dt and A is the caller's."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    n = B.shape[-1]
    groups = B.shape[2] if B.ndim == 4 else 1
    hb, grid, operands, spec = _plan("ssd_bwd", x, dt, cum, B, C, D, chunk, reverse=True)
    programs = grid[1]
    columns, rows = operands[2].shape, operands[3].shape
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        name="ssd_bwd",
        interpret=interpret,
        grid=grid,
        in_specs=[spec[name] for name in ("main", "column", "column", "row", "d", "group", "group", "state", "main")],
        out_specs=[spec[name] for name in ("main", "column", "column", "row", "partial", "partial")]
        + [pl.BlockSpec((None, 1, hb * p), lambda bi, k, ci: (bi, 0, k))],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), x.dtype), jax.ShapeDtypeStruct(columns, f32),
                   jax.ShapeDtypeStruct(columns, f32), jax.ShapeDtypeStruct(rows, f32),
                   jax.ShapeDtypeStruct((b, programs, s, n), f32), jax.ShapeDtypeStruct((b, programs, s, n), f32),
                   jax.ShapeDtypeStruct((b, 1, h * p), f32)],
        scratch_shapes=[pltpu.VMEM((n, hb * p), f32)],
        compiler_params=_PARAMS,
    )
    with tracing.scope("ssd_bwd", kernel=True):
        dx, ddt, dcum, dcum_row, dB, dC, dD = call(*operands, entering, dy.reshape(b, s, h * p))
        positions = lambda a: a.transpose(0, 2, 1, 3).reshape(b, s, h)  # [b, programs, S, heads] -> [b, S, H]
        by_group = lambda a: a.reshape(b, groups, programs // groups, s, n).sum(axis=2)  # a group's programs
        dB, dC = (jnp.moveaxis(by_group(a), 1, 2).reshape(B.shape) for a in (dB, dC))
        return (dx.reshape(x.shape), positions(ddt), positions(dcum + dcum_row[:, :, :hb].swapaxes(2, 3)), dB, dC,
                dD.reshape(b, h, p).sum(axis=(0, 2)))
