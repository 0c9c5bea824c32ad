"""Mamba-2's causal depthwise convolution + SiLU, forward and backward, in
Pallas for TPU (`ops/ssm.py` has the contract and the plain form).

The kernels take x as [B, C, S], the SEQUENCE along the lanes: the order in
which XLA keeps every [1, S, features] array of a Mamba-2 layer on the v5e
(its scan wants the 256 positions of a chunk in the lanes, not the 64 of a
head), so the caller's `swapaxes` on both sides are bitcasts THERE: for the
Mamba-2 and S6 layers (`mixers/mamba2.py`, `mixers/s6.py`), the callers this
file has.  They are not for a delta layer, whose projection is saved
positions-major and whose scan reads [b, S, H * 128] blocks: it crossed the
whole array twice more a direction, and has had a convolution of its own since
PR 60 (`ops/delta_conv.py`, `ops/pallas/delta_conv.py`).  x is walked in
blocks of `rows` channels by `lanes` positions.  A tap `x_{t-j}` is the block
rolled by j lanes (`pltpu.roll`); the j positions that roll in from the wrong
end are taken from a 128-lane HALO, the neighbouring block's edge, passed as
a second view of the same array.  So every full-size array crosses HBM once,
in its own dtype; the float32 arithmetic (taps, bias, sigmoid, the cotangent
of the pre-activation) lives in VMEM.

- `conv_fwd`: y = silu(b + sum_k w[:, k] * x_{t-(K-1)+k}), zeros before the start.
- `conv_bwd`: from dy and x, with the pre-activation recomputed in the pass:
  `dpre = dy * silu'(pre)`; `dx_t = sum_k w[:, k] * dpre_{t+(K-1-k)}`, zeros
  past the end (the same taps run anti-causally: a halo of x BEFORE the block
  for pre, halos of x and dy AFTER it for the dpre of the next 128 positions);
  `dw[:, k] = sum_t dpre_t * x_{t-(K-1)+k}` and `db = sum_t dpre_t` accumulate
  in a float32 [C, 128] block per batch row (lanes 0..K-1 the taps, lane K the
  bias) that stays in VMEM across the blocks of the sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.ssm import _dsilu
from ray_tpu.util import tracing

_HALO = 128  # positions of a neighbouring block a block sees: one lane tile
_SUBLANES = 16  # channels per block come in whole bf16 sublane tiles
# Channels x positions of a block: see PERF.md section 6, PR 31 for the timing.
_ROWS, _LANES = 64, 4096


def _tile(dim: int, largest: int, unit: int) -> int:
    """The largest multiple of `unit` up to `largest` that divides dim."""
    t = min(largest, dim) // unit * unit
    while dim % t:
        t -= unit
    return t


def supported(s: int, c: int, k: int) -> bool:
    """Whether the kernels take these shapes (else `ops/ssm.py` uses the plain
    form): whole lane tiles of positions, whole sublane tiles of channels, and
    the taps and the bias in the 128 lanes of the weight-gradient block."""
    return s % _HALO == 0 and c % _SUBLANES == 0 and 1 < k < _HALO


def _shifted(x, edge, j: int):
    """`x_{t-j}` for the positions of a block [rows, lanes]: j > 0 looks back
    and `edge` is the 128 positions before the block, j < 0 looks ahead and
    `edge` is the 128 positions after it."""
    if j == 0:
        return x
    n = x.shape[1]
    rolled = pltpu.roll(x, j % n, 1)
    from_edge = pltpu.roll(edge, j % _HALO, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, edge.shape, 1)
    if j > 0:
        head = jnp.where(lane < j, from_edge, rolled[:, :_HALO])
        return head if n == _HALO else jnp.concatenate([head, rolled[:, _HALO:]], axis=1)
    tail = jnp.where(lane >= _HALO + j, from_edge, rolled[:, n - _HALO:])
    return tail if n == _HALO else jnp.concatenate([rolled[:, : n - _HALO], tail], axis=1)


def _pre(x, before, wb):
    """(b + sum_k w[:, k] * x_{t-(K-1)+k}, the taps [x_t, x_{t-1}, ...]), float32;
    wb [rows, K + 1] is w with b as its last column."""
    k = wb.shape[1] - 1
    taps = [_shifted(x, before, j) for j in range(k)]
    out = wb[:, k:]
    for i in range(k):
        out = out + taps[k - 1 - i] * wb[:, i: i + 1]
    return out, taps


def _fwd_kernel(x_ref, before_ref, wb_ref, y_ref):
    f32 = jnp.float32
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[0].astype(f32))
    pre, _ = _pre(x_ref[0].astype(f32), before, wb_ref[...])
    y_ref[0] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, wb_ref, dx_ref, dwb_ref):
    f32 = jnp.float32
    i, n = pl.program_id(2), pl.num_programs(2)
    wb = wb_ref[...]
    k = wb.shape[1] - 1
    x = x_ref[0].astype(f32)
    before = jnp.where(i == 0, 0.0, before_ref[0].astype(f32))
    pre, taps = _pre(x, before, wb)
    dpre = dy_ref[0].astype(f32) * _dsilu(pre)
    # the 128 positions after the block: their taps reach back into the block's last ones
    pre_after, _ = _pre(after_ref[0].astype(f32), x[:, x.shape[1] - _HALO:], wb)
    dpre_after = jnp.where(i == n - 1, 0.0, dy_after_ref[0].astype(f32) * _dsilu(pre_after))
    dx = sum(_shifted(dpre, dpre_after, -(k - 1 - t)) * wb[:, t: t + 1] for t in range(k))
    dx_ref[0] = dx.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _zero():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, dwb_ref.shape[1:], 1)
    sums = [jnp.sum(dpre * taps[k - 1 - t], axis=1, keepdims=True) for t in range(k)]
    sums.append(jnp.sum(dpre, axis=1, keepdims=True))
    dwb_ref[0] += sum(jnp.where(lane == t, v, 0.0) for t, v in enumerate(sums))


def _blocks(s: int, c: int, rows, lanes):
    return _tile(c, rows or _ROWS, _SUBLANES), _tile(s, lanes or _LANES, _HALO)


def _weights(w, b):
    """w [C, K], b [C] -> float32 [C, K + 1], the bias the last column: ONE small
    operand, built per call (a relayout of `w` alone is hoisted out of the loop
    over layers onto the whole stack, K padded to 128 lanes: 10 MB at the peak)."""
    return jnp.concatenate([w, b[:, None]], axis=1).astype(jnp.float32)


def _call(kernel, name: str, interpret: bool, **kwargs):
    """`pl.pallas_call` under its own name: `name` names the Mosaic kernel and
    is a `named_scope` around the call, so a profile finds it by the op's
    metadata (as `flash_attention._pallas_call` does; the platform is the
    caller's to choose here, `ops/ssm.py`)."""
    call = pl.pallas_call(kernel, name=name, interpret=interpret, **kwargs)

    def named(*args):
        with tracing.scope(name, kernel=True):
            return call(*args)

    return named


def conv_fwd(x: jax.Array, w: jax.Array, b: jax.Array, *, rows=None, lanes=None, interpret=False) -> jax.Array:
    """x [B, C, S], w [C, K], b [C] -> silu(conv) [B, C, S] in x's dtype."""
    bsz, c, s = x.shape
    k = w.shape[1]
    r, l = _blocks(s, c, rows, lanes)
    per = l // _HALO
    return _call(
        _fwd_kernel, "ssm_conv_fwd", interpret,
        grid=(bsz, c // r, s // l),
        in_specs=[
            pl.BlockSpec((1, r, l), lambda bi, j, i: (bi, j, i)),
            pl.BlockSpec((1, r, _HALO), lambda bi, j, i: (bi, j, jnp.maximum(i * per - 1, 0))),
            pl.BlockSpec((r, k + 1), lambda bi, j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, l), lambda bi, j, i: (bi, j, i)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(flops=12 * x.size, transcendentals=x.size,
                                      bytes_accessed=2 * x.size * x.dtype.itemsize),
    )(x, x, _weights(w, b))


def conv_bwd(x: jax.Array, w: jax.Array, b: jax.Array, dy: jax.Array, *, rows=None, lanes=None, interpret=False):
    """x, dy [B, C, S] -> (dx [B, C, S] in x's dtype, dw [C, K] float32, db [C] float32)."""
    bsz, c, s = x.shape
    k = w.shape[1]
    r, l = _blocks(s, c, rows, lanes)
    per, last = l // _HALO, s // _HALO - 1
    main = pl.BlockSpec((1, r, l), lambda bi, j, i: (bi, j, i))
    before = pl.BlockSpec((1, r, _HALO), lambda bi, j, i: (bi, j, jnp.maximum(i * per - 1, 0)))
    after = pl.BlockSpec((1, r, _HALO), lambda bi, j, i: (bi, j, jnp.minimum((i + 1) * per, last)))
    dx, dwb = _call(
        _bwd_kernel, "ssm_conv_bwd", interpret,
        grid=(bsz, c // r, s // l),
        in_specs=[
            main, before, after, main, after,
            pl.BlockSpec((r, k + 1), lambda bi, j, i: (j, 0)),
        ],
        out_specs=[main, pl.BlockSpec((1, r, _HALO), lambda bi, j, i: (bi, j, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((bsz, c, _HALO), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=40 * x.size, transcendentals=x.size,
                                      bytes_accessed=3 * x.size * x.dtype.itemsize),
    )(x, x, x, dy, dy, _weights(w, b))
    dwb = jnp.sum(dwb, axis=0)
    return dx, dwb[:, :k], dwb[:, k]
