"""A delta layer's causal depthwise convolution + SiLU with the per-head L2
norm of q and k as its epilogue, forward and backward, in Pallas for TPU
(`ops/delta_conv.py` has the contract and the plain form).

The kernels take x as it lies, [B, S, Cx] POSITIONS-major: channels along the
lanes, positions along the sublanes.  A delta layer's fused projection is
saved in that order, its scan kernels read [b, S, H * 128] blocks, and a head
of 128 channels is one lane tile, so its sum of squares is a reduction inside
a tile (`ops/pallas/ssm_conv.py` wants the sequence in the lanes, which a
Mamba-2 layer's arrays have and a delta layer's do not: there each direction
crossed the whole array twice more, into [B, C, S] and out of it, and a third
time for the norm).  x is walked in blocks of `rows` positions by `lanes`
channels, of the first C = (Hq + Hk) * 128 + Cv of its Cx columns (a wider
array is read by column range through the `BlockSpec`, no slice copied).  A
tap `x_{t-j}` is the block rolled by j sublanes (`pltpu.roll`); the j
positions that roll in from the wrong end are taken from a HALO of one
sublane tile (16 positions), the neighbouring block's edge, passed as a
second view of the same array.  Every full-size array crosses HBM once; the
float32 arithmetic (taps, sigmoid, the norm, the cotangents) lives in VMEM.

The column blocks of one call belong to three arrays: q's heads first, then
k's, then v's channels (the PHASES of the grid's middle axis).  An array whose
phase is not running keeps the block index it had (or will first have), so
nothing of it is copied in either direction while the others run, and what a
phase leaves in its last block is written out once, when the index moves on.

- `conv_fwd`: y = silu(sum_k w[:, k] * x_{t-(K-1)+k}) rounded to x's dtype,
  zeros before the start; v = y; q = y / |y|_head * 128^-0.5 and
  k = y / |y|_head in float32, the norm taken of the ROUNDED y (what the
  plain form's norm of a bf16 convolution reads).
- `conv_bwd`: from dq, dk (float32), dv and x, with the pre-activation and the
  norm recomputed in the pass: `dy = (dq - n (n . dq)) * scale / |y|` with
  `n = y / |y|` for a head of q or k, `dy = dv` for v; `dpre = dy *
  silu'(pre)`; `dx_t = sum_k w[:, k] * dpre_{t+(K-1-k)}`, zeros past the end
  (the same taps run anti-causally: a halo of x BEFORE the block for pre,
  halos of x and of the output's cotangent AFTER it for the dpre of the next
  16 positions); `dw[:, k] = sum_t dpre_t * x_{t-(K-1)+k}` accumulates in a
  float32 [8, C] block per batch row (sublanes 0..K-1 the taps) that stays in
  VMEM across the blocks of the sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

HEAD = 128  # channels of a head of q or k: one lane tile
EPS = 1e-6  # under the norm's root, as `ops/delta_conv.py` has it
_HALO = 16  # positions of a neighbouring block a block sees: one bf16 sublane tile
_TAPS = 8  # sublanes of the weights' block and of the weight-gradient block
# Positions x channels of a block: PERF.md section 6, PR 60 for the timing.
_ROWS, _LANES = 512, 512
_VMEM = 64 * 2 ** 20


def _tile(dim: int, largest: int, unit: int) -> int:
    """The largest multiple of `unit` up to `largest` that divides dim."""
    t = min(largest, dim) // unit * unit
    while dim % t:
        t -= unit
    return t


def supported(s: int, cx: int, q_heads: int, k_heads: int, head: int, v_channels: int, k: int) -> bool:
    """Whether the kernels take these shapes (else `ops/delta_conv.py` uses the
    plain form): heads of one lane tile, whole lane tiles of v, whole sublane
    tiles of positions, the taps in the sublanes of the weights' block."""
    return (head == HEAD and q_heads > 0 and k_heads > 0 and v_channels > 0 and v_channels % HEAD == 0
            and s % _HALO == 0 and 1 < k <= _TAPS and (q_heads + k_heads) * HEAD + v_channels <= cx)


def _shifted(x, edge, j: int):
    """`x_{t-j}` for the positions of a block [rows, lanes]: j > 0 looks back
    and `edge` is the 16 positions before the block, j < 0 looks ahead and
    `edge` is the 16 positions after it."""
    if j == 0:
        return x
    n = x.shape[0]
    rolled = pltpu.roll(x, j % n, 0)
    from_edge = pltpu.roll(edge, j % _HALO, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, edge.shape, 0)
    if j > 0:
        head = jnp.where(row < j, from_edge, rolled[:_HALO])
        return head if n == _HALO else jnp.concatenate([head, rolled[_HALO:]], axis=0)
    tail = jnp.where(row >= _HALO + j, from_edge, rolled[n - _HALO:])
    return tail if n == _HALO else jnp.concatenate([rolled[: n - _HALO], tail], axis=0)


def _pre(x, before, w, k: int):
    """(sum_k w[k] * x_{t-(K-1)+k}, the taps [x_t, x_{t-1}, ...]), float32; w [8, lanes], a tap a sublane."""
    taps = [_shifted(x, before, j) for j in range(k)]
    out = taps[k - 1] * w[0:1]
    for i in range(1, k):
        out = out + taps[k - 1 - i] * w[i: i + 1]
    return out, taps


def _per_head(fn, *arrays):
    """`fn` on each head's 128 lanes of [rows, lanes] arrays, the results side by side again."""
    heads = arrays[0].shape[1] // HEAD
    parts = [fn(*(a[:, h * HEAD: (h + 1) * HEAD] for a in arrays)) for h in range(heads)]
    return parts[0] if heads == 1 else jnp.concatenate(parts, axis=1)


def _normed(y, scale: float):
    """y / |y|_2 * scale over each head's lanes, float32."""
    return _per_head(lambda a: a * (jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + EPS) * scale), y)


def _normed_cotangent(y, d, scale: float):
    """The cotangent of y from that of `_normed(y, scale)`: with r = 1 / |y|, `scale * r * (d - y r^2 (y . d))`."""

    def one(a, d):
        r = jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + EPS)
        return (d - a * (r * r * jnp.sum(a * d, axis=1, keepdims=True))) * (r * scale)

    return _per_head(one, y, d)


def _phases(nq: int, nk: int):
    """Which array the column block j belongs to: (is q's, is k's, is v's)."""
    j = pl.program_id(1)
    return j < nq, jnp.logical_and(j >= nq, j < nq + nk), j >= nq + nk


def _fwd_kernel(nq, nk, k, x_ref, before_ref, w_ref, q_ref, k_ref, v_ref):
    f32 = jnp.float32
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[0].astype(f32))
    pre, _ = _pre(x_ref[0].astype(f32), before, w_ref[...], k)
    y = (pre * jax.nn.sigmoid(pre)).astype(v_ref.dtype)
    is_q, is_k, is_v = _phases(nq, nk)

    @pl.when(is_q)
    def _q():
        q_ref[0] = _normed(y.astype(f32), HEAD ** -0.5)

    @pl.when(is_k)
    def _k():
        k_ref[0] = _normed(y.astype(f32), 1.0)

    @pl.when(is_v)
    def _v():
        v_ref[0] = y


def _bwd_kernel(nq, nk, k, x_ref, before_ref, after_ref, dq_ref, dq_after_ref, dk_ref, dk_after_ref, dv_ref,
                dv_after_ref, w_ref, dx_ref, dw_ref, dy_ref):
    f32 = jnp.float32
    i, n = pl.program_id(2), pl.num_programs(2)
    w = w_ref[...]
    x = x_ref[0].astype(f32)
    rows = x.shape[0]
    before = jnp.where(i == 0, 0.0, before_ref[0].astype(f32))
    pre, taps = _pre(x, before, w, k)
    sig = jax.nn.sigmoid(pre)
    # the 16 positions after the block: their taps reach back into the block's last ones
    pre_after, _ = _pre(after_ref[0].astype(f32), x[rows - _HALO:], w, k)
    sig_after = jax.nn.sigmoid(pre_after)
    is_q, is_k, is_v = _phases(nq, nk)

    def through_norm(d_ref, d_after_ref, scale):  # the cotangent of y, the block's rows then the 16 after it, into the scratch
        rounded = lambda a: a.astype(x_ref.dtype).astype(f32)  # the norm read the rounded y
        dy_ref[:rows] = _normed_cotangent(rounded(pre * sig), d_ref[0], scale)
        dy_ref[rows:] = _normed_cotangent(rounded(pre_after * sig_after), d_after_ref[0], scale)

    @pl.when(is_q)
    def _q():
        through_norm(dq_ref, dq_after_ref, HEAD ** -0.5)

    @pl.when(is_k)
    def _k():
        through_norm(dk_ref, dk_after_ref, 1.0)

    @pl.when(is_v)
    def _v():
        dy_ref[:rows] = dv_ref[0].astype(f32)
        dy_ref[rows:] = dv_after_ref[0].astype(f32)

    dsilu = lambda pre, sig: sig * (1.0 + pre * (1.0 - sig))
    dpre = dy_ref[:rows] * dsilu(pre, sig)
    dpre_after = jnp.where(i == n - 1, 0.0, dy_ref[rows:] * dsilu(pre_after, sig_after))
    dx = _shifted(dpre, dpre_after, -(k - 1)) * w[0:1]
    for t in range(1, k):
        dx = dx + _shifted(dpre, dpre_after, -(k - 1 - t)) * w[t: t + 1]
    dx_ref[0] = dx.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    tap = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 0)
    sums = [jnp.sum(dpre * taps[k - 1 - t], axis=0, keepdims=True) for t in range(k)]
    dw_ref[0] += sum(jnp.where(tap == t, v, 0.0) for t, v in enumerate(sums))


def _taps(w):
    """w [C, K] -> float32 [8, C], a tap a sublane: ONE small operand, built per call."""
    return jnp.pad(w.astype(jnp.float32).T, ((0, _TAPS - w.shape[1]), (0, 0)))


class _Grid:
    """The blocks of one call: `rows` positions by `lanes` channels, and the
    index maps of the three arrays the column blocks belong to."""

    def __init__(self, s: int, q_heads: int, k_heads: int, v_channels: int, rows, lanes):
        q, k = q_heads * HEAD, k_heads * HEAD
        self.rows = _tile(s, rows or _ROWS, _HALO)
        lanes = lanes or _LANES
        self.lanes = next(t for t in range(min(lanes, q, k, v_channels) // HEAD * HEAD, 0, -HEAD)
                          if q % t == 0 and k % t == 0 and v_channels % t == 0)
        self.per, self.row_blocks, self.halo_blocks = self.rows // _HALO, s // self.rows, s // _HALO
        self.nq, self.nk, self.nv = q // self.lanes, k // self.lanes, v_channels // self.lanes

    def _rows_of(self, which: str, i):
        if which == "main":
            return i
        if which == "before":
            return jnp.maximum(i * self.per - 1, 0)
        return jnp.minimum((i + 1) * self.per, self.halo_blocks - 1)

    def of_x(self, which: str):
        """x's block (or a halo of it) at column block j: every phase reads x."""
        height = self.rows if which == "main" else _HALO
        return pl.BlockSpec((1, height, self.lanes), lambda b, j, i: (b, self._rows_of(which, i), j))

    def of_phase(self, first: int, count: int, which: str = "main"):
        """The block of an array that holds the column blocks [first, first +
        count) alone: inside its phase the grid's own block, before it the
        first it will have, after it the last it had."""
        height = self.rows if which == "main" else _HALO

        def index(b, j, i):
            i = jnp.where(j < first, 0, jnp.where(j >= first + count, self.row_blocks - 1, i))
            return b, self._rows_of(which, i), jnp.clip(j - first, 0, count - 1)

        return pl.BlockSpec((1, height, self.lanes), index)

    def of_weights(self):
        return pl.BlockSpec((_TAPS, self.lanes), lambda b, j, i: (0, j))

    @property
    def phases(self):
        return (0, self.nq), (self.nq, self.nk), (self.nq + self.nk, self.nv)

    def grid(self, bsz: int):
        return bsz, self.nq + self.nk + self.nv, self.row_blocks


# The column blocks run in order (an array's block waits through the other phases), so only the batch may be split.
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM)


def _call(kernel, name: str, interpret: bool, **kwargs):
    """`pl.pallas_call` under its own name: `name` names the Mosaic kernel and
    is a `named_scope` around the call, so a profile finds it by the op's
    metadata (as `ssm_conv._call` does)."""
    call = pl.pallas_call(kernel, name=name, interpret=interpret, compiler_params=_PARAMS, **kwargs)

    def named(*args):
        with tracing.scope(name, kernel=True):
            return call(*args)

    return named


def conv_fwd(x, w, *, q_heads: int, k_heads: int, rows=None, lanes=None, interpret=False):
    """x [B, S, Cx], w [C, K] the taps of x's first C columns: `q_heads` heads
    of 128 that are q's, `k_heads` that are k's, the rest v's ->
    (q [B, S, Hq * 128], k [B, S, Hk * 128] float32, v [B, S, Cv] in x's dtype)."""
    bsz, s, _ = x.shape
    c, k = w.shape
    g = _Grid(s, q_heads, k_heads, c - (q_heads + k_heads) * HEAD, rows, lanes)
    widths = [n * g.lanes for n in (g.nq, g.nk, g.nv)]
    return _call(
        functools.partial(_fwd_kernel, g.nq, g.nk, k), "delta_conv_fwd", interpret,
        grid=g.grid(bsz),
        in_specs=[g.of_x("main"), g.of_x("before"), g.of_weights()],
        out_specs=[g.of_phase(*phase) for phase in g.phases],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, width), dtype)
                   for width, dtype in zip(widths, (jnp.float32, jnp.float32, x.dtype))],
        cost_estimate=pl.CostEstimate(
            flops=16 * bsz * s * c, transcendentals=bsz * s * c,
            bytes_accessed=bsz * s * (c * x.dtype.itemsize + 4 * (widths[0] + widths[1]) + widths[2] * x.dtype.itemsize)),
    )(x, x, _taps(w))


def conv_bwd(x, w, dq, dk, dv, *, q_heads: int, k_heads: int, rows=None, lanes=None, interpret=False):
    """x, w and the head counts as `conv_fwd` takes them, and the cotangents
    of its three outputs -> (dx [B, S, C] in x's dtype, of the C columns the
    forward read, dw [C, K] float32)."""
    bsz, s, _ = x.shape
    c, k = w.shape
    g = _Grid(s, q_heads, k_heads, c - (q_heads + k_heads) * HEAD, rows, lanes)
    cotangents = [g.of_phase(*phase, which=which) for phase in g.phases for which in ("main", "after")]
    dx, dw = _call(
        functools.partial(_bwd_kernel, g.nq, g.nk, k), "delta_conv_bwd", interpret,
        grid=g.grid(bsz),
        in_specs=[g.of_x("main"), g.of_x("before"), g.of_x("after"), *cotangents, g.of_weights()],
        out_specs=[g.of_x("main"), pl.BlockSpec((1, _TAPS, g.lanes), lambda b, j, i: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, c), x.dtype), jax.ShapeDtypeStruct((bsz, _TAPS, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g.rows + _HALO, g.lanes), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=60 * bsz * s * c, transcendentals=bsz * s * c,
            bytes_accessed=bsz * s * (2 * c * x.dtype.itemsize + 4 * (g.nq + g.nk) * g.lanes + g.nv * g.lanes * dv.dtype.itemsize)),
    )(x, x, x, dq, dq, dk, dk, dv, dv, _taps(w))
    return dx, jnp.sum(dw, axis=0)[:k].T
