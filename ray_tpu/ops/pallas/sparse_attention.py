"""Pallas TPU kernels of `ops/sparse_attention.py`: softmax attention over a
SELECTED set of keys a query, the set given as a `[B, S, S]` mask (nonzero =
selected; the caller's mask lies inside the causal triangle).

The first form, correct and small, not fast: the flash kernels' online
softmax and in-kernel recompute (`ops/pallas/flash_attention.py`) at ONE tile
size, 512 x 512, with the mask's tile as one more operand of every step: a
tile pair above the diagonal is predicated off and its index held on the
diagonal's tile (nothing is copied for it), every other pair runs, whatever
the mask holds of it.  At 8,192 positions and 2,048 keys a query no tile under
the diagonal is empty, so no tile is skipped for the mask's sake; a form that
gathers the selected keys, or orders them so that tiles empty, is a later
one's (PERF.md section 7).

`selected_fwd` also returns the log-sum-exp over the selected keys, from which
`head_mean_probs` makes the indexer's target: the mean over the heads of each
pair's probability, `[B, S, S]` float32, one more `q k^T` a head with the heads
as the innermost grid axis, so that an output tile is written once.  q arrives
scaled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.flash_attention import _LANES, NEG_INF, _pallas_call

TILE = 512


def tile(s: int) -> int:
    """The largest of 512, 256, 128 that divides s (0: none does)."""
    return next((t for t in (TILE, 256, 128) if s % t == 0), 0)


def supported(q_shape, v_shape) -> bool:
    """Sequences a 128-tile divides, head sizes in whole 64s (the flash kernels' own limits)."""
    return tile(q_shape[1]) > 0 and q_shape[-1] % 64 == 0 and v_shape[-1] % 64 == 0


def _selected(logits, mask_ref):
    """The tile's logits where its pair is selected, NEG_INF elsewhere."""
    return jnp.where(mask_ref[0].astype(jnp.float32) != 0.0, logits, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
    # q [1, 1, bq, D]; k [1, 1, bk, D]; v [1, 1, bk, Dv]; mask [1, bq, bk]; o [1, 1, bq, Dv]; lse [1, 1, bq, 1]
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = _selected(jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32), mask_ref)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m_new))  # a row with no key so far has m_new = NEG_INF
        corr = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def _heads_first(*arrays):
    return tuple(a.transpose(0, 2, 1, 3) for a in arrays)


def selected_fwd(q, k, v, mask):
    """q, k [B, S, H, D] (q scaled), v [B, S, H, Dv], mask [B, S, S] -> (out [B, S, H, Dv], lse [B, S, H] float32)."""
    b, s, h, d = q.shape
    dv, t = v.shape[-1], tile(s)
    n = s // t
    qt, kt, vt = _heads_first(q, k, v)
    key = lambda bi, hi, qi, ki: (bi, hi, jnp.minimum(ki, qi), 0)  # held on the diagonal's tile through the steps past it
    out, lse = _pallas_call(
        _fwd_kernel, name="dsa_attn_fwd", grid=(b, h, n, n),
        in_specs=[
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, t, d), key),
            pl.BlockSpec((1, 1, t, dv), key),
            pl.BlockSpec((1, t, t), lambda bi, hi, qi, ki: (bi, qi, jnp.minimum(ki, qi))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, t, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, dv), q.dtype), jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, dv), jnp.float32), pltpu.VMEM((t, _LANES), jnp.float32), pltpu.VMEM((t, _LANES), jnp.float32)],
    )(qt, kt, vt, mask)
    return out.transpose(0, 2, 1, 3), lse[..., 0].transpose(0, 2, 1)


def _probs(q_ref, k_ref, lse_ref, mask_ref):
    """The tile's probabilities, recomputed from the saved log-sum-exp: 0 where the pair is not selected."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    logits = _selected(jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32), mask_ref)
    return q, k, jnp.exp(logits - lse_ref[0, 0])


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _step():
        _, k, p = _probs(q_ref, k_ref, lse_ref, mask_ref)
        do = do_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref):
    # grid (b, h, key tile, query tile): the key's tiles stay resident
    ki, qi, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(qi * bq + bq - 1 >= ki * bk)
    def _step():
        q, _, p = _probs(q_ref, k_ref, lse_ref, mask_ref)
        do = do_ref[0, 0].astype(jnp.float32)
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def selected_bwd(q, k, v, mask, out, lse, do):
    """(dq, dk, dv) of `selected_fwd`'s `out`, each in its argument's shape and dtype (dq of the SCALED q)."""
    b, s, h, d = q.shape
    dv, t = v.shape[-1], tile(s)
    n = s // t
    qt, kt, vt, dot = _heads_first(q, k, v, do)
    lse = lse.transpose(0, 2, 1)[..., None]  # [B, H, S, 1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).transpose(0, 2, 1)[..., None]
    own = lambda width: pl.BlockSpec((1, 1, t, width), lambda bi, hi, i, j: (bi, hi, i, 0))
    held = jnp.minimum  # (key step, query tile): the last key tile a query tile sees, through the steps past it
    key = lambda width: pl.BlockSpec((1, 1, t, width), lambda bi, hi, qi, ki: (bi, hi, held(ki, qi), 0))
    dq = _pallas_call(
        _dq_kernel, name="dsa_attn_bwd_dq", grid=(b, h, n, n),
        in_specs=[own(d), key(d), key(dv), pl.BlockSpec((1, t, t), lambda bi, hi, qi, ki: (bi, qi, held(ki, qi))),
                  own(dv), own(1), own(1)],
        out_specs=own(d), out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
    )(qt, kt, vt, mask, dot, lse, delta)
    first = jnp.maximum  # (query step, key tile): the first query tile that sees a key tile, through the steps before it
    query = lambda width: pl.BlockSpec((1, 1, t, width), lambda bi, hi, ki, qi: (bi, hi, first(qi, ki), 0))
    dk, dv_ = _pallas_call(
        _dkv_kernel, name="dsa_attn_bwd_dkv", grid=(b, h, n, n),
        in_specs=[query(d), own(d), own(dv), pl.BlockSpec((1, t, t), lambda bi, hi, ki, qi: (bi, first(qi, ki), ki)),
                  query(dv), query(1), query(1)],
        out_specs=[own(d), own(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), k.dtype), jax.ShapeDtypeStruct((b, h, s, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32), pltpu.VMEM((t, dv), jnp.float32)],
    )(qt, kt, vt, mask, dot, lse, delta)
    return tuple(a.transpose(0, 2, 1, 3) for a in (dq, dk, dv_))


def _mean_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, *, heads: int):
    # grid (b, query tile, key tile, head): p [1, bq, bk] is written once, behind its last head
    qi, ki, hi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(hi == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _step():
        p_ref[0] = p_ref[0] + _probs(q_ref, k_ref, lse_ref, mask_ref)[2] * (1.0 / heads)


def head_mean_probs(q, k, lse, mask):
    """q, k [B, S, H, D] (q scaled), lse [B, S, H], mask [B, S, S] -> the mean over the heads of each pair's
    probability [B, S, S] float32: 0 outside the mask, every row sums to 1."""
    b, s, h, d = q.shape
    t = tile(s)
    n = s // t
    qt, kt = _heads_first(q, k)
    return _pallas_call(
        functools.partial(_mean_kernel, heads=h), name="dsa_target", grid=(b, n, n, h),
        in_specs=[
            pl.BlockSpec((1, 1, t, d), lambda bi, qi, ki, hi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, qi, ki, hi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, t, 1), lambda bi, qi, ki, hi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, t, t), lambda bi, qi, ki, hi: (bi, qi, ki)),
        ],
        out_specs=pl.BlockSpec((1, t, t), lambda bi, qi, ki, hi: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
    )(qt, kt, lse.transpose(0, 2, 1)[..., None], mask)
