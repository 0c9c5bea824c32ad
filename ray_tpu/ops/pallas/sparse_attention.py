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

And the indexer's scores before the selection, `I[t, s] = sum_j w[t, j] *
relu(q[t, j] . k[s])` over J heads and ONE key a position (`index_fwd`,
`index_bwd`): a (query tile, key tile) pair on or under the diagonal runs its J
heads in a loop INSIDE the kernel, a head's `[keys, queries]` float32 products
made, cut at 0, weighed and added to one float32 tile in VMEM, which is written
once; the `[., J, .]` products never reach HBM.  Operands go to the MXU as they
come (bf16 in a step), every product accumulated in float32.  The loop's step
is a GROUP of 16 (or 8) heads written out, so that the scheduler overlaps one
head's products with the sums of the one before (a loop of single heads runs
at two thirds of that: PERF.md section 6, PR 67).  Keys lie along the sublanes
and queries along the lanes, so that a head's weights are a ROW (a sublane
broadcast) and the sum of `dI * relu(z)` over the keys adds rows: the tile is
turned once, behind the loop, never a head.  A pair wholly above the diagonal
runs nothing: its index is held on the diagonal's tile and its scores are
written as 0.0, as every pair above the diagonal is (the op's contract:
`ops/sparse_attention.py` `index_scores`).  The backward is the flash
backward's split, each kernel making the tile's products again:
`dsa_index_bwd_dq` walks a query tile's keys for `dw` and `dq`, whose weight
(one number a query and head, whatever the key) multiplies the float32 sum
once at the end, so `dI [z > 0]` goes to the MXU in k's dtype without it, and
`dq` leaves as `[B, J, D, S]` (`k^T dz^T` is a plain product in this
orientation) to be turned beside the kernel; `dsa_index_bwd_dk` walks a key
tile's queries, a group's heads ONE product `dz [keys, group x queries] q`,
the sum over heads and queries the MXU's.
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


# -- the indexer's scores ---------------------------------------------------------------

INDEX_QUERY_TILE = 256  # at most: a query tile holds its J heads, [J, tile, D], twice (the pipeline's two buffers)
# of a v5e core's 128 MiB: `dsa_index_bwd_dq` holds q and dq twice (4 MB each at 64 heads) and a float32 dq, 25 MB in all
_INDEX_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 * 2 ** 20)
_GROUPS = (16, 8)  # heads a step of a kernel's loop, written out (the first that divides J): the scheduler overlaps one
# head's products with the last one's sums, which a loop of single heads does not (PERF.md section 6, PR 67)


def index_tiles(s: int):
    """(query tile, key tile) of the index kernels at `s` positions: the key tile `tile(s)`, the query tile at most
    `INDEX_QUERY_TILE` of it."""
    t = tile(s)
    return min(t, INDEX_QUERY_TILE), t


def index_supported(q_shape) -> bool:
    """q [B, S, J, D]: sequences a 128-tile divides, heads in whole groups of 8 (a sublane tile of the weights, a step
    of the kernels' loops) of whole 128s (the MXU's depth), at most 64 x 128 a position (what a query tile's buffers
    are sized for)."""
    _, s, j, d = q_shape
    return tile(s) > 0 and j % _GROUPS[-1] == 0 and d % 128 == 0 and j * d <= 64 * 128


def index_tiles_visited_pct(s: int) -> float:
    """The tile pairs `index_fwd` runs, as % of all tile pairs at `index_tiles(s)`."""
    tq, ts = index_tiles(s)
    run = sum((qi * tq + tq - 1) // ts + 1 for qi in range(s // tq))
    return 100.0 * run / ((s // tq) * (s // ts))


def _runs(qi, ki, tq: int, ts: int):
    """Whether the tile pair holds a causal pair: the key tile's first key is no later than the query tile's last query."""
    return ki * ts <= qi * tq + tq - 1


def _causal_t(qi, ki, tq: int, ts: int):
    """[ts, tq]: whether key `ki * ts + row` is no later than query `qi * tq + column`."""
    key = ki * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, tq), 0)
    return key <= qi * tq + jax.lax.broadcasted_iota(jnp.int32, (ts, tq), 1)


def _held_key_tile(qi, ki, tq: int, ts: int):
    """The key tile of a query tile's step: `ki`, held on the last tile the query tile sees through the steps past it
    (nothing is copied for a step that runs nothing)."""
    return jnp.minimum(ki, (qi * tq + tq - 1) // ts)


def _products_t(k, q_ref, j):
    """Head j's products [keys, queries] float32: k [ts, D] against q_ref [1, J, tq, D]."""
    return jax.lax.dot_general(k, q_ref[0, j], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _head_groups(heads: int, group):
    """`group(its heads' indices)` for every group of the heads."""
    size = next(g for g in _GROUPS if heads % g == 0)

    def step(g, carry):
        group([g * size + i for i in range(size)])
        return carry

    jax.lax.fori_loop(0, heads // size, step, 0)


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, acc_ref):
    # q [1, J, tq, D]; k [1, ts, D]; w [1, J, tq] float32; o [1, tq, ts] float32; acc [ts, tq] float32
    qi, ki = pl.program_id(1), pl.program_id(2)
    heads, tq, ts = q_ref.shape[1], q_ref.shape[2], k_ref.shape[1]

    @pl.when(_runs(qi, ki, tq, ts))
    def _step():
        k = k_ref[0]
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def group(heads_):
            acc_ref[...] += sum(jnp.maximum(_products_t(k, q_ref, j), 0.0) * w_ref[0, pl.ds(j, 1), :] for j in heads_)

        _head_groups(heads, group)
        o_ref[0] = jnp.where(_causal_t(qi, ki, tq, ts), acc_ref[...], 0.0).T

    @pl.when(jnp.logical_not(_runs(qi, ki, tq, ts)))
    def _above():
        o_ref[...] = jnp.zeros_like(o_ref)


def _index_operands(q, w):
    """q [B, S, J, D] -> [B, J, S, D]; w [B, S, J] -> [B, J, S] float32."""
    return q.transpose(0, 2, 1, 3), w.astype(jnp.float32).transpose(0, 2, 1)


def index_fwd(k, q, w):
    """k [B, S, D], q [B, S, J, D], w [B, S, J] (the order of the op's record) -> I [B, S, S] float32, 0.0 above the
    diagonal."""
    b, s, j, d = q.shape
    tq, ts = index_tiles(s)
    qt, wt = _index_operands(q, w)
    held = lambda qi, ki: _held_key_tile(qi, ki, tq, ts)
    return _pallas_call(
        _index_fwd_kernel, name="dsa_index_fwd", grid=(b, s // tq, s // ts),
        in_specs=[
            pl.BlockSpec((1, j, tq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, ts, d), lambda bi, qi, ki: (bi, held(qi, ki), 0)),
            pl.BlockSpec((1, j, tq), lambda bi, qi, ki: (bi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, tq, ts), lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ts, tq), jnp.float32)],
        compiler_params=_INDEX_PARAMS,
    )(qt, k, wt)


def _cotangent_t(di_ref, qi, ki, tq: int, ts: int):
    """The scores' cotangent of the tile, [keys, queries], 0.0 above the diagonal as the scores are."""
    return jnp.where(_causal_t(qi, ki, tq, ts), di_ref[0].T, 0.0)


def _index_dq_kernel(q_ref, k_ref, kt_ref, w_ref, di_ref, dq_ref, dw_ref, dq_acc_ref, dw_acc_ref):
    # grid (b, query tile, key tile).  q [1, J, tq, D]; k [1, ts, D] and again as kt [1, D, ts]; w, dw [1, J, tq];
    # di [1, tq, ts]; dq [1, J, D, tq].
    # A head's weight is one number a query, whatever the key: `dq[t, j] = w[t, j] * sum_s dI[t, s] [z > 0] k[s]`, so the
    # sum goes to the MXU without it (`dI [z > 0]` in k's dtype) and the weight multiplies the float32 sum once, at the end.
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    heads, tq, ts = q_ref.shape[1], q_ref.shape[2], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    @pl.when(_runs(qi, ki, tq, ts))
    def _step():
        k, k_t = k_ref[0], kt_ref[0]
        di = _cotangent_t(di_ref, qi, ki, tq, ts)

        def group(heads_):
            for j in heads_:
                z = _products_t(k, q_ref, j)
                live = jnp.where(z > 0.0, di, 0.0)  # dI * [z > 0]
                dw_acc_ref[pl.ds(j, 1), :] += jnp.sum(live * z, axis=0, keepdims=True)
                dq_acc_ref[j] += jax.lax.dot_general(k_t, live.astype(k.dtype), (((1,), (0,)), ((), ())),
                                                     preferred_element_type=jnp.float32)

        _head_groups(heads, group)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[...] * w_ref[0][:, None, :]).astype(dq_ref.dtype)
        dw_ref[0] = dw_acc_ref[...]


def _index_dk_kernel(q_ref, k_ref, w_ref, di_ref, dk_ref, dk_acc_ref):
    # grid (b, key tile, query tile): the key's tile stays resident.  dk [1, ts, D].  A group's heads are ONE product,
    # `dz [ts, group x tq] q [group x tq, D]`: the sum over the heads and the queries is the MXU's.
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    heads, tq, ts, d = q_ref.shape[1], q_ref.shape[2], k_ref.shape[1], q_ref.shape[3]

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)

    @pl.when(_runs(qi, ki, tq, ts))
    def _step():
        k = k_ref[0]
        di = _cotangent_t(di_ref, qi, ki, tq, ts)

        def group(heads_):
            dz = jnp.concatenate([jnp.where(_products_t(k, q_ref, j) > 0.0, di * w_ref[0, pl.ds(j, 1), :], 0.0).astype(k.dtype)
                                  for j in heads_], axis=1)
            q = q_ref[0, pl.ds(heads_[0], len(heads_))].reshape(len(heads_) * tq, d)
            dk_acc_ref[...] += jax.lax.dot_general(dz, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        _head_groups(heads, group)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)


def _index_bwd_dq(k, qt, wt, di, tq: int, ts: int):
    """(dq [B, J, D, S] in q's dtype, dw [B, J, S] float32) from heads-first q [B, J, S, D] and w [B, J, S]."""
    b, j, s, d = qt.shape
    held = lambda qi, ki: _held_key_tile(qi, ki, tq, ts)
    return _pallas_call(
        _index_dq_kernel, name="dsa_index_bwd_dq", grid=(b, s // tq, s // ts),
        in_specs=[
            pl.BlockSpec((1, j, tq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, ts, d), lambda bi, qi, ki: (bi, held(qi, ki), 0)),
            pl.BlockSpec((1, d, ts), lambda bi, qi, ki: (bi, 0, held(qi, ki))),
            pl.BlockSpec((1, j, tq), lambda bi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, tq, ts), lambda bi, qi, ki: (bi, qi, held(qi, ki))),
        ],
        out_specs=[pl.BlockSpec((1, j, d, tq), lambda bi, qi, ki: (bi, 0, 0, qi)),
                   pl.BlockSpec((1, j, tq), lambda bi, qi, ki: (bi, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct((b, j, d, s), qt.dtype), jax.ShapeDtypeStruct((b, j, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((j, d, tq), jnp.float32), pltpu.VMEM((j, tq), jnp.float32)],
        compiler_params=_INDEX_PARAMS,
    )(qt, k, k.transpose(0, 2, 1), wt, di)


def _index_bwd_dk(k, qt, wt, di, tq: int, ts: int):
    """dk [B, S, D] in k's dtype."""
    b, j, s, d = qt.shape
    seen = lambda ki, qi: jnp.maximum(qi, (ki * ts) // tq)  # held on the first query tile that sees the key tile, before it
    return _pallas_call(
        _index_dk_kernel, name="dsa_index_bwd_dk", grid=(b, s // ts, s // tq),
        in_specs=[
            pl.BlockSpec((1, j, tq, d), lambda bi, ki, qi: (bi, 0, seen(ki, qi), 0)),
            pl.BlockSpec((1, ts, d), lambda bi, ki, qi: (bi, ki, 0)),
            pl.BlockSpec((1, j, tq), lambda bi, ki, qi: (bi, 0, seen(ki, qi))),
            pl.BlockSpec((1, tq, ts), lambda bi, ki, qi: (bi, seen(ki, qi), ki)),
        ],
        out_specs=pl.BlockSpec((1, ts, d), lambda bi, ki, qi: (bi, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), k.dtype),
        scratch_shapes=[pltpu.VMEM((ts, d), jnp.float32)],
        compiler_params=_INDEX_PARAMS,
    )(qt, k, wt, di)


def index_bwd(k, q, w, di):
    """(dk, dq, dw) of `index_fwd`'s scores from their cotangent `di` [B, S, S] float32, each in its argument's shape
    and dtype; `dI [z > 0]` (times w, for dk) goes to the MXU in k's dtype, as the operands do."""
    tq, ts = index_tiles(q.shape[1])
    qt, wt = _index_operands(q, w)
    dq, dw = _index_bwd_dq(k, qt, wt, di, tq, ts)
    return _index_bwd_dk(k, qt, wt, di, tq, ts), dq.transpose(0, 3, 1, 2), dw.transpose(0, 2, 1).astype(w.dtype)
