"""The chunked gated delta rule with ONE decay a head (Gated DeltaNet, as
Qwen3-Next runs it) in Pallas for TPU, forward (`gdn_fwd`) and backward
(`gdn_bwd`).  `ops/gdn.py` has the op; `ops/kda.py` has the recurrence, its
chunked form and the plain `jax.numpy` code these equal (`_segment` with the
head's decay broadcast over the key's channels, and JAX's own differentiation
of it), which stay the path for every other platform and shape and are what
the tests hold both kernels to.

What one decay a head buys.  `ops/pallas/kda.py` has a decay per CHANNEL of the
key: `D[t, s] = exp(G_t - G_s)` is a vector there, so each [chunk, chunk]
matrix is built by halves, six levels of decayed operands with two
exponentials and two products a level.  Here `D[t, s]` is a number, so inside
a pair of chunks (128 positions of one head, every array [128, 128] float32):

- `G`, the inclusive running sum of g inside each chunk, is a [128, 1] column
  (and the same numbers as a [1, 128] row): XLA's, on [b, S, Hv], as
  `ops/ssm.py` has Mamba-2's `cum`;
- `decay = exp(min(G_t - G_s, 0))`: ONE [128, 128] exponential of a difference
  taken first, and `A = tril(k k^T * decay, -1)`, `QK = tril(q k^T * decay)`:
  ONE product each (both chunks of the pair at once; what lies between the two
  is masked away);
- `X = (I + beta A)^-1` by doubling as the KDA kernels have it (`X - X M X`
  over the blocks of 1, 2, .., 32 of one plain matrix; no triangular solve);
- `[u_bar | w] = X [beta v | beta k exp(G)]`, `q_in = q exp(G)`, `k_out = k
  exp(G_end - G)`: an exponential is a column broadcast along the lanes; then
  the state chain of `ops/pallas/kda.py`, chunk by chunk: `u = u_bar - w S`,
  `o = q_in S + QK u`, `S <- exp(G_end) S + k_out^T u`.

About 19 three-pass products a pair of chunks forward where the per-channel
kernel has 31, 36 backward where it has 67, and 4 exponentials where it has 9
full tiles.

The grid and the state chain are `kda_fwd`'s / `kda_bwd`'s: the sequence last
and sequential, a head's state [K, V] float32 in a VMEM scratch, the state that
enters each segment written and, for a backward, the state that enters each
pair of chunks; the backward walks the sequence from its END with the state's
cotangent in the scratch and recomputes a pair from the state that entered it.
Unlike those:

- the arrays are read where the layer has them: q, k [b, S, Hk K], v, o
  [b, S, Hv V], a block some positions of a head's lanes; nothing is repeated
  or laid out again in HBM.
- a program is one KEY head and the `Hv / Hk` value heads that read it (grid
  (batch, key head, segment, program)): q and k are loaded, split and
  multiplied (`k k^T`, `q k^T`) once for the group, and backward dq and dk
  leave summed over it.
- a program's `_UNITS` pairs of chunks (its value heads x their pairs) are
  independent until the state chain, and what a pair needs before the state
  is written STAGE BY STAGE for all of them (`_inside`): the inverse is ten
  dependent products, and the compiler does not interleave one pair's chain
  with the next pair's by itself (7.6 ms a layer forward pair by pair, 5.0
  stage by stage; my chip runs, PR 58).  The value heads' state chains run
  side by side for the same reason, and backward only `du` and the state's
  cotangent are on the chain; the rest of a pair's backward follows it.
- products that share their right operand are one product over 128 rows (`w
  S` and `q_in S`; `do S^T` and `du S^T`; `q_in^T do - w^T du`): the MXU loads
  that operand once.
- the cotangents of G and beta leave as rows [b, Hv, 1, S] (the two columns
  share ONE float32 transpose a pair), and the reverse running sum that turns
  dG into dg is XLA's, here in `gdn_bwd`.

PRECISION, the contract of `ops/kda.py`, unchanged: every operand is float32
and every product three bf16 passes with float32 accumulation (`_split`,
`_dot`, `ops/pallas/kda.py`'s own); no dot takes float32 operands; every
exponential is of a difference <= 0, clamped there (and differentiated as the
identity the clamp is on exact values); the running sums are float32's own;
o leaves in float32.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import kda
from ray_tpu.ops.pallas.kda import CHUNK, _LANES, _NT, _PAIR, _alone, _dot, _head_column, _split
from ray_tpu.util import tracing

_CHUNKS = _PAIR // CHUNK
_UNITS = 4  # pairs of chunks a program works on side by side, over its value heads: PERF.md section 6, PR 58


def supported(dk: int, dv: int, chunk: int, per_segment: int, hv: int, hk: int) -> bool:
    """Whether the kernels take these shapes (else `ops/gdn.py` runs the plain
    form): what the KDA kernels take, and whole groups of value heads a key head."""
    return kda.supported(dk, dv, chunk, per_segment) and hk > 0 and hv % hk == 0


def _within_chunks(g):
    """[b, S, Hv] -> [b, S / chunk, chunk, Hv]: the axis a running sum inside a chunk runs along."""
    b, s, h = g.shape
    return g.reshape(b, s // CHUNK, CHUNK, h)


def _running_sums(g):
    """g [b, S, Hv] -> G, its inclusive running sum inside each chunk, float32: as columns [b, S, Hv] and as rows [b, Hv, 1, S]."""
    G = jnp.cumsum(_within_chunks(g.astype(jnp.float32)), axis=2).reshape(g.shape)
    return G, jnp.moveaxis(G, 1, 2)[:, :, None, :]


def _inside(keys, units):
    """Some pairs of chunks before the state that enters them: `keys`, per pair
    of chunks (q, k [128, 128] float32); `units`, per value head and pair of
    chunks (the pair's place in `keys`, v [128, 128] float32, G and beta
    [128, 1], G again as [1, 128]) -> per unit what both directions go on from
    (module docstring).  The units are independent here, and every stage is
    written for all of them before the next: the inverse is ten DEPENDENT
    products a unit, and side by side the units' chains fill each other's
    waits.  `q k^T` and `k k^T` are their key head's: one product for the
    group's value heads."""
    f32 = jnp.float32
    n = _PAIR
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    same_chunk = row // CHUNK == col // CHUNK
    below, upto = same_chunk & (col < row), same_chunk & (col <= row)

    halves = [(_split(q), _split(k)) for q, k in keys]
    products = [(_dot(ks, ks, _NT), _dot(qs, ks, _NT)) for qs, ks in halves]
    out = []
    for j, v, G, G_row, beta in units:
        decay = jnp.exp(jnp.minimum(G - G_row, 0.0))  # [t, s]
        A = jnp.where(below, products[j][0] * decay, 0.0)
        QK = jnp.where(upto, products[j][1] * decay, 0.0)
        out.append(types.SimpleNamespace(q=keys[j][0], k=keys[j][1], v=v, beta=beta, qs=halves[j][0], ks=halves[j][1],
                                         below=below, upto=upto, decay=decay, A=A, QK=QK, M=A * beta))

    Xs = [(row == col).astype(f32)] * len(out)
    for level in range(CHUNK.bit_length() - 1):
        # this level's entries: lower-half row, upper-half column, one pair of blocks (a pair never crosses a chunk)
        keep = ((row >> level) - (col >> level) == 1) & ((row >> level) & 1 == 1)
        crosses = [jnp.where(keep, p.M, 0.0) for p in out]
        if level == 0:
            Xs = [X - cross for X, cross in zip(Xs, crosses)]
        else:
            split = [_split(X) for X in Xs]
            inner = [_dot(_split(cross), X) for cross, X in zip(crosses, split)]
            outer = [_dot(X, _split(MX)) for X, MX in zip(split, inner)]
            Xs = [X - XMX for X, XMX in zip(Xs, outer)]

    for p, X, (_, _, G, _, _) in zip(out, Xs, units):
        lasts = [G[c * CHUNK + CHUNK - 1: (c + 1) * CHUNK] for c in range(_CHUNKS)]  # [1, 1] each: G at a chunk's end
        p.X, p.from_start = X, jnp.exp(jnp.minimum(G, 0.0))
        p.to_end = jnp.exp(jnp.minimum(jnp.concatenate([jnp.broadcast_to(e, (CHUNK, 1)) for e in lasts], axis=0) - G, 0.0))
        p.whole = [jnp.broadcast_to(jnp.exp(jnp.minimum(e, 0.0)), (n, 1)) for e in lasts]  # a chunk's whole decay, on the state's rows
        X = _split(X)
        p.rhs_v = _split(p.beta * p.v)
        p.k_start = p.k * p.from_start
        p.rhs_k = _split(p.beta * p.k_start)
        p.u_bar, p.w, p.q_in, p.k_out = _dot(X, p.rhs_v), _dot(X, p.rhs_k), p.q * p.from_start, p.k * p.to_end
    return out


def _pair(p, state):
    """What `_inside` gives for a unit and the state [K, V] that enters it -> (o [128, V], the state that leaves)."""
    k_out_t = _split(p.k_out.T)  # [K, positions]
    us, o_state = [], []
    for c in range(_CHUNKS):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        # w S and q_in S as ONE product over 128 rows: the state is loaded once
        from_state = _dot(_split(jnp.concatenate([p.w[rows], p.q_in[rows]], axis=0)), _split(state))
        us.append(p.u_bar[rows] - from_state[:CHUNK])
        o_state.append(from_state[CHUNK:])
        state = state * p.whole[c] + _dot(k_out_t, _split(_alone(us[c], c)))  # the other chunk's rows are zeros
    o = jnp.concatenate(o_state, axis=0) + _dot(_split(p.QK), _split(jnp.concatenate(us, axis=0)))
    return o, state


def _chunks_per_program(per_segment: int, group: int) -> int:
    """Chunks of one program: `kda.chunks_per_program`'s (whole pairs, a
    divisor of the segment's), fewer the more value heads a key head has: a
    program works on all of them, `_UNITS` pairs of chunks in all where it can."""
    n = max(min(_UNITS // group, per_segment // 2), 1) * 2
    while n and per_segment % n:
        n -= 2
    return n


def _plan(who: str, q, k, v, per_segment: int, reverse: bool):
    """What both kernels share: (the grid, the arrays' shapes as the kernels
    view them, their block specs by name).  q and k as [b, S, Hk K], v (o and
    their cotangents) as [b, S, Hv V]: free reshapes.  A program is one KEY
    head's `rows` positions and the `group` value heads that read it.
    `reverse` walks the sequence from its end."""
    b, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    if s % CHUNK or not supported(dk, dv, CHUNK, per_segment, hv, hk):
        raise ValueError(f"{who}: unsupported shapes {k.shape}, {v.shape}, {per_segment} chunks a segment")
    group = hv // hk
    per = _chunks_per_program(per_segment, group)
    rows, programs = per * CHUNK, per_segment // per
    n = s // (per_segment * CHUNK)
    if reverse:
        segment = lambda si: n - 1 - si
        position = lambda si, i: (n - 1 - si) * programs + programs - 1 - i
    else:
        segment = lambda si: si
        position = lambda si, i: si * programs + i
    specs = dict(
        key=pl.BlockSpec((None, rows, dk), lambda bi, hi, si, i: (bi, position(si, i), hi)),
        value=pl.BlockSpec((None, rows, group * dv), lambda bi, hi, si, i: (bi, position(si, i), hi)),
        column=pl.BlockSpec((None, rows, hv), lambda bi, hi, si, i: (bi, position(si, i), 0)),
        row=pl.BlockSpec((None, group, 1, rows), lambda bi, hi, si, i: (bi, hi, 0, position(si, i))),
        entering=pl.BlockSpec((None, None, group, dk, dv), lambda bi, hi, si, i: (segment(si), bi, hi, 0, 0)),
        pairs=pl.BlockSpec((None, per // 2, group, dk, dv), lambda bi, hi, si, i: (bi, position(si, i), hi, 0, 0)),
    )
    shapes = dict(key=(b, s, hk * dk), value=(b, s, hv * dv), row=(b, hv, 1, s), entering=(n, b, hv, dk, dv),
                  pairs=(b, s // _PAIR, hv, dk, dv), state=(group, dk, dv))
    return (b, hk, n, programs), shapes, specs


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))


def _units_of(q_ref, k_ref, v_ref, g_ref, g_row_ref, beta_ref):
    """The program's blocks as `_inside` takes them: (the lanes of each value
    head in a value block, the rows of each pair of chunks, `keys`, `units` head
    by head)."""
    f32 = jnp.float32
    group, dv = g_row_ref.shape[0], v_ref.shape[1] // g_row_ref.shape[0]
    first = pl.program_id(1) * group  # the key head's first value head
    lanes = [slice(i * dv, (i + 1) * dv) for i in range(group)]
    ats = [slice(j * _PAIR, (j + 1) * _PAIR) for j in range(q_ref.shape[0] // _PAIR)]
    keys = [(q_ref[at].astype(f32), k_ref[at].astype(f32)) for at in ats]
    units = []
    for i, of_head in enumerate(lanes):
        G, beta = _head_column(g_ref, first + i), _head_column(beta_ref, first + i)
        units += [(j, v_ref[at, of_head].astype(f32), G[at], g_row_ref[i, :, at], beta[at]) for j, at in enumerate(ats)]
    return lanes, ats, keys, units


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, g_row_ref, beta_ref, o_ref, entering_ref, *rest):
    *pairs_ref, state_ref = rest  # with `pair_states`, one more output: the state that enters each pair of chunks
    first_of_segment = pl.program_id(3) == 0

    @pl.when((pl.program_id(2) == 0) & first_of_segment)
    def _zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(first_of_segment)
    def _keep():
        entering_ref[...] = state_ref[...]

    lanes, ats, keys, units = _units_of(q_ref, k_ref, v_ref, g_ref, g_row_ref, beta_ref)
    ps = _inside(keys, units)
    states = [state_ref[i] for i in range(len(lanes))]
    for j, at in enumerate(ats):  # the heads' chains are independent: side by side, pair by pair
        for i, of_head in enumerate(lanes):
            if pairs_ref:
                pairs_ref[0][j, i] = states[i]
            o_ref[at, of_head], states[i] = _pair(ps[i * len(ats) + j], states[i])
    for i, state in enumerate(states):
        state_ref[i] = state


def gdn_fwd(q, k, v, g, beta, *, per_segment: int, pair_states=False, interpret=False):
    """q, k [b, S, Hk, 128], v [b, S, Hv, 128] in any float dtype, g (log
    decay, <= 0) and beta [b, S, Hv] float32, `per_segment` chunks of 64 a
    segment -> (o [b, S, Hv, 128] float32, the state that enters each segment
    [segments, b, Hv, K, V] float32) and, with `pair_states`, the state that
    enters each pair of chunks [b, S / 128, Hv, K, V] float32: what `gdn_bwd`
    starts each 128 positions from."""
    f32 = jnp.float32
    grid, shape, spec = _plan("gdn_fwd", q, k, v, per_segment, reverse=False)
    call = pl.pallas_call(
        _fwd_kernel,
        name="gdn_fwd",
        interpret=interpret,
        grid=grid,
        in_specs=[spec[name] for name in ("key", "key", "value", "column", "row", "column")],
        out_specs=[spec["value"], spec["entering"]] + [spec["pairs"]] * pair_states,
        out_shape=[jax.ShapeDtypeStruct(shape["value"], f32), jax.ShapeDtypeStruct(shape["entering"], f32)]
        + [jax.ShapeDtypeStruct(shape["pairs"], f32)] * pair_states,
        scratch_shapes=[pltpu.VMEM(shape["state"], f32)],
        compiler_params=_PARAMS,
    )
    with tracing.scope("gdn_fwd", kernel=True):
        o, *states = call(q.reshape(shape["key"]), k.reshape(shape["key"]), v.reshape(shape["value"]), *_running_sums(g),
                          beta.astype(f32))
        return (o.reshape(v.shape), *states)


def _units_bwd(ps, states, d_os, d_states, pairs_per_head: int):
    """`_pair` backwards for a program's units: what `_inside` gives for each
    (head by head, a head's pairs of chunks in order), the state that entered
    each, the cotangent of each o [128, V] and, per head, the cotangent of the
    state that leaves its LAST pair [K, V] -> (per unit (dq, dk, dv
    [128, 128], the cotangents of beta and of G as ROWS [1, 128]), per head the
    cotangent of the state that enters its first pair).

    (A) Per unit, the state that enters its second chunk, and what of the
    backward does not wait for a state's cotangent.  (B) The state chains
    backwards, the heads' side by side, pair by pair from the last and chunk 1
    then chunk 0: `du`, and the cotangent of the state that enters, alone;
    everything else is (C), per unit again: the cotangents of QK, q_in, k_out,
    w and, through `[u_bar | w] = X rhs`, of X and rhs; with `X = (I + M)^-1`,
    `dM = -X^T dX X^T` kept where M has entries: no level of the inverse is
    differentiated.  `A = k k^T * decay` and `QK = q k^T * decay`: with `P = dA
    * decay` and `R = dQK * decay`, `dk = (P + P^T) k + R^T q`, `dq = R k`, and
    `W = dA * A + dQK * QK` is the cotangent of `G_t - G_s`: its row sums go to
    G_t, its column sums, negated, to G_s."""
    f32 = jnp.float32
    chunks = [slice(c * CHUNK, (c + 1) * CHUNK) for c in range(_CHUNKS)]

    # (A) the states that enter each chunk; QK^T do; per chunk q_in above -w, transposed: [K, 128]
    for p, state, d_o in zip(ps, states, d_os):
        k_out_t = _split(p.k_out.T)
        p.states, p.us = [state], []
        for c, rows in enumerate(chunks):
            p.us.append(p.u_bar[rows] - _dot(_split(p.w[rows]), _split(p.states[c])))
            if c + 1 < _CHUNKS:
                p.states.append(p.states[c] * p.whole[c] + _dot(k_out_t, _split(_alone(p.us[c], c))))
        p.d_o, p.d_os, p.d_wholes = d_o, _split(d_o), []
        p.from_qk = _dot(_split(p.QK.T), p.d_os)
        p.into = [_split(jnp.concatenate([p.q_in[rows], -p.w[rows]], axis=0).T) for rows in chunks]
        p.k_outs = [_split(p.k_out[rows]) for rows in chunks]

    # (B) the state chains backwards
    d_states = list(d_states)
    for j in reversed(range(pairs_per_head)):
        for c, rows in reversed(list(enumerate(chunks))):
            for i, d_state in enumerate(d_states):
                p = ps[i * pairs_per_head + j]
                if c == _CHUNKS - 1:
                    p.d_us, p.d_lefts, p.do_du = [None] * _CHUNKS, [None] * _CHUNKS, [None] * _CHUNKS
                p.d_lefts[c] = _split(d_state)
                p.d_us[c] = p.from_qk[rows] + _dot(p.k_outs[c], p.d_lefts[c])
                # q_in^T do - w^T du as ONE product over 128 rows, against do above du
                p.do_du[c] = _split(jnp.concatenate([p.d_o[rows], p.d_us[c]], axis=0))
                # of the chunk's whole decay [1, 1], times it: what goes to G at the chunk's end
                p.d_wholes.append(jnp.sum(jnp.sum(p.states[c] * d_state, axis=1, keepdims=True), axis=0, keepdims=True)
                                  * p.whole[c][:1])
                d_states[i] = d_state * p.whole[c] + _dot(p.into[c], p.do_du[c])

    # (C) what hangs on the chains
    out = []
    position = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, _LANES), 1)
    for p in ps:
        # do S^T and du S^T as ONE product over 128 rows: (B)'s do above du again
        of_state = [_dot(p.do_du[c], _split(p.states[c]), _NT) for c in range(_CHUNKS)]
        d_q_in = jnp.concatenate([x[:CHUNK] for x in of_state], axis=0)
        d_w = -jnp.concatenate([x[CHUNK:] for x in of_state], axis=0)
        d_k_out = jnp.concatenate([_dot(_split(p.us[c]), p.d_lefts[c], _NT) for c in range(_CHUNKS)], axis=0)
        d_QK = jnp.where(p.upto, _dot(p.d_os, _split(jnp.concatenate(p.us, axis=0)), _NT), 0.0)

        d_u, d_w = _split(jnp.concatenate(p.d_us, axis=0)), _split(d_w)  # u = u_bar - w S: d_u is u_bar's cotangent too
        Xt = _split(p.X.T)
        d_rhs_v, d_rhs_k = _dot(Xt, d_u), _dot(Xt, d_w)
        d_X = _dot(d_u, p.rhs_v, _NT) + _dot(d_w, p.rhs_k, _NT)
        d_M = jnp.where(p.below, -_dot(_split(_dot(Xt, _split(d_X))), Xt), 0.0)

        d_A = d_M * p.beta
        P, R = d_A * p.decay, d_QK * p.decay
        W = d_A * p.A + d_QK * p.QK
        dq = d_q_in * p.from_start + _dot(_split(R), p.ks)
        dk = (d_k_out * p.to_end + p.beta * d_rhs_k * p.from_start + _dot(_split(P + P.T), p.ks) + _dot(_split(R.T), p.qs))
        dv = p.beta * d_rhs_v
        ended = jnp.sum(d_k_out * p.k_out, axis=1, keepdims=True)  # [128, 1]: d(G_end - G)
        for_G = jnp.sum(W + (d_q_in * p.q + p.beta * d_rhs_k * p.k) * p.from_start, axis=1, keepdims=True) - ended
        for c, d_whole in enumerate(reversed(p.d_wholes)):  # (B) walked the chunks from the last
            d_last = d_whole + jnp.sum(ended[chunks[c]], axis=0, keepdims=True)
            for_G = for_G + jnp.where(position == c * CHUNK + CHUNK - 1, jnp.broadcast_to(d_last, (_PAIR, 1)), 0.0)
        for_beta = jnp.sum(d_rhs_v * p.v + d_rhs_k * p.k_start + d_M * p.A, axis=1, keepdims=True)
        # the two columns as rows, one transpose for both: lane 0 and lane 1 of a tile
        as_rows = jnp.where(lane == 0, for_beta, jnp.where(lane == 1, for_G, jnp.zeros((_PAIR, _LANES), f32))).T
        out.append((dq, dk, dv, as_rows[:1], as_rows[1:2] - jnp.sum(W, axis=0, keepdims=True)))
    return out, d_states


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, g_row_ref, beta_ref, states_ref, d_o_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state_ref):
    f32 = jnp.float32

    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _zero():  # the grid walks the sequence from its END: nothing leaves the last chunk
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    lanes, ats, keys, units = _units_of(q_ref, k_ref, v_ref, g_ref, g_row_ref, beta_ref)
    by_unit = [(i, of_head, j, at) for i, of_head in enumerate(lanes) for j, at in enumerate(ats)]
    out, d_states = _units_bwd(_inside(keys, units), [states_ref[j, i] for i, _, j, _ in by_unit],
                               [d_o_ref[at, of_head].astype(f32) for _, of_head, _, at in by_unit],
                               [d_state_ref[i] for i in range(len(lanes))], len(ats))
    for j, at in enumerate(ats):  # dq and dk: the group's sum
        dq_ref[at] = sum(out[i * len(ats) + j][0] for i in range(len(lanes))).astype(dq_ref.dtype)
        dk_ref[at] = sum(out[i * len(ats) + j][1] for i in range(len(lanes))).astype(dk_ref.dtype)
    for (i, of_head, j, at), (_, _, dv, dbeta, dG) in zip(by_unit, out):
        dv_ref[at, of_head] = dv.astype(dv_ref.dtype)
        dbeta_ref[i, :, at] = dbeta
        dg_ref[i, :, at] = dG
    for i, d_state in enumerate(d_states):
        d_state_ref[i] = d_state


def gdn_bwd(q, k, v, g, beta, states, d_o, *, per_segment: int, interpret=False):
    """`gdn_fwd`'s arguments, the state that enters each PAIR of chunks
    [b, S / 128, Hv, K, V] (`gdn_fwd(.., pair_states=True)`) and the cotangent
    of o [b, S, Hv, 128] -> (dq, dk, dv, dg, dbeta in the shapes and dtypes of
    q, k, v, g, beta).  The kernel leaves the cotangents of G and beta as rows
    [b, Hv, 1, S]; the way back to [b, S, Hv] and the reverse running sum
    inside a chunk (G is g's running sum) are XLA's, here."""
    f32 = jnp.float32
    grid, shape, spec = _plan("gdn_bwd", q, k, v, per_segment, reverse=True)
    call = pl.pallas_call(
        _bwd_kernel,
        name="gdn_bwd",
        interpret=interpret,
        grid=grid,
        in_specs=[spec[name] for name in ("key", "key", "value", "column", "row", "column", "pairs", "value")],
        out_specs=[spec[name] for name in ("key", "key", "value", "row", "row")],
        out_shape=[jax.ShapeDtypeStruct(shape["key"], q.dtype), jax.ShapeDtypeStruct(shape["key"], k.dtype),
                   jax.ShapeDtypeStruct(shape["value"], v.dtype), jax.ShapeDtypeStruct(shape["row"], f32),
                   jax.ShapeDtypeStruct(shape["row"], f32)],
        scratch_shapes=[pltpu.VMEM(shape["state"], f32)],
        compiler_params=_PARAMS,
    )
    with tracing.scope("gdn_bwd", kernel=True):
        dq, dk, dv, dG, dbeta = call(q.reshape(shape["key"]), k.reshape(shape["key"]), v.reshape(shape["value"]),
                                     *_running_sums(g), beta.astype(f32), states, d_o.reshape(shape["value"]))
        positions = lambda d: jnp.moveaxis(d[:, :, 0], 1, 2)  # [b, Hv, 1, S] -> [b, S, Hv]
        dg = jax.lax.cumsum(_within_chunks(positions(dG)), axis=2, reverse=True).reshape(g.shape)
        return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), dg.astype(g.dtype), positions(dbeta).astype(beta.dtype)
