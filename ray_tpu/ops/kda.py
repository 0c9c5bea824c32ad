"""Kimi Delta Attention (KDA, "Kimi Linear", arXiv:2510.26692): a gated delta
rule whose decay is PER CHANNEL of the key, in its chunked form; no
counterpart in the reference (SURVEY.md §5.7).  `kda_chunked` is one
`jax.custom_vjp`, declared as a `kernel_pair.KernelPair` (`PAIR`) and run by
`ops/kernel_pair.py`'s scaffold.  Both directions are plain `jax.numpy`
everywhere but on TPU: the forward `plain_forward`, a scan over `_segment`;
the backward `plain_backward`, JAX's own differentiation of `_segment`, a
segment at a time (no gradient in THIS file is derived by hand).  For TPU, at
the shapes they take, two Pallas kernels keep a chunk's matrices in VMEM
(`ops/pallas/kda.py`: `kda_fwd`, PR 39, the same arithmetic at the same
precision; `kda_bwd`, PR 41, the cotangents of that arithmetic derived by
hand, at the same precision, and held to `plain_backward` by the tests; see
Precision below).  `kda/scan`, the scope around all of this, is what the
benchmark reads it by (PERF.md section 3).

The recurrence, per batch row and per head, with a state `S_t` of shape
[K, V] (key size x value size), a log decay `g_t <= 0` per key channel
(`alpha_t = exp(g_t)` in (0, 1]^K) and a write strength `beta_t` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

which alone defines the layer.  `kda_chunked` computes the same o without a
pass over tokens.  Write the update as `S_t = Diag(alpha_t) S_{t-1} + k_t
u_t^T` with the pseudo-value `u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T
k_t)`.  Inside a chunk of C positions that enters with the state `S_0`, with
`G_t` the running sum of g from the chunk's start (inclusive) and
`D[t, s] = exp(G_t - G_s)` (per channel):

- `A[t, s] = beta_t sum_d k_t[d] k_s[d] D[t, s][d]` for s < t, and the rows of
  U solve the unit lower-triangular system
  `(I + A) U = beta (V - (K * exp(G)) S_0)`: the system's inverse, once per
  chunk, gives `Ubar = (I + A)^-1 (beta V)` and `W = (I + A)^-1 (beta K *
  exp(G))`, neither of which needs the state, and `U = Ubar - W S_0`;
- `o_t = S_0^T (q_t * exp(G_t)) + sum_{s<=t} (sum_d q_t[d] k_s[d] D[t, s][d]) u_s`;
- the state that leaves: `exp(G_C) * S_0 + (K * exp(G_C - G))^T U`;
- one `lax.scan` over the S / C chunk states: the only serial part, two small
  matmuls a step.

The chunks are worked on `SEGMENT` at a time, one `lax.scan` step each with
the state as its carry: everything above is float32 arrays the size of k or
larger (at one 16,384-token sequence and 32 heads of 128, 256 MB each, and
JAX's backward holds one cotangent of that size per level and operand of
`_decayed_lower` until they are summed: 4.6 GB in the compiled step), so a
segment's temporaries exist while that segment runs.  The forward keeps the
state that ENTERS each segment (8 x [b, 32, 128, 128] float32, 16.8 MB a
layer: the only residual beside the arguments), and the plain backward walks
the segments from the last to the first: `jax.vjp` of `_segment` at that
state, its forward once more and then its backward, the state's cotangent
carried.  The backward kernel walks the same way 128 positions at a time and
recomputes a pair of chunks from the state that entered it, so where the
forward runs for a backward (`call.residuals`) the kernel also writes the state that
enters each PAIR of chunks (128 x [b, 32, 128, 128] float32, 268 MB a layer,
alive from the layer's recompute to the end of its backward); the plain form
has no use for them and gives zeros in their place.

A decay is always the exponential of a DIFFERENCE of running sums that is
<= 0, never a quotient of exponentials: `exp(G_t) / exp(G_s)` is 0/0 once G
passes -88 in float32, which one fast channel does inside a chunk.  With a
decay per channel `D[t, s]` is a vector, so the [C, C] matrices cannot take
it as a mask on a plain `k k^T` (as `ssd_chunked` does with its scalar decay),
and a [C, C, K] array of them is 17 GB at the benchmark's shapes.
`_decayed_lower` builds the lower triangle by halves instead: the rows of a
block's lower half against the columns of its upper half, both measured from
the lower half's first row r, `(a_t * exp(G_t - G_r)) . (b_s * exp(G_r - G_s))`
with both exponents <= 0; then the same inside each half, down to single
positions.  log2(C) levels, each one batched matmul over operands the size of
k (`_decayed_lower` says how the levels are laid out).

Precision: everything here is float32 whatever the inputs are, the matmuls'
operands too, and every matmul runs at `EXACT` (three bf16 passes: a float32
product to about 16 bits): g, its running sums and their exponentials, the
decayed operands, A and the system's inverse, the chunk states, the outputs,
which leave in float32 (the layer rounds once, behind its gated norm).  The
products are small ([64, 64] .. [64, 128] x [128, 128]; 2% of a step's needed
FLOPs), and a delta rule is less forgiving than softmax attention, which
averages its values' roundings away: with bf16 operands the benchmark's
five-layer model read 1.9-2.5% of relative RMS error against the float32
reference where the comparison allows 2.68% (22 readings), with these 1.6-2.0%
(6 readings), for 0.19 s of a 1.64 s step; six passes read the same and cost
0.26 s (my chip runs, PR 37; PERF.md section 6).  The kernels hold the same
contract by hand, in both directions: float32 operands, each product three
bf16 passes with float32 accumulation, every exponent <= 0; on the chip the
forward's o is 1.6e-5 from this code's and as far from `kda_recurrent` as this
code's is (4.4e-5 against 4.2e-5; my chip runs, PR 39), the backward's five
cotangents 2.1e-5 to 2.5e-5 from this code's and within a twentieth of as far
from the recurrence's gradient (dq 6.7e-5 against 6.4e-5, dg 1.58e-4 against
1.54e-4; my chip runs, PR 41).

Sharding: nothing here names a mesh axis; batch sharding is GSPMD's to
propagate through the einsums.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import kernel_pair

# The precision of every matmul here (module docstring).
EXACT = jax.lax.Precision.HIGH
# The chunk length of the published kernels (`fla.ops.kda`); the program's own constant.
CHUNK = 64
# Chunks worked on at once (2,048 positions): what bounds the scan's temporaries, see `kda_chunked`.
SEGMENT = 32


def _decayed_lower(a, b, G, *, diagonal: bool, row_scale=None, inverse: bool = False):
    """M[..., t, s] = row_scale[t] * sum_d a[t, d] b[s, d] exp(G[t, d] - G[s, d])
    for s < t (and s == t with `diagonal`), zero above; with `inverse`,
    (I + M)^-1 instead (M strictly lower, so the system is unit lower
    triangular).  a, b, G: [..., C, K] float32, C a power of two, G
    non-increasing along C; row_scale [..., C, 1] or None.

    By halves (module docstring), level by level from single positions up, and
    every level in FULL [C, K] and [C, C] arrays: the rows that are in a lower
    half at this level carry `a * exp(G - G_r)`, the rows in an upper half
    `b * exp(G_r - G)` (r the first row of their pair's lower half; the other
    rows are zero), one [C, K] x [K, C] product, and a constant mask keeps the
    entries whose row and column share a pair.  (Blocks of their own size,
    [size, size] with size 1, 2, 4, .., are arrays whose minor dimensions a TPU
    pads to (8, 128) tiles.)  The inverse rides the same loop: X holds the
    inverse of the diagonal blocks of `size`; with this level's blocks M21
    below them, `X - X M21 X` is the inverse of the blocks of twice the size
    (a rounding here is one the next level multiplies), so no triangular solve is called: on the v5e XLA's ran 0.5 s
    a step in 16,384 [64, 64] systems (PERF.md section 6, PR 37)."""
    f32 = jnp.float32
    *lead, c, k = a.shape
    eye = jnp.eye(c, dtype=f32)
    if diagonal:
        out = jnp.sum(a * b, axis=-1)[..., None] * eye
    else:
        out = jnp.broadcast_to(eye, (*lead, c, c)) if inverse else jnp.zeros((*lead, c, c), f32)
    pos = np.arange(c)
    size = 1
    while size < c:
        in_lower = ((pos // size) % 2 == 1)[:, None]  # [C, 1]: the row is in a lower half at this level
        same_pair = pos[:, None] // (2 * size) == pos[None, :] // (2 * size)
        keep = jnp.asarray(same_pair & in_lower & ~in_lower.T)  # [C, C]: lower-half row, upper-half column, one pair
        in_lower = jnp.asarray(in_lower)
        by_pair = G.reshape(*lead, c // (2 * size), 2, size, k)
        ref = jnp.broadcast_to(by_pair[..., 1:, :1, :], by_pair.shape).reshape(G.shape)  # the lower half's first row
        rel = G - ref  # <= 0 on the rows of a lower half, >= 0 on those of an upper half
        lower = jnp.where(in_lower, a * jnp.exp(jnp.where(in_lower, rel, 0.0)), 0.0)
        upper = jnp.where(in_lower, 0.0, b * jnp.exp(jnp.where(in_lower, 0.0, -rel)))
        cross = jnp.where(keep, jnp.einsum("...td,...sd->...ts", lower, upper, precision=EXACT), 0.0)
        if row_scale is not None:
            cross = cross * row_scale
        if inverse:
            out = out - jnp.einsum("...ts,...su,...uv->...tv", out, cross, out, precision=EXACT)
        else:
            out = out + cross
        size *= 2
    return out


def _segment(state, inp):
    """Some chunks at once, in plain `jax.numpy`: (the state that enters, their
    q, k, v, g, beta as [b, c, H, l, d]) -> (the state that leaves,
    o [b, c, H, l, V]).  The forward off TPU, and the function whose `jax.vjp`
    is the backward everywhere (`plain_backward`)."""
    f32 = jnp.float32
    qc, kc, vc, gc, bc = (x.astype(f32) for x in inp)  # here, a segment at a time: v stays bf16 until then
    chunk, dv = qc.shape[-2], vc.shape[-1]
    # the running sum from each chunk's start (inclusive), as a product with a triangle of ones:
    # `cumsum` lowers to a windowed reduction that ran at 24 GB/s on the v5e
    G = jnp.einsum("ts,bchsk->bchtk", jnp.tril(jnp.ones((chunk, chunk), f32)), gc, precision=EXACT)

    # inside a chunk: nothing here needs the state that enters
    solve = _decayed_lower(kc, kc, G, diagonal=False, row_scale=bc, inverse=True)  # (I + A)^-1
    qk = _decayed_lower(qc, kc, G, diagonal=True)
    from_start = jnp.exp(G)  # [b, c, H, l, K], <= 1
    rhs = jnp.concatenate([bc * vc, bc * (kc * from_start)], axis=-1)
    solved = jnp.einsum("bchts,bchsd->bchtd", solve, rhs, precision=EXACT)
    u_bar, w = solved[..., :dv], solved[..., dv:]
    q_in = qc * from_start
    k_out = kc * jnp.exp(G[..., -1:, :] - G)  # each row up to the chunk's end
    through = from_start[..., -1, :]  # [b, c, H, K]: the whole chunk's decay

    # the serial part: the state that enters each chunk
    def cross(carry, per_chunk):
        w_c, u_c, k_c, decay_c = per_chunk
        u = u_c - jnp.einsum("bhlk,bhkv->bhlv", w_c, carry, precision=EXACT)
        out = carry * decay_c[..., None] + jnp.einsum("bhlk,bhlv->bhkv", k_c, u, precision=EXACT)
        return out, carry

    by_chunk = lambda x: jnp.moveaxis(x, 1, 0)
    state, entering = jax.lax.scan(cross, state, tuple(map(by_chunk, (w, u_bar, k_out, through))))
    entering = jnp.moveaxis(entering, 0, 1)  # [b, c, H, K, V]

    # every position's output, the segment's chunks at once
    u = u_bar - jnp.einsum("bchlk,bchkv->bchlv", w, entering, precision=EXACT)
    o = jnp.einsum("bchlk,bchkv->bchlv", q_in, entering, precision=EXACT)
    o = o + jnp.einsum("bchts,bchsv->bchtv", qk, u, precision=EXACT)
    return state, o


def segments(x, chunk: int, per_segment: int):
    """[b, S, H, d] -> [segments, b, c, H, l, d]: what the scans over `_segment` and the kernel walk."""
    b, s, h, d = x.shape
    x = x.reshape(b, s // (chunk * per_segment), per_segment, chunk, h, d)
    return x.transpose(1, 0, 2, 4, 3, 5)


def positions(o):
    """Back: [segments, b, c, H, l, d] -> [b, S, H, d]."""
    n, b, c, h, l, d = o.shape
    return o.transpose(1, 0, 2, 4, 3, 5).reshape(b, n * c * l, h, d)


def per_segment(s: int, chunk: int) -> int:
    return math.gcd(s // chunk, SEGMENT)


def plain_forward(q, k, v, g, beta):
    """One `lax.scan` over `_segment`: q, k, v, g, beta as `segments` gives them ->
    (o [segments, b, c, H, l, V], the state that enters each segment [segments, b, H, K, V]).
    The plain form of the per-channel rule, which `ops/gdn.py`'s scalar-decay rule is defined by."""
    _, b, _, h, _, dk = k.shape

    def step(state, inp):
        left, o = _segment(state, inp)
        return left, (o, state)

    return jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), (q, k, v, g, beta))[1]


def plain_backward(q, k, v, g, beta, entering, d_o):
    """JAX's own differentiation of `_segment`, a segment at a time from the
    last to the first: the segment's forward once more at the state that
    entered it, then its backward, the state's cotangent carried along.  The
    [chunk, chunk] matrices, their decayed operands and every cotangent of them
    exist for one segment at a time.  Arguments and cotangents as `segments`
    gives them (beta [segments, b, c, H, l, 1])."""

    def step(d_state, xs):
        state, inp, d_o = xs
        # a checkpoint, so that what `pull` holds is the segment's arguments and it runs forward and
        # backward in one piece (the primal outputs are unused: the forward runs once, in `pull`);
        # without it the step's peak was 0.11 GB higher (AOT build, PR 39)
        _, pull = jax.vjp(jax.checkpoint(_segment), state, inp)
        d_state, d_inp = pull((d_state, d_o))
        return d_state, d_inp

    return jax.lax.scan(step, jnp.zeros_like(entering[0]), (entering, (q, k, v, g, beta), d_o), reverse=True)[1]


def _forward(call, q, k, v, g, beta):
    """(o [b, S, H, V] float32, the state that enters each segment, and where
    the forward runs for a backward at shapes the kernel takes the state that
    enters each PAIR of chunks [segments, b, c / 2, H, K, V], which the
    backward kernel starts from; else None).  The plain form's backward has no
    use for the pairs' states and gives zeros of their shape."""
    cut = functools.partial(segments, chunk=call.chunk, per_segment=per_segment(k.shape[1], call.chunk))
    pair_states = call.residuals and call.takes

    def plain(q, k, v, g, beta):
        o, entering = plain_forward(q, k, v, g, cut(beta[..., None]))
        if not pair_states:
            return o, entering
        n, b, c = k.shape[:3]
        return o, entering, jnp.zeros((n, b, c // 2, *entering.shape[2:]), jnp.float32)

    kernel = functools.partial(call.kernels.kda_fwd, pair_states=pair_states)
    o, entering, *pairs = call(kernel, plain, *map(cut, (q, k, v, g)), beta)
    return positions(o), entering, (pairs[0] if pairs else None)


def _backward(call, q, k, v, g, beta, entering, pairs, do):
    """(dq, dk, dv, dg, dbeta), each in its argument's dtype.  For TPU at the
    shapes the kernel takes, `kda_bwd` from the state that entered each pair of
    chunks; everywhere else `plain_backward` from the state that entered each
    segment.  Both read the arrays the forward read, as `segments` gives them."""
    cut = functools.partial(segments, chunk=call.chunk, per_segment=per_segment(k.shape[1], call.chunk))

    def plain(q, k, v, g, beta, entering, pairs, d_o):
        *d_inputs, dbeta = plain_backward(q, k, v, g, cut(beta[..., None]), entering, d_o)
        return (*d_inputs, positions(dbeta)[..., 0])  # each in its argument's dtype: `_segment` casts inside

    def kernel(q, k, v, g, beta, entering, pairs, d_o):
        return call.kernels.kda_bwd(q, k, v, g, beta, pairs, d_o)

    *d_inputs, dbeta = call(kernel, plain, *map(cut, (q, k, v, g)), beta, entering, pairs, cut(do))
    return (*map(positions, d_inputs), dbeta)


PAIR = kernel_pair.KernelPair(
    name="kda_chunked", scope="kda/scan", kernels="kda", power_of_two_chunk=True,
    takes=lambda kernels, q, k, v, g, beta, chunk: kernels.supported(
        k.shape[-1], v.shape[-1], chunk, per_segment(k.shape[1], chunk)),
    forward=_forward, backward=_backward,
)


def kda_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    chunk: Optional[int] = None,
    mesh=None,
    batch_axes=None,
) -> jax.Array:
    """The gated delta rule of the module docstring, chunked.

    q, k [b, S, H, K] (already normalised and scaled as the layer has them);
    v [b, S, H, V]; g [b, S, H, K] (log decay, <= 0); beta [b, S, H].  Returns
    o [b, S, H, V] in float32.  `chunk` (None = `CHUNK`, a power of two) is
    cut to S when S is shorter; S must be a multiple of it.

    mesh / batch_axes say how the arguments are sharded.  GSPMD partitions the
    plain form by itself; a Mosaic kernel it cannot, so with a mesh the kernel
    runs under shard_map over the batch axes, each device on its own rows with
    the whole sequence and every head."""
    return kernel_pair.run(PAIR, q, k, v, g, beta, chunk=chunk or CHUNK, mesh=mesh, batch_axes=batch_axes)


def kda_recurrent(q, k, v, g, beta):
    """The recurrence itself, token by token in float32: what `kda_chunked`
    equals, and what the tests hold it to.  Same arguments; o in v's dtype."""
    f32 = jnp.float32
    b, s, h, dk = k.shape

    def step(state, inp):
        qt, kt, vt, gt, bt = inp  # [b, H, K], ..., [b, H]
        state = state * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, state))
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    seq = lambda x: jnp.moveaxis(x.astype(f32), 1, 0)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), tuple(map(seq, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)
