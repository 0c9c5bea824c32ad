"""Learned sparse attention (DeepSeek-V3.2-Exp's DSA): an indexer scores every
causal pair cheaply, each query keeps its `k` best keys, and the attention
core runs over those alone.  Four steps, `[batch, seq, ...]` throughout:

- `index_scores(qi, ki, w)`: `I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])`
  over the indexer's heads j, ONE key a position; operands as they come (bf16
  in a step), products accumulated, weighed and summed in float32.  `[B, S, S]`
  float32: every causal pair scored, every pair above the diagonal 0.0 (the
  selection and the loss look at causal pairs alone).  A `KernelPair`
  (`INDEX`): in a step lowered for TPU, at shapes they take, the kernels
  `dsa_index_fwd` / `dsa_index_bwd_dq` / `dsa_index_bwd_dk` of
  `ops/pallas/sparse_attention.py`, which keep a tile's heads in VMEM and walk
  the causal tiles alone; everywhere else the plain form below, query blocks
  under a `jax.checkpoint`, its backward JAX's own.  Neither form names a
  residual: the backward reads qi, ki and w as its caller's recompute makes them.
- `select_topk(scores, k)`: the mask `[B, S, S]` int8 of each query's
  `min(t + 1, k)` causal keys of largest score.  The k-th largest score of a
  row is found exactly, by 32 passes that fix one bit each of its float32
  pattern (a radix select: no sort, no indices), and every causal key that
  scores no less is kept; keys that TIE with the k-th are all kept (a sort
  would break the tie by position; float32 scores of 64 summed heads do not
  tie).
- `selected_attention(q, k, v, mask)`: `o[t] = softmax_{s in S_t}(q[t] . k[s])
  v[s]`, exactly the selected set, q arriving scaled; returns `(o, lse)`, the
  log-sum-exp over the selected keys `[B, S, H]` float32 for the target's
  sake.  A `kernel_pair.KernelPair` (`PAIR`): in a step lowered for TPU, at
  shapes they take, `ops/pallas/sparse_attention.py`'s kernels (the flash
  kernels' arithmetic with the mask's tile as one more operand); everywhere
  else the plain form below, query blocks under a `jax.checkpoint`, its
  backward JAX's own.  No gradient reaches `lse` or the mask: a caller that
  reads `lse` reads it under `stop_gradient`.
- `index_kl(scores, mask, target)`: `mean_t KL(p_t || softmax_{S_t}(I[t, .]))`
  with `p_t` the target `head_mean_probs(q, k, lse, mask)` gives (the mean
  over the heads of each selected pair's probability, one more `q k^T` from
  the saved `lse`), L1-normalised.  Differentiated in `scores` alone, by
  hand: its forward writes `(softmax_S(I) - p) / T`, named `KL_GRAD`, and its
  backward multiplies that by the cotangent, so a policy that keeps the name
  runs neither the scores nor the target again.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import kernel_pair
from ray_tpu.ops.attention import NEG_INF

QUERY_BLOCK = 256  # rows of the plain forms' [J or H, block, S] float32 intermediates
KL_GRAD = "dsa_kl_grad"  # `index_kl`'s one residual
MASK = "dsa_mask"  # what the layer names `select_topk`'s result


def _blocks(x, block: int):
    """[B, S, ...] -> [S / block, B, block, ...]."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // block, block, *x.shape[2:]), 1, 0)


def _positions(x):
    """`_blocks`' inverse."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _block_of(s: int) -> int:
    return QUERY_BLOCK if s % QUERY_BLOCK == 0 else s


def _plain_scores(ki, qi, w):
    s = qi.shape[1]
    block = _block_of(s)

    @jax.checkpoint
    def one(rows):
        q, weights, first = rows
        z = jnp.einsum("bqjd,bsd->bqjs", q, ki, preferred_element_type=jnp.float32)
        causal = first + jnp.arange(block)[:, None] >= jnp.arange(s)[None, :]
        return jnp.where(causal, jnp.sum(jax.nn.relu(z) * weights[..., None], axis=2), 0.0)

    return _positions(jax.lax.map(one, (_blocks(qi, block), _blocks(w.astype(jnp.float32), block), jnp.arange(0, s, block))))


def _plain_scores_backward(ki, qi, w, d_scores):
    return jax.vjp(_plain_scores, ki, qi, w)[1](d_scores)


def _index_forward(call, ki, qi, w):
    return (call(call.kernels.index_fwd, _plain_scores, ki, qi, w),)


def _index_backward(call, ki, qi, w, d_scores):
    """(dk, dq, dw) from the scores' cotangent."""
    return call(call.kernels.index_bwd, _plain_scores_backward, ki, qi, w, d_scores)


# ki first: the scaffold places the output as it places its first argument, and the scores have the key's rank
INDEX = kernel_pair.KernelPair(
    name="index_scores", scope=None, kernels="sparse_attention",
    takes=lambda kernels, ki, qi, w, chunk: kernels.index_supported(qi.shape),
    forward=_index_forward, backward=_index_backward,
)


def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array, mesh=None, batch_axes=None) -> jax.Array:
    """qi [B, S, J, D], ki [B, S, D], w [B, S, J] float32 -> I [B, S, S] float32, 0.0 above the diagonal.  mesh /
    batch_axes as the other pairs have them."""
    return kernel_pair.run(INDEX, ki, qi, w, mesh=mesh, batch_axes=batch_axes)


def _ordered(scores: jax.Array) -> jax.Array:
    """float32 -> uint32 in the same order (negative patterns flipped whole, the others in their sign bit)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_topk(scores: jax.Array, k: int) -> jax.Array:
    """scores [B, S, S] -> int8 [B, S, S]: 1 on each query's `min(t + 1, k)` causal keys of largest score."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    ordered = jnp.where(causal, _ordered(jax.lax.stop_gradient(scores)), jnp.uint32(0))  # a finite score's pattern is over 0
    wanted = jnp.minimum(jnp.arange(s) + 1, k)

    def fix_bit(i, kth):
        """`kth` holds the k-th largest pattern's bits above `31 - i`: the next bit is set iff enough patterns reach it."""
        with_bit = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum((ordered >= with_bit[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(reach >= wanted, with_bit, kth)

    kth = jax.lax.fori_loop(0, 32, fix_bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    return ((ordered >= kth[..., None]) & causal).astype(jnp.int8)


# -- the core over the selected keys ------------------------------------------------


def _plain_forward(q, k, v, mask):
    """(o [B, S, H, Dv] in q's dtype, lse [B, S, H] float32), a block of queries at a time."""

    @jax.checkpoint
    def one(block):
        qb, selected = block[0], block[1][:, None] != 0  # [B, 1, block, S]
        logits = jnp.where(selected, jnp.einsum("bqhd,bshd->bhqs", qb, k, preferred_element_type=jnp.float32), NEG_INF)
        top = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.where(selected, jnp.exp(logits - top), 0.0)
        total = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhqs,bshd->bqhd", (p / total).astype(v.dtype), v, preferred_element_type=jnp.float32)
        return out.astype(q.dtype), jnp.moveaxis((top + jnp.log(total))[..., 0], 1, 2)

    block = _block_of(q.shape[1])
    out, lse = jax.lax.map(one, (_blocks(q, block), _blocks(mask, block)))
    return _positions(out), _positions(lse)


def _forward(call, q, k, v, mask):
    """((o, lse [B, S, H, 1]: of q's rank, as the scaffold's one `out_specs` places every output), and the two again as
    the states the backward reads)."""
    out, lse = call(call.kernels.selected_fwd, _plain_forward, q, k, v, mask)
    return (out, lse[..., None]), out, lse


def _backward(call, q, k, v, mask, out, lse, d_out):
    """(dq, dk, dv, nothing for the mask) from o's cotangent; lse's is not read (module docstring)."""

    def plain(q, k, v, mask, out, lse, do):
        return jax.vjp(lambda q, k, v: _plain_forward(q, k, v, mask)[0], q, k, v)[1](do)

    return (*call(call.kernels.selected_bwd, plain, q, k, v, mask, out, lse, d_out[0]), None)


PAIR = kernel_pair.KernelPair(
    name="selected_attention", scope=None, kernels="sparse_attention",
    takes=lambda kernels, q, k, v, mask, chunk: kernels.supported(q.shape, v.shape),
    forward=_forward, backward=_backward,
)


def selected_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, mesh=None, batch_axes=None):
    """q, k [B, S, H, D] (q scaled), v [B, S, H, Dv], mask [B, S, S] (nonzero:
    selected; every query holds a key) -> (o [B, S, H, Dv], lse [B, S, H]
    float32).  mesh / batch_axes as the other pairs have them."""
    out, lse = kernel_pair.run(PAIR, q, k, v, mask, mesh=mesh, batch_axes=batch_axes)
    return out, lse[..., 0]


def _plain_mean_probs(q, k, lse, mask):
    def one(block):
        qb, lb, selected = block[0], block[1], block[2][:, None] != 0
        logits = jnp.einsum("bqhd,bshd->bhqs", qb, k, preferred_element_type=jnp.float32)
        p = jnp.where(selected, jnp.exp(logits - jnp.moveaxis(lb, 2, 1)[..., None]), 0.0)
        return jnp.mean(p, axis=1)

    block = _block_of(q.shape[1])
    return _positions(jax.lax.map(one, (_blocks(q, block), _blocks(lse, block), _blocks(mask, block))))


def head_mean_probs(q: jax.Array, k: jax.Array, lse: jax.Array, mask: jax.Array) -> jax.Array:
    """The indexer's target before its normalisation: the mean over the heads
    of each selected pair's probability, [B, S, S] float32, from `lse` as
    `selected_attention` returned it.  A constant of the step: nothing is
    differentiated through it."""
    q, k, lse = map(jax.lax.stop_gradient, (q, k, lse))
    kernels = PAIR.module()
    return kernel_pair.dispatch(kernels.supported(q.shape, q.shape), kernels.head_mean_probs, _plain_mean_probs, q, k, lse, mask)


@jax.custom_vjp
def index_kl(scores: jax.Array, mask: jax.Array, target: jax.Array) -> jax.Array:
    """`mean_t KL(p_t || softmax_{S_t}(scores[t, .]))`, p_t = target[t] / its sum: a float32 scalar (module docstring)."""
    return _index_kl(scores, mask, target)[0]


def _index_kl(scores, mask, target):
    selected = mask != 0
    p = target / jnp.sum(target, axis=-1, keepdims=True)
    logits = jnp.where(selected, scores, NEG_INF)
    log_q = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    rows = scores.shape[0] * scores.shape[1]
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0)) / rows
    return kl, jnp.where(selected, jnp.exp(log_q) - p, 0.0) / rows


def _index_kl_fwd(scores, mask, target):
    kl, grad = _index_kl(scores, mask, target)
    return kl, checkpoint_name(grad, KL_GRAD)


def _index_kl_bwd(grad, g):
    return g * grad, None, None  # the mask selects and the target is a constant: neither has a cotangent


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
