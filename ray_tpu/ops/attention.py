"""Attention ops: reference jnp, blockwise (flash-semantics) scan, dispatcher.

No counterpart exists in the reference — it delegates all math to torch
(SURVEY.md §5.7: no attention/sequence-parallel code anywhere in python/ray).
Built TPU-first: the blockwise form keeps the working set in VMEM-sized tiles
and is what the pallas kernel (ops/pallas/flash_attention.py) and ring
attention (ops/ring_attention.py) are built from.

Shapes follow [batch, seq, heads, head_dim] throughout.  GQA is expressed by
n_kv_heads < n_heads; kv heads are repeated on the fly.  q and k share one
head size; v, and with it the output, may have another.

A `window` w (None = none; needs `causal`) keeps of the causal keys the last
w, the query's own position counted: query i sees keys i - w + 1 .. i.  All
three forms take it: `reference` and `blockwise` as a second term of their
masks, the flash kernels as tiles never visited (ops/pallas/flash_attention.py).
Ring attention does not (models/mixers/attention.py refuses the pairing by name).

A `block_diffusion` (`BlockDiffusion(block, noisy)`; None = none; REPLACES the
causal mask and excludes a window; equal sequence lengths) is the mask of a
block-diffusion model (BD3-LMs, arXiv:2503.09573): positions are cut into
blocks of `block`, b(i) = i // block.  The first `noisy` rows of the call are
the noisy copy x_t of a sequence and the rest its clean copy x_0, both at
positions 0.. (a row's position is its index in its own copy):

    noisy query -> noisy key  iff b(j) == b(i)    (two-sided inside its block)
    noisy query -> clean key  iff b(j) <  b(i)    (the finished blocks before it)
    clean query -> clean key  iff b(j) <= b(i)    (block-causal)
    clean query -> noisy key  never

`noisy=0` is the plain forward of such a model, block-causal over one copy;
`noisy = rows / 2` is its training step over `[x_t ‖ x_0]`.  All three forms
take it; the flash kernels leave out the tile pairs no query sees.  The ring
does not (refused by name in the mixer's `placement`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import kernel_pair

NEG_INF = -1e30

# `checkpoint_name`s of what attention's backward needs besides q, k and v.
# Each form names them where they are residuals of ITS backward, because a
# name outside a `custom_vjp` does not reach the residual inside: the XLA
# forms and ring attention name their result, the flash kernel names the
# `out` and the log-sum-exp of its forward rule.  A remat policy that keeps
# the attention output keeps both names (models/transformer.py `_remat_policy`), or the
# backward re-runs the whole forward for the one it lacks.
ATTN_OUT = "attn"
ATTN_LSE = "attn_lse"


class BlockDiffusion(NamedTuple):
    """The block-diffusion mask of a call (module docstring): the block
    length, and how many of the call's first rows are the noisy copy."""
    block: int
    noisy: int = 0

    def check(self, sq: int, sk: int) -> None:
        clean = sq - self.noisy
        if sq != sk or self.block < 1 or 2 * self.noisy not in (0, sq) or clean % self.block:
            raise ValueError(f"{self} needs equal sequence lengths (got {sq}, {sk}), the noisy rows none or the first "
                             f"half of them, and a block length that divides a copy's {clean} positions")


def _seen_block_diffusion(qrow: jax.Array, krow: jax.Array, bd: BlockDiffusion) -> jax.Array:
    """bool [q, k] from the rows' indices in the call: the three rules of the
    module docstring (a clean query sees no noisy key)."""
    q_noisy, k_noisy = qrow < bd.noisy, krow < bd.noisy
    qb = (jnp.where(q_noisy, qrow, qrow - bd.noisy) // bd.block)[:, None]
    kb = (jnp.where(k_noisy, krow, krow - bd.noisy) // bd.block)[None, :]
    q_noisy, k_noisy = q_noisy[:, None], k_noisy[None, :]
    return jnp.where(k_noisy, q_noisy & (kb == qb), jnp.where(q_noisy, kb < qb, kb <= qb))


def _seen(qpos: jax.Array, kpos: jax.Array, window: Optional[int]) -> jax.Array:
    """bool [q, k]: the keys a causal query sees, the last `window` of them
    (its own position counted) when there is one."""
    seen = qpos[:, None] >= kpos[None, :]
    if window is not None:
        seen = seen & (qpos[:, None] - kpos[None, :] < window)
    return seen


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, H, D] by repeating groups (GQA)."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    assert n_heads % n_kv == 0
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    window: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> jax.Array:
    """O(S^2) materialized-scores attention. Ground truth for tests.

    q_offset: absolute position of q[0] relative to k[0] (decode/ring steps).
    block_diffusion: the module docstring's mask, in `causal`'s place.
    """
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if block_diffusion is not None:
        block_diffusion.check(sq, k.shape[1])
        logits = jnp.where(_seen_block_diffusion(jnp.arange(sq), jnp.arange(sq), block_diffusion), logits, NEG_INF)
    elif causal:
        sk = k.shape[1]
        logits = jnp.where(_seen(jnp.arange(sq) + q_offset, jnp.arange(sk), window), logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_size: int = 512,
    q_offset: int = 0,
    window: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> jax.Array:
    """Flash-attention semantics in pure JAX: scan over KV blocks with an
    online softmax, never materializing the [S, S] score matrix.  XLA keeps
    the per-block compute on the MXU; memory is O(S * block).

    Also the inner step of ring attention, where successive KV blocks arrive
    over ICI (ops/ring_attention.py).
    """
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    sk, dv = k.shape[1], v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    if block_diffusion is not None:
        block_diffusion.check(sq, sk)
    if sk % block_size != 0:
        block_size = sk  # fall back to one block rather than pad
    n_blocks = sk // block_size

    qf = (q * scale).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_blocks = kf.reshape(b, n_blocks, block_size, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = vf.reshape(b, n_blocks, block_size, h, dv).transpose(1, 0, 2, 3, 4)

    qpos = jnp.arange(sq) + q_offset

    def step(carry, blk):
        acc, m, l = carry
        kb, vb, kpos = blk
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)
        if block_diffusion is not None:
            logits = jnp.where(_seen_block_diffusion(qpos, kpos, block_diffusion)[None, None], logits, NEG_INF)
        elif causal:
            logits = jnp.where(_seen(qpos, kpos, window)[None, None], logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # Guard: a fully-masked row has logits == m_new == NEG_INF; exp(0)=1
        # would poison l. Force those probabilities to 0.
        p = jnp.where(
            logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m_new[..., None])
        )
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (acc_new, m_new, l_new), None

    kpos_blocks = (jnp.arange(sk).reshape(n_blocks, block_size))
    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (k_blocks, v_blocks, kpos_blocks))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_size: int = 512,
    impl: Optional[str] = None,
    mesh=None,
    batch_axes=None,
    head_axis: Optional[str] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> jax.Array:
    """Dispatching attention entry point used by models/.

    impl: None (auto) | "reference" | "blockwise" | "pallas".
    Auto gives a program LOWERED FOR TPU the pallas flash kernel when the
    shapes are tile-aligned (sequence lengths a multiple of 128, both head
    sizes, q/k's and v's, a multiple of 64); every other lowering, and every other shape, gets the
    XLA forms (blockwise scan beyond block_size, else reference).
    The choice is `kernel_pair.dispatch`'s, so it follows the platform a step
    is compiled for, not the process's default backend.

    window, block_diffusion: see the module docstring; every form takes
    them, one or the other.

    mesh / batch_axes / head_axis say how q, k, v are sharded.  GSPMD
    partitions the XLA forms by itself; a Mosaic kernel it cannot, so with
    a mesh the kernel runs under shard_map over those axes.
    """

    if window is not None and (not causal or block_diffusion is not None):
        raise ValueError("a window is the last keys of a CAUSAL mask: it needs causal=True, and no block_diffusion "
                         "(whose mask takes the causal one's place)")
    masked = {} if window is None else {"window": window}
    if block_diffusion is not None:  # handed on as a window is
        masked = {"block_diffusion": block_diffusion}

    def reference(q, k, v):
        out = reference_attention(q, k, v, causal=causal, scale=scale, **masked)
        return checkpoint_name(out, ATTN_OUT)

    def blockwise(q, k, v):
        out = blockwise_attention(
            q, k, v, causal=causal, scale=scale, block_size=block_size, **masked
        )
        return checkpoint_name(out, ATTN_OUT)

    def pallas(q, k, v):
        from ray_tpu.ops.pallas import flash_attention as fa

        if mesh is None:
            return fa.flash_attention(q, k, v, causal=causal, scale=scale, **masked)
        return fa.flash_attention_sharded(
            q, k, v, mesh, batch_axes=batch_axes, head_axis=head_axis,
            causal=causal, scale=scale, **masked,
        )

    if impl is None:
        xla = blockwise if q.shape[1] > block_size else reference
        takes = q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0 and q.shape[-1] % 64 == 0 and v.shape[-1] % 64 == 0
        return kernel_pair.dispatch(takes, pallas, xla, q, k, v)
    forms = {"reference": reference, "blockwise": blockwise, "pallas": pallas}
    if impl not in forms:
        raise ValueError(f"unknown attention impl {impl!r}")
    return forms[impl](q, k, v)
