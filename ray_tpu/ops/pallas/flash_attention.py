"""Pallas TPU flash attention: forward + backward kernels.

Forward: online softmax, one (block_q, block_k) tile pair per grid step on a
3-D grid (batch, head, tile pair); accumulator/max/denominator live in VMEM
scratch carried across a query tile's pairs, so VMEM holds only the current
tiles (full-K/V-resident designs blow the ~16MB/core budget and a 128-tile
grid design starves the MXU at ~3 TFLOP/s on v5e).  The per-row logsumexp is
saved for the backward.

Backward: two pallas kernels with flash-style in-kernel recompute (no [S,S]
materialization, O(S) memory):
  - dq kernel, a query tile's key tiles innermost: recompute P from
    (q, k, lse), accumulate dq = scale * sum_kv P*(dP - delta) @ K in scratch.
  - dkv kernel, a key tile's query tiles innermost (so k/v blocks stay
    resident): accumulate dv = P^T @ dO and dk = (P*(dP - delta))^T @ q_scaled.

The innermost grid axis of all three walks a TABLE of the tile pairs the
call's mask lets through (`tile_pairs`), built once a (shape, mask) in numpy
when the call is traced and handed to the kernel as scalar-prefetched int32
arrays: for pair p of a head, the tile of the outer sequence (`own`: a query
tile in the forward and dq, a key tile in dkv), the tile of the other one, and
a kind word (first pair of its own tile: zero the accumulators; last: write
the output; crossed by the mask's edge: take the body with the mask).  The
index maps and the kernels read the table and nothing else, so every grid
step computes and a tile no query of the call sees is never visited, whatever
the mask: 136 of the 256 tile pairs of a head at 16,384 causal positions and
1024 x 1024 (`causal_steps_copying_pct`), the same table for every batch row
and head.  A call without a mask gets the full rectangle from the same
builder.

The masks (all static): `causal`; a `window` w, which keeps of the causal
keys the last w, the query's own position counted (query i sees keys
i - w + 1 .. i); a `block_diffusion` (`ops.attention.BlockDiffusion(block,
noisy)`), which takes the causal mask's place: the call's first `noisy` rows
are a sequence's noisy copy, the rest its clean copy (`noisy` 0: one copy,
block-causal; `rows / 2`: a training step's doubled rows), and a query sees
the noisy keys of its own block (a clean query none) and the clean keys up to
its block (a noisy query: before it).  Its tiles divide a COPY, so that none
lies across both (80 of a head's 256 forward tile pairs are visited at
2 x 8,192 rows, blocks of 4 and 1024-tiles).  An own tile's pairs are walked in
ascending order, under block diffusion the noisy run, then the clean run.

Only a tile the mask's edge crosses is masked.  A pair whose every query sees
every key (the table's word says so) runs the body without the mask's index
compares and select (and, in the forward, without the guard for a row whose
keys are all masked, which no such tile has), every other pair (the causal
diagonal, a window's two boundary tiles, the boundary tiles of the
block-diffusion mask) the body with them.  A select whose condition is true
everywhere returns its operand, so no output changes a bit; 120 of a head's 136
forward pairs at 16,384 causal positions are wholly visible, 56 of 80 at
2 x 8,192 block-diffusion rows (`tiles_unmasked_pct`).  A call without a mask
has no edge and traces the unmasked body alone.

Which form of a kernel runs is decided by the platform the enclosing program
is LOWERED for (`jax.lax.platform_dependent`), never by the process-global
default backend: a TPU lowering gets the Mosaic kernel, a CPU lowering gets
interpret mode (so tests on the virtual CPU mesh exercise the same kernel
bodies), and no other platform lowers at all.

GSPMD cannot partition a Mosaic call, so under a mesh the kernel runs inside
`shard_map` over the batch and head axes (`flash_attention_sharded`).

Reference parity note: the reference (Ray) has no attention kernels at all
(SURVEY.md §5.7) — this is TPU-native new work.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import ATTN_LSE, ATTN_OUT, BlockDiffusion, _repeat_kv
from ray_tpu.util import tracing

NEG_INF = -1e30
_LANES = 128  # VPU lane count: row-scalar scratch is kept lane-broadcast


def _pallas_call(kernel, *, name: str, **kwargs):
    """`pl.pallas_call` whose form follows the platform lowered for: the
    Mosaic kernel for TPU, interpret mode for CPU, an error anywhere else.

    `name` is how a profile finds the kernel: it names the Mosaic kernel and
    is a `named_scope` around the call, so it is in the op's metadata
    whatever the compiler calls the instruction (`branch_0_fun.N`, after
    `platform_dependent`'s branch)."""
    compiled = pl.pallas_call(kernel, name=name, **kwargs)
    interpreted = pl.pallas_call(kernel, name=name, interpret=True, **kwargs)

    def call(*args):
        with tracing.scope(name, kernel=True):
            return jax.lax.platform_dependent(*args, tpu=compiled, cpu=interpreted)

    return call


# (block_q, block_k, bwd_block_q, bwd_block_k) of a call that names none: `flash_attention`'s defaults, and what the
# two counters below size their tiles from (why these: `flash_attention`'s docstring)
DEFAULT_BLOCKS = (1024, 1024, 1024, 512)

# -- the masks inside a tile ------------------------------------------------------


def _window_mask(logits, q_start, k_start, window):
    """The causal mask, and the window's, inside a tile, from the rows' positions in the call."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    seen = qpos >= kpos
    if window is not None:
        seen = seen & (qpos - kpos < window)
    return jnp.where(seen, logits, NEG_INF)


def _diffusion_mask(logits, q_start, k_start, bd: BlockDiffusion):
    """The three rules inside a tile, from the rows' indices in the call
    (`ops.attention._seen_block_diffusion`), worked out on a column of query
    rows and a row of key rows and joined by two compares of integers (Mosaic
    selects no booleans): a clean key stands for its block, a noisy key for
    its block + `_NOISY`; a query sees the clean blocks up to its own (a noisy
    query: before its own) and the one noisy value that is its own block's (a
    clean query: none, -1)."""
    bq, bk = logits.shape
    qrow = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    krow = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    q_noisy, k_noisy = qrow < bd.noisy, krow < bd.noisy
    qb = _block_of(jnp.where(q_noisy, qrow, qrow - bd.noisy), bd.block)
    kb = _block_of(jnp.where(k_noisy, krow, krow - bd.noisy), bd.block)
    key = jnp.where(k_noisy, kb + _NOISY, kb)
    seen = (key <= jnp.where(q_noisy, qb - 1, qb)) | (key == jnp.where(q_noisy, qb + _NOISY, -1))
    return jnp.where(seen, logits, NEG_INF)


_NOISY = 1 << 30  # what tells a noisy key's block from every clean one's in `_diffusion_mask`


def _block_of(pos, block: int):
    """pos // block of non-negative int32 positions: a shift where the block length is a power of two."""
    if block & (block - 1) == 0:
        return jnp.right_shift(pos, block.bit_length() - 1)
    return jax.lax.div(pos, jnp.int32(block))


def _edge_mask(logits, q_start, k_start, window, diffusion, lead: Optional[int] = None):
    """The mask of a call that has one, inside a tile its edge crosses.  The
    causal mask and its window read the two starts through their difference
    alone: where every masked pair of the table has ONE difference (`lead`: a
    causal call on square tiles masks its diagonal, 0) the mask is a constant
    of the kernel, not index arithmetic on every masked step."""
    if diffusion is not None:
        return _diffusion_mask(logits, q_start, k_start, diffusion)
    if lead is not None:
        q_start, k_start = lead, 0
    return _window_mask(logits, q_start, k_start, window)


# -- the table of visible tile pairs ----------------------------------------------


_FIRST, _LAST, _MASKED = 1, 2, 4  # bits of a pair's kind word: see `TilePairs`


class TilePairs(NamedTuple):
    """The tile pairs one head's grid walks, in order (int32 [n_pairs] each):
    the tile of the outer sequence, the tile of the other one, and the kind
    word: `_FIRST` on the first pair of its own tile (the accumulators are
    zeroed), `_LAST` on the last (the output block is written), `_MASKED`
    where the mask's edge crosses the tile (some query of it misses some key
    of it), so that the pair takes the body with the mask."""
    own: np.ndarray
    other: np.ndarray
    kind: np.ndarray


def _seen_runs(sq: int, sk: int, causal: bool, window: Optional[int], bd: Optional[BlockDiffusion]):
    """The keys each of the call's `sq` query rows sees, as runs of key rows
    (first, one past the last), int arrays over the query rows: the mask's
    rules themselves, the one place the table takes them from."""
    row = np.arange(sq)
    if bd is not None:  # a noisy row: its block's noisy keys, the clean blocks before its own; a clean row: the clean blocks up to its own
        noisy = row < bd.noisy
        block = np.where(noisy, row, row - bd.noisy) // bd.block
        return [(np.where(noisy, block * bd.block, 0), np.where(noisy, (block + 1) * bd.block, 0)),
                (np.full(sq, bd.noisy), bd.noisy + np.where(noisy, block, block + 1) * bd.block)]
    if not causal:
        return [(np.zeros(sq, int), np.full(sq, sk))]
    return [(np.zeros(sq, int) if window is None else np.maximum(row - window + 1, 0), row + 1)]


@functools.lru_cache(maxsize=None)  # every layer of a kind asks for the same one
def tile_pairs(sq: int, sk: int, bq: int, bk: int, causal: bool, window: Optional[int], bd: Optional[BlockDiffusion],
               keys: bool) -> TilePairs:
    """The ONE enumeration of the tile pairs a call visits, from its static
    shapes and mask: with `keys` the query tiles are the outer sequence
    (forward, dq), without it the key tiles (dkv).  For each own tile, in
    order, the other sequence's tiles that hold a pair the mask lets through,
    ascending.  An own tile that sees nothing (the key tiles past the last
    query of a causal call with more keys than queries) keeps one pair, masked
    everywhere, since its output block has to be written (with zeros)."""
    first_key = np.arange(0, sk, bk)
    seen = sum(np.clip(np.minimum(hi[:, None], first_key + bk) - np.maximum(lo[:, None], first_key), 0, None)
               for lo, hi in _seen_runs(sq, sk, causal, window, bd))  # [sq, key tiles]: the keys of a tile that a row sees
    seen = seen.reshape(sq // bq, bq, sk // bk)
    some, whole = (seen > 0).any(axis=1), (seen == bk).all(axis=1)  # [query tiles, key tiles]
    if not keys:
        some, whole = some.T.copy(), whole.T
    some[~some.any(axis=1), -1] = True
    own, other = np.nonzero(some)  # row by row, ascending: under block diffusion the noisy run, then the clean run
    assert some.any(axis=1).all()  # an own tile without a pair would never write its output block
    turns = own[1:] != own[:-1]
    kind = _FIRST * np.r_[True, turns] + _LAST * np.r_[turns, True] + _MASKED * ~whole[own, other]
    pairs = TilePairs(own.astype(np.int32), other.astype(np.int32), kind.astype(np.int32))
    for column in pairs:
        column.setflags(write=False)  # the cache hands every caller the same arrays
    return pairs


def _diffusion_blocks(rows: int, bd: BlockDiffusion, blocks):
    """A block-diffusion call's tiles: each the whole call where the call is
    no longer than the request, else the request fitted to a COPY's rows
    (`_fit_block`), so that no tile lies across the two copies."""
    copy = rows - bd.noisy
    return tuple(rows if rows <= b else _fit_block(copy, b) for b in blocks)


def _forward_tiles(rows: int, d: int, dv: int, window: Optional[int] = None, bd: Optional[BlockDiffusion] = None):
    """(block_q, block_k) of the FORWARD call over `rows` rows at these head
    sizes, as `flash_attention` picks them from its defaults (`_window_blocks`,
    `_head_blocks`, then `_fit_block` or `_diffusion_blocks`): what the step
    counters size their tiles from; None in the place of a tile nothing divides."""
    blocks = DEFAULT_BLOCKS if window is None else _window_blocks(window, DEFAULT_BLOCKS)
    blocks = _head_blocks(d, dv, blocks)[:2]
    return _diffusion_blocks(rows, bd, blocks) if bd is not None else tuple(_fit_block(rows, b) for b in blocks)


def diffusion_mask_fill_pct(seq: int, block: int, d: int, dv: int) -> Optional[float]:
    """The pairs the training mask of `seq` positions in blocks of `block`
    holds (seq^2 + seq * block over the doubled rows: the clean copy's
    block-causal half-square, the noisy copy's diagonal blocks and its view of
    the clean blocks before) as % of the pairs of the tiles the FORWARD kernel
    visits, from the block sizes in use; None at a length no tile divides."""
    bd = BlockDiffusion(block, seq)
    bq, bk = _forward_tiles(2 * seq, d, dv, bd=bd)
    if bq is None or bk is None:
        return None
    visited = len(tile_pairs(2 * seq, 2 * seq, bq, bk, False, None, bd, True).own)
    return 100.0 * (seq * seq + seq * block) / (visited * bq * bk)


def window_tiles_visited_pct(seq: int, window: int) -> Optional[float]:
    """Area of the key tiles the windowed FORWARD kernel visits, as % of what
    the causal call visits at ITS tiles, from the block sizes in use
    (`flash_attention`'s defaults and `_window_blocks`); None at a length no
    tile divides (the kernels do not run there)."""
    full = _fit_block(seq, DEFAULT_BLOCKS[0])
    if full is None:
        return None
    causal = full * full * len(tile_pairs(seq, seq, full, full, True, None, None, True).own)
    bq, bk, _, _ = (_fit_block(seq, b) for b in _window_blocks(window, DEFAULT_BLOCKS))
    visited = len(tile_pairs(seq, seq, bq, bk, True, window, None, True).own)
    return 100.0 * visited * bq * bk / causal


def causal_steps_copying_pct(seq: int, block_q: int, block_k: int, *, keys: bool) -> float:
    """Share (%) of the rectangle of a head's tile pairs that are grid steps
    at all, in a causal call without a window at these tiles: the pairs of the
    table the kernel is handed (`tile_pairs`) over the n_q x n_k steps of the
    rectangular grid it replaces.  With `keys` the outer axis is the query
    tiles (forward, dq), without it the key tiles (dkv).  Until PR 70 the
    steps of that rectangle that made the pipeline COPY (an off step named its
    neighbour's block), which were the same ones: 136 of 256 at 16,384
    positions and 1024 x 1024, 272 of 512 at 1024 x 512, 100 at one tile a
    head."""
    pairs = len(tile_pairs(seq, seq, block_q, block_k, True, None, None, keys).own)
    return 100.0 * pairs / ((seq // block_q) * (seq // block_k))


def run_steps_unmasked(rows: int, bq: int, bk: int, window: Optional[int], bd: Optional[BlockDiffusion]) -> Tuple[int, int]:
    """(the pairs that take the body without the mask, all pairs) of one head
    in a kernel with these tiles, under the causal mask, its `window` or the
    block-diffusion mask `bd`, from the kind words of the table the kernels
    are handed.  The pairs are the same whichever axis is the outer one."""
    kind = tile_pairs(rows, rows, bq, bk, bd is None, window, bd, True).kind
    return int(np.sum(kind & _MASKED == 0)), len(kind)


def tiles_unmasked_pct(seq: int, d: int, dv: int, *, window: Optional[int] = None,
                       diffusion_block: Optional[int] = None) -> Optional[float]:
    """Share (%) of a head's FORWARD tile pairs that take the body without the
    mask (`run_steps_unmasked`) at the tiles in use (`_forward_tiles`): of a
    causal call over `seq` positions, of one under `window`, or of a training
    step's block-diffusion call over 2 x `seq` rows in blocks of
    `diffusion_block`.  120 of 136 at 16,384 causal positions, 56 of 80 at
    2 x 8,192 rows in blocks of 4, none under a window no wider than a tile
    (both visited tiles are boundary tiles) or at one tile a head; None at a
    length no tile divides."""
    rows, bd = (seq, None) if diffusion_block is None else (2 * seq, BlockDiffusion(diffusion_block, seq))
    bq, bk = _forward_tiles(rows, d, dv, window, bd)
    if bq is None or bk is None:
        return None
    clear, run = run_steps_unmasked(rows, bq, bk, window, bd)
    return 100.0 * clear / run


def causal_forward_tiles(seq: int, d: int, dv: int) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) of the causal FORWARD call at this length and these
    head sizes, from the block sizes in use (`flash_attention`'s defaults,
    `_head_blocks`, `_fit_block`); None at a length no tile divides."""
    tiles = _forward_tiles(seq, d, dv)
    return None if None in tiles else tiles


def _window_blocks(window: int, blocks):
    """A windowed call's tiles: none larger than the window (rounded down to
    128 lanes, at least 128).  At 1024 x 1024 a window of 512 visits two key
    tiles a query tile, 2,048 keys a query where 512 are needed; at 512 x 512
    two tiles are 1,024.  Smaller tiles waste less and feed the MXU less
    (PERF.md section 7)."""
    cap = max(128, window // 128 * 128)
    return tuple(min(b, cap) for b in blocks)


_FWD_FULL_TILE_HEADS = 448  # d + dv up to which the forward's 1024 x 1024 tile fits the scoped VMEM


def _head_blocks(d: int, dv: int, blocks):
    """Tiles by head size, the ONE place they follow from it.  The forward
    kernel at 1024 x 1024 holds, beside its float32 scores (7.5 MB), the
    tiles of q, k, v and the output twice in their own dtype, q, k and v once
    more in float32 and the float32 accumulator: 16 KB a unit of `d + dv`,
    13.5 MB at heads of 192 / 128, 16.5 MB at 256 / 256 against the 16 MB a
    kernel may scope (libtpu refuses it; PERF.md section 6, PR 54).  Heads
    whose two sizes sum over 448 halve the forward's key tile; the backward's
    1024 x 512 fits them as it is.  Heads of 64, 128 and 192 / 128 keep the
    tiles they had.  (Since PR 70 a causal forward's masked tiles hold no
    int32 position arrays, `_edge_mask`'s `lead`, and 256 / 256 compiles at
    1024 x 1024; by what margin, and whether it is faster, is a sweep's to
    say: `scripts/flash_tiles_check.py`.)"""
    block_q, block_k, bwd_block_q, bwd_block_k = blocks
    if d + dv > _FWD_FULL_TILE_HEADS:
        block_k = min(block_k, 512)
    return block_q, block_k, bwd_block_q, bwd_block_k


# -- walking the table ---------------------------------------------------------


def _pair(own_ref, other_ref, kind_ref, bq: int, bk: int, *, keys: bool):
    """(first query row, first key row, kind word) of this grid step's pair."""
    p = pl.program_id(2)
    q_tile, k_tile = (own_ref[p], other_ref[p]) if keys else (other_ref[p], own_ref[p])
    return q_tile * bq, k_tile * bk, kind_ref[p]


def _common(pairs: TilePairs, bq: int, bk: int, *, keys: bool) -> Tuple[int, int, Optional[int]]:
    """What all the words of a table agree on, static when the call is traced
    (`_walk`, `_edge_mask`): the bits every kind word has, the bits some word
    has, and `q_start - k_start` of the masked pairs where it is one value."""
    q_tile, k_tile = (pairs.own, pairs.other) if keys else (pairs.other, pairs.own)
    leads = np.unique((q_tile * bq - k_tile * bk)[pairs.kind & _MASKED != 0])
    return int(np.bitwise_and.reduce(pairs.kind)), int(np.bitwise_or.reduce(pairs.kind)), int(leads[0]) if len(leads) == 1 else None


def _walk(kind, common, init, step, finish):
    """One grid step: `init` on the first pair of an own tile, `step(masked)`
    by the table's word, `finish` on the last.  Every step computes.  What all
    the table's words agree on (`_common`) is decided when the call is traced,
    as the grids this replaces decided it: a table of one pair a head
    (`mistral7b-1chip.seq1k`) has no branch at all, a call without a mask
    traces the unmasked body alone, one whose every pair is a boundary tile (a
    window no wider than a tile) the masked body alone."""
    every, some, _ = common

    def on(bit: int, fn, *, unset: bool = False):
        always, sometimes = (not some & bit, not every & bit) if unset else (every & bit, some & bit)
        if always:
            fn()
        elif sometimes:
            pl.when(kind & bit == 0 if unset else kind & bit != 0)(fn)

    on(_FIRST, init)
    on(_MASKED, functools.partial(step, False), unset=True)
    on(_MASKED, functools.partial(step, True))
    on(_LAST, finish)


def _tile_spec(rows: int, width: int, *, own: bool):
    """A [rows, width] block of one head at the tile the table names for the
    grid step: the own (outer) sequence's or the other's."""
    return pl.BlockSpec((1, 1, rows, width), lambda bi, hi, p, own_t, other_t, kind: (bi, hi, (own_t if own else other_t)[p], 0))


def _pairs_call(kernel, name: str, b: int, h: int, call: tuple, *, keys: bool, **specs):
    """The kernel on the grid (batch, head, pair of the table), the table of
    `call` (`tile_pairs`' arguments but the last, `keys`) scalar-prefetched and
    what its words agree on (`_common`) the kernel's static `common`."""
    pairs = tile_pairs(*call, keys)
    kernel = functools.partial(kernel, common=_common(pairs, *call[2:4], keys=keys))
    run = _pallas_call(kernel, name=name, out_shape=specs.pop("out_shape"), grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pairs), grid=(b, h, len(pairs.own)), **specs))
    return functools.partial(run, *map(jnp.asarray, pairs))


# -- forward ---------------------------------------------------------------


def _fwd_kernel(
    own_ref, other_ref, kind_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, common, window: Optional[int] = None, diffusion: Optional[BlockDiffusion] = None,
):
    # Blocks: q [1, 1, bq, D]; k [1, 1, bk, D]; v [1, 1, bk, Dv]; o [1, 1, bq, Dv];
    # lse [1, 1, bq, 1].  Scratch (carried across a query tile's pairs): acc [bq, Dv] f32,
    # m/l [bq, LANES] f32 (lane-broadcast row scalars).
    q_start, k_start, kind = _pair(own_ref, other_ref, kind_ref, q_ref.shape[2], k_ref.shape[2], keys=True)

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, Dv]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if masked:
            logits = _edge_mask(logits, q_start, k_start, window, diffusion, common[2])
        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(logits - m_new)
        if masked:  # a row whose keys are all masked so far has m_new = NEG_INF: its masked entries are 0, not exp(0)
            p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.maximum(l, 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l_safe)

    _walk(kind, common, _init, _step, _finish)


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, window=None, diffusion=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]  # q and k share one head size, v and the output another
    # Kernels work in [B, H, S, D].
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    out, lse = _pairs_call(
        functools.partial(_fwd_kernel, scale=scale, window=window, diffusion=diffusion), "flash_fwd", b, h,
        (sq, sk, block_q, block_k, causal, window, diffusion), keys=True,
        in_specs=[_tile_spec(block_q, d, own=True), _tile_spec(block_k, d, own=False), _tile_spec(block_k, dv, own=False)],
        out_specs=[_tile_spec(block_q, dv, own=True), _tile_spec(block_q, 1, own=True)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# -- backward --------------------------------------------------------------


def _bwd_dq_kernel(
    own_ref, other_ref, kind_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale: float, common, window: Optional[int] = None, diffusion: Optional[BlockDiffusion] = None,
):
    # q/dq [1, 1, bq, D]; k [1, 1, bk, D]; v [1, 1, bk, Dv]; do [1, 1, bq, Dv];
    # lse/delta [1, 1, bq, 1].
    q_start, k_start, kind = _pair(own_ref, other_ref, kind_ref, q_ref.shape[2], k_ref.shape[2], keys=True)

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32) * scale  # pre-scaled
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]  # [bq, 1]
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if masked:
            logits = _edge_mask(logits, q_start, k_start, window, diffusion, common[2])
        p = jnp.exp(logits - lse)  # masked -> exp(-inf) = 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = p * (dp - delta)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)

    _walk(kind, common, _init, _step, _finish)


def _bwd_dkv_kernel(
    own_ref, other_ref, kind_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, scale: float, common, window: Optional[int] = None, diffusion: Optional[BlockDiffusion] = None,
):
    # A key tile's query tiles innermost, so k/v blocks stay resident.
    # k/dk [1, 1, bk, D]; v/dv [1, 1, bk, Dv]; q [1, 1, bq, D]; do [1, 1, bq, Dv];
    # lse/delta [1, 1, bq, 1].
    q_start, k_start, kind = _pair(own_ref, other_ref, kind_ref, q_ref.shape[2], k_ref.shape[2], keys=False)

    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if masked:
            logits = _edge_mask(logits, q_start, k_start, window, diffusion, common[2])
        p = jnp.exp(logits - lse)
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, Dv]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # q is pre-scaled, so this accumulates the true dk.
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)

    _walk(kind, common, _init, _step, _finish)


def _flash_bwd(q, k, v, o, lse, do, *, causal, scale, block_q, block_k, window=None, diffusion=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, computed outside.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)[..., None]  # [B, H, Sq, 1]
    masks = dict(scale=scale, window=window, diffusion=diffusion)

    def operands(queries_own: bool):  # q, k, v, do, lse, delta: the query tile is the own one in dq, the other one in dkv
        of_q, of_k = functools.partial(_tile_spec, block_q, own=queries_own), functools.partial(_tile_spec, block_k, own=not queries_own)
        return [of_q(d), of_k(d), of_k(dv), of_q(dv), of_q(1), of_q(1)]

    call = (sq, sk, block_q, block_k, causal, window, diffusion)
    dq = _pairs_call(
        functools.partial(_bwd_dq_kernel, **masks), "flash_bwd_dq", b, h, call, keys=True,
        in_specs=operands(True),
        out_specs=_tile_spec(block_q, d, own=True),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(qt, kt, vt, dot, lse, delta)

    dk, dv = _pairs_call(
        functools.partial(_bwd_dkv_kernel, **masks), "flash_bwd_dkv", b, h, call, keys=False,
        in_specs=operands(False),
        out_specs=[_tile_spec(block_k, d, own=True), _tile_spec(block_k, dv, own=True)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
    )(qt, kt, vt, dot, lse, delta)
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


# -- custom_vjp wiring -----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q, bwd_block_k, window, diffusion=None):
    out, _ = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k, window=window, diffusion=diffusion
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q, bwd_block_k, window, diffusion):
    out, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k, window=window, diffusion=diffusion
    )
    # Named so that a remat policy can keep them (ops/attention.py); any
    # other policy runs this forward again in the backward pass.  The
    # log-sum-exp is saved lane-dense, [B, H, S]: as the kernels'
    # [B, H, S, 1] operand its rows pad to 128 lanes.
    out = checkpoint_name(out, ATTN_OUT)
    lse = checkpoint_name(lse[..., 0], ATTN_LSE)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k, window, diffusion, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(
        q, k, v, out, lse[..., None], g,
        causal=causal, scale=scale, block_q=bwd_block_q, block_k=bwd_block_k, window=window, diffusion=diffusion,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCKS[0],
    block_k: int = DEFAULT_BLOCKS[1],
    bwd_block_q: int = DEFAULT_BLOCKS[2],
    bwd_block_k: int = DEFAULT_BLOCKS[3],
    window: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> jax.Array:
    """Flash attention, [B, S, H, D] layout, GQA via repeated kv heads.  q and
    k share one head size, v and the output may have another (latent
    attention's 192 against 128): the kernels take both, and at equal sizes
    they are the calls they were.

    Forward tiles default larger than backward: the bwd kernels hold four
    [bq, bk] f32 intermediates (logits/p/dp/ds) at once, so 1024x1024 there
    would exceed the ~16MB VMEM scoped budget.

    Which tile pairs are grid steps: the ones the mask lets through and no
    other (`tile_pairs`, a table the kernels are handed): a causal call walks
    the pairs on and under the diagonal, 136 of the 256 of a head's forward
    at 16,384 positions, 272 of the 512 of each backward kernel
    (`causal_steps_copying_pct`); a call without a mask walks them all.

    `window` (static; needs `causal` and equal sequence lengths): query i
    sees keys i - window + 1 .. i.  A windowed call gets tiles no larger
    than its window (`_window_blocks`): 512 x 512 in all three kernels at a
    window of 512, two key tiles a query tile.

    `block_diffusion` (static; excludes `window`, takes `causal`'s place;
    equal sequence lengths): the mask of a block-diffusion model over one copy
    of a sequence or over its noisy and its clean copy (module docstring); its
    tiles divide a copy (`_diffusion_blocks`)."""
    if block_diffusion is not None:
        if window is not None:
            raise ValueError("flash_attention: block_diffusion takes the causal mask's place and has no window")
        block_diffusion.check(q.shape[1], k.shape[1])
        causal = False  # the table of tile pairs and the mask inside a tile are the block-diffusion ones
    if window is not None:
        if not causal or q.shape[1] != k.shape[1] or window < 1:
            raise ValueError("flash_attention: a window needs causal=True, equal sequence lengths and window >= 1")
        block_q, block_k, bwd_block_q, bwd_block_k = _window_blocks(
            window, (block_q, block_k, bwd_block_q, bwd_block_k))
    block_q, block_k, bwd_block_q, bwd_block_k = _head_blocks(
        q.shape[-1], v.shape[-1], (block_q, block_k, bwd_block_q, bwd_block_k))
    h = q.shape[2]
    if k.shape[2] != h:
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # Shrink each tile to the largest 128-multiple divisor of its sequence
    # length (tail tiles would be silently dropped by the grid floor
    # division).  A length no tile divides is the caller's to route
    # elsewhere (ops.attention.dot_product_attention does): answering with
    # a different algorithm here would hide that the kernel did not run.
    if block_diffusion is not None:
        blocks = _diffusion_blocks(q.shape[1], block_diffusion, (block_q, block_k, bwd_block_q, bwd_block_k))
    else:
        blocks = (
            _fit_block(q.shape[1], block_q),
            _fit_block(k.shape[1], block_k),
            _fit_block(q.shape[1], bwd_block_q),
            _fit_block(k.shape[1], bwd_block_k),
        )
    if None in blocks:
        raise ValueError(
            f"flash_attention: no tile divides seq lengths q={q.shape[1]} "
            f"k={k.shape[1]} (requested blocks {block_q}/{block_k}, bwd "
            f"{bwd_block_q}/{bwd_block_k})"
        )
    return _flash(q, k, v, causal, scale, *blocks, window, block_diffusion)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> jax.Array:
    """flash_attention on global [B, S, H, D] arrays sharded over a mesh.

    Attention is independent per batch row and per head, so each device
    runs the kernel on its own [B/b, S, H/h, D] block with no collective.
    Specs are shape-fitted like ring_attention_sharded's: a dim the axes
    do not divide runs replicated.  The caller passes head_axis=None when
    the kv heads do not divide it (GQA), so q and k/v stay aligned."""
    from ray_tpu.parallel.sharding import _fit_spec

    spec = P(batch_axes, None, head_axis, None)
    qspec, kspec = _fit_spec(q.shape, spec, mesh), _fit_spec(k.shape, spec, mesh)
    body = functools.partial(flash_attention, causal=causal, scale=scale, window=window, block_diffusion=block_diffusion)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qspec, kspec, kspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v)


def _fit_block(s: int, requested: int) -> Optional[int]:
    """Tile size that divides s: the request itself if it divides, else the
    largest 128-multiple <= requested that does; None if neither exists.  A
    windowed call's request is already cut to its window (`_window_blocks`),
    so that a tile outside the window is one the grid can leave out."""
    requested = min(requested, s)
    if s % requested == 0:
        return requested
    for b in range((requested // 128) * 128, 127, -128):
        if s % b == 0:
            return b
    return None
