"""Pallas TPU flash attention: forward + backward kernels.

Forward: online softmax, one (block_q, block_k) tile pair per grid step on a
4-D grid (batch, head, q_tile, kv_tile); accumulator/max/denominator live in
VMEM scratch carried across the innermost kv dimension, so VMEM holds only
the current tiles (full-K/V-resident designs blow the ~16MB/core budget and
a 128-tile grid design starves the MXU at ~3 TFLOP/s on v5e).  The per-row
logsumexp is saved for the backward.

Backward: two pallas kernels with flash-style in-kernel recompute (no [S,S]
materialization, O(S) memory):
  - dq kernel, grid (b, h, q_tile, kv_tile): recompute P from (q, k, lse),
    accumulate dq = scale * sum_kv P*(dP - delta) @ K in scratch.
  - dkv kernel, grid (b, h, kv_tile, q_tile): accumulate dv = P^T @ dO and
    dk = (P*(dP - delta))^T @ q_scaled in scratch.

Causal masking skips fully-masked tile pairs via pl.when predication.  A
skipped step computes nothing, but the grid of a causal call without a window
is a rectangle and Pallas's pipeline copies every block whose index differs
from the step before, run or not: under the map `j -> j` an off step of the
forward and of dq still copied a key and a value tile (512 KB at 1024 x 128
bf16 twice, 256 KB at the backward's 512), one of dkv a query and a dO tile
and the log-sum-exp and delta blocks, whose `[.., 1024, 1]` float32 rows pad
to 128 lanes (1.5 MB in all), and at 16,384 positions 120 of a head's 256
forward steps and 240 of its 512 backward steps are off.  So the index map
of the inner axis (`_inner_tile`) stays on the diagonal's tile through the
off steps: the last visible key tile where they come last (forward, dq), the
first visible query tile where they come first (dkv).  An off step then
names the block its neighbour named and nothing is copied for it; the run
steps, their order and the bodies are the ones they were
(`causal_steps_copying_pct` counts the steps that still copy).

A `window` w (static; None = none) keeps of the causal keys the last w, the
query's own position counted: query i sees keys i - w + 1 .. i.  A windowed
call's grids do not span the other sequence: the innermost dimension counts
only the tiles a tile of the outer one can see (`_visible`), its index maps
start at the first of them, the two boundary tiles are masked inside and a
step past the last visible tile is predicated off, in all three kernels.  At
`window=None` nothing of this is traced: grids, index maps and kernel bodies
are the ones they were.

A `block_diffusion` (`ops.attention.BlockDiffusion(block, noisy)`; static;
None = none) puts the block-diffusion mask in the causal one's place: the
call's first `noisy` rows are a sequence's noisy copy, the rest its clean copy
(`noisy` 0: one copy, block-causal; `rows / 2`: a training step's doubled
rows), and of the four quadrants of the doubled rows a query tile sees a
block-diagonal band of the noisy keys and the clean keys up to its own blocks
(a clean tile the second alone).  Tiles divide a COPY, so that none lies
across both, and the grids count, as a windowed call's do, only the tiles a
tile of the outer axis can see: two runs of them (`_diffusion_ranges`), walked
one after the other, the index held on the last visible tile through the
steps past it, which the kernels predicate off (80 of a head's 256 forward
tile pairs are visited at 2 x 8,192 rows, blocks of 4 and 1024-tiles, 9 inner
steps where 16 would span the rows).  The two boundary kinds of tile are
masked inside from the rows' indices alone.  At `block_diffusion=None` nothing
of this is traced either.

Only a tile the mask's edge crosses is masked.  A run step of each kernel
decides from its own grid indices (`_wholly_visible`) whether every query of
its tile sees every key of it: such a step runs the body without the mask's
index compares and select (and, in the forward, without the guard for a row
whose keys are all masked, which no such tile has), every other run step (the
causal diagonal, a window's two boundary tiles, the boundary tiles of the
block-diffusion mask) the body with them.  A select whose condition is true
everywhere returns its operand, so no output changes a bit; 120 of a head's 136
forward run steps at 16,384 causal positions are wholly visible, 56 of 80 at
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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
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

# -- windows ---------------------------------------------------------------


def _lower(a, b):
    """min of two tile indices, traced grid indices or Python ints."""
    return jnp.minimum(a, b) if isinstance(a, jax.Array) or isinstance(b, jax.Array) else min(a, b)


def _higher(a, b):
    return jnp.maximum(a, b) if isinstance(a, jax.Array) or isinstance(b, jax.Array) else max(a, b)


def _first_visible(i, own: int, other: int, window: int, *, keys: bool):
    """First tile of the OTHER sequence that tile `i` (of `own` positions)
    can see under a causal window: with `keys`, i is a query tile and the
    answer a key tile (of `other` positions); without, the reverse.  `i` may
    be a traced grid index or a Python int."""
    if keys:  # the first query's oldest key, i * own - (window - 1), clamped at 0
        return _higher(i * own - (window - 1), 0) // other
    return (i * own) // other  # the first key's own position is the first query that sees it


def _visible(n_own: int, n_other: int, own: int, other: int, window: int, *, keys: bool) -> list:
    """How many tiles of the other sequence each tile sees; the most of them
    is the innermost grid dimension of a windowed call."""
    counts = []
    for i in range(n_own):
        first = _first_visible(i, own, other, window, keys=keys)
        # the last query's own position / the last key's newest query
        last_pos = (i + 1) * own - 1 if keys else (i + 1) * own - 1 + window - 1
        counts.append(min(last_pos // other, n_other - 1) - first + 1)
    return counts


def _window_mask(logits, q_start, k_start, window):
    """The causal mask, and the window's, inside a tile, from the rows' positions in the call."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    seen = qpos >= kpos
    if window is not None:
        seen = seen & (qpos - kpos < window)
    return jnp.where(seen, logits, NEG_INF)


# -- the block-diffusion mask ---------------------------------------------------


def _pick(cond, a, b):
    """a if cond else b, of traced grid indices or Python ints."""
    traced = any(isinstance(x, jax.Array) for x in (cond, a, b))
    return jnp.where(cond, a, b) if traced else (a if cond else b)


def _diffusion_ranges(i, own: int, other: int, rows: int, bd: BlockDiffusion, *, keys: bool):
    """The tiles of the OTHER sequence that tile `i` (of `own` rows) can see
    under the block-diffusion mask, as two runs (first, count, first, count),
    a count possibly 0: with `keys`, i is a query tile and the runs are key
    tiles (of `other` rows): the noisy keys of its own blocks, then the clean
    keys up to its blocks; without, i is a key tile and the runs are the noisy
    and then the clean query tiles that see it.  `i` may be a traced grid
    index or a Python int.  Tiles divide a copy, or are the whole call."""
    if own == rows:  # one tile of every row sees, and is seen by, every tile
        return 0, rows // other, 0, 0
    if other == rows:
        return 0, 1, 0, 0
    block, clean = bd.block, rows - bd.noisy
    n_noisy, n_clean = bd.noisy // other, clean // other  # tiles of the other sequence in each copy
    noisy = i < bd.noisy // own
    first_pos = (i - _pick(noisy, 0, bd.noisy // own)) * own  # the tile's positions in its copy
    b0, b1 = first_pos // block, (first_pos + own - 1) // block  # its first and last block
    own_first, own_last = (b0 * block) // other, ((b1 + 1) * block - 1) // other  # the tiles that hold its blocks
    if keys:
        # a noisy query: the noisy keys of its blocks; the clean keys of the blocks BEFORE its last (ceil of b1 * block / other tiles);
        # a clean query: no noisy key; the clean keys up to its last block
        return (own_first, _pick(noisy, own_last - own_first + 1, 0),
                n_noisy, ((b1 + _pick(noisy, 0, 1)) * block + other - 1) // other)
    # a noisy key: the noisy queries of its blocks, no clean one; a clean key: the noisy queries of the blocks AFTER its
    # first, and the clean queries from its first block on
    after = _lower(((b0 + 1) * block) // other, n_noisy)
    return (_pick(noisy, own_first, after), _pick(noisy, own_last - own_first + 1, n_noisy - after),
            n_noisy + own_first, _pick(noisy, 0, n_clean - own_first))


def _diffusion_step(i, j, own: int, other: int, rows: int, bd: BlockDiffusion, *, keys: bool):
    """(the other sequence's tile at grid step (i, j), whether the step
    runs): the two runs of `_diffusion_ranges` one after the other, and past
    them the last visible tile, held, so that the pipeline copies nothing."""
    a1, n1, a2, n2 = _diffusion_ranges(i, own, other, rows, bd, keys=keys)
    second = _pick(n2 > 0, a2 + _lower(j - n1, n2 - 1), a1 + n1 - 1)
    return _pick(j < n1, a1 + j, second), j < n1 + n2


def _diffusion_visible(n_own: int, own: int, other: int, rows: int, bd: BlockDiffusion, *, keys: bool) -> list:
    """How many tiles of the other sequence each tile sees; the most of them
    is the innermost grid dimension of a block-diffusion call."""
    ranges = (_diffusion_ranges(i, own, other, rows, bd, keys=keys) for i in range(n_own))
    return [n1 + n2 for _, n1, _, n2 in ranges]


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


# -- which run steps are masked -------------------------------------------------


def _wholly_visible(q_start, k_start, bq: int, bk: int, window: Optional[int], bd: Optional[BlockDiffusion]):
    """Whether every query of the tile at `q_start` (bq rows) sees every key
    of the tile at `k_start` (bk rows), so that the mask selects `logits`
    everywhere: the ONE predicate by which a run step of the three kernels
    takes the body without the mask, and what `tiles_unmasked_pct` counts.
    Causal: the tile's last key is no later than its first query and, under a
    window, its oldest pair is inside it.  Block-diffusion: a clean key tile
    whose last block is before (noisy queries) or no later than (clean
    queries) the query tile's first block; a noisy key tile is never wholly
    visible (its queries see their own blocks' keys alone), nor is a tile that
    holds both copies.  The starts may be traced grid values or Python ints."""
    if bd is None:
        clear = k_start + bk - 1 <= q_start
        return clear if window is None else clear & (q_start + bq - 1 - k_start < window)
    q_noisy = q_start < bd.noisy  # tiles divide a copy; one that holds the whole call starts at 0, "noisy", and sees no clean tile whole
    q_first = (q_start - _pick(q_noisy, 0, bd.noisy)) // bd.block
    k_last = (k_start - bd.noisy + bk - 1) // bd.block
    return (k_start >= bd.noisy) & (k_last < q_first + _pick(q_noisy, 0, 1))


def _edge_mask(logits, q_start, k_start, window, diffusion):
    """The mask of a call that has one, inside a tile its edge crosses."""
    if diffusion is not None:
        return _diffusion_mask(logits, q_start, k_start, diffusion)
    return _window_mask(logits, q_start, k_start, window)


def _by_kind(step, run, q_start, k_start, bq: int, bk: int, *, causal: bool, window, diffusion):
    """Runs `step(masked)` on a grid step that `run`s: without the mask where
    the tile is wholly visible, with it where the mask's edge crosses the
    tile.  A call without a mask has no edge and the one body, as it had."""
    if not causal and diffusion is None:
        pl.when(run)(functools.partial(step, False))
        return
    clear = _wholly_visible(q_start, k_start, bq, bk, window, diffusion)
    pl.when(run & clear)(functools.partial(step, False))
    pl.when(run & jnp.logical_not(clear))(functools.partial(step, True))


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
    visited = sum(_diffusion_visible(2 * seq // bq, bq, bk, 2 * seq, bd, keys=True))
    return 100.0 * (seq * seq + seq * block) / (visited * bq * bk)


def window_tiles_visited_pct(seq: int, window: int) -> Optional[float]:
    """Area of the key tiles the windowed FORWARD kernel visits, as % of what
    the causal call visits at ITS tiles, from the block sizes in use
    (`flash_attention`'s defaults and `_window_blocks`); None at a length no
    tile divides (the kernels do not run there)."""
    full = _fit_block(seq, DEFAULT_BLOCKS[0])
    if full is None:
        return None
    causal = full * full * sum(qi + 1 for qi in range(seq // full))
    bq, bk, _, _ = (_fit_block(seq, b) for b in _window_blocks(window, DEFAULT_BLOCKS))
    visited = sum(_visible(seq // bq, seq // bk, bq, bk, window, keys=True))
    return 100.0 * visited * bq * bk / causal


def causal_steps_copying_pct(seq: int, block_q: int, block_k: int, *, keys: bool) -> float:
    """Share (%) of a head's grid steps that make the pipeline copy, in a
    causal call without a window at these tiles: the steps whose pair of
    blocks (the outer axis's own tile, the inner axis's by the map) differs
    from the step before's, evaluated from the map the kernel is given
    (`_inner_tile`).  With `keys` the outer axis is the query tiles (forward,
    dq), without it the key tiles (dkv).  100 under the map `j -> j`; with the
    map held on the diagonal's tile, the visible pairs alone: 136 of 256 at
    16,384 positions and 1024 x 1024, 272 of 512 at 1024 x 512."""
    own, other = (block_q, block_k) if keys else (block_k, block_q)
    n_inner, tile = _inner_tile(seq // own, seq // other, own, other, None, keys=keys, causal=True)
    steps = [(i, tile(i, j)) for i in range(seq // own) for j in range(n_inner)]
    return 100.0 * (1 + sum(a != b for a, b in zip(steps, steps[1:]))) / len(steps)


def run_steps_unmasked(rows: int, bq: int, bk: int, window: Optional[int], bd: Optional[BlockDiffusion]) -> Tuple[int, int]:
    """(the run steps that take the body without the mask, all run steps) of
    one head in a kernel with these tiles, under the causal mask, its `window`
    or the block-diffusion mask `bd`, from the predicate the kernels are given
    (`_wholly_visible`).  The tile pairs that run are the same whichever axis
    is the outer one: walked here query tile by query tile, the key tiles from
    the first visible one to the diagonal's (the kernels' `run`) or the two
    runs of `_diffusion_ranges`."""
    if bd is not None:
        runs = (_diffusion_ranges(i, bq, bk, rows, bd, keys=True) for i in range(rows // bq))
        steps = [(i * bq, j * bk) for i, (a1, n1, a2, n2) in enumerate(runs) for j in (*range(a1, a1 + n1), *range(a2, a2 + n2))]
    else:
        first = (lambda i: 0) if window is None else functools.partial(_first_visible, own=bq, other=bk, window=window, keys=True)
        steps = [(i * bq, j * bk) for i in range(rows // bq) for j in range(first(i), (i * bq + bq - 1) // bk + 1)]
    return sum(bool(_wholly_visible(q0, k0, bq, bk, window, bd)) for q0, k0 in steps), len(steps)


def tiles_unmasked_pct(seq: int, d: int, dv: int, *, window: Optional[int] = None,
                       diffusion_block: Optional[int] = None) -> Optional[float]:
    """Share (%) of a head's FORWARD run steps that take the body without the
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
    tiles they had."""
    block_q, block_k, bwd_block_q, bwd_block_k = blocks
    if d + dv > _FWD_FULL_TILE_HEADS:
        block_k = min(block_k, 512)
    return block_q, block_k, bwd_block_q, bwd_block_k


# -- forward ---------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: Optional[int] = None, diffusion: Optional[BlockDiffusion] = None,
    rows: Optional[int] = None,
):
    # Blocks: q [1, 1, bq, D]; k [1, 1, bk, D]; v [1, 1, bk, Dv]; o [1, 1, bq, Dv];
    # lse [1, 1, bq, 1].  Scratch (carried across the kv grid dim): acc [bq, Dv] f32,
    # m/l [bq, LANES] f32 (lane-broadcast row scalars).
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    q_start = qi * bq
    k_start = ki * bk
    if window is not None:  # the grid counts from the first visible key tile
        k_start = (_first_visible(qi, bq, bk, window, keys=True) + ki) * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (k_start <= q_start + bq - 1) if causal else True
    if diffusion is not None:  # the grid walks the visible key tiles, noisy then clean
        k_tile, run = _diffusion_step(qi, ki, bq, bk, rows, diffusion, keys=True)
        k_start = k_tile * bk

    def _step(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, Dv]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if masked:
            logits = _edge_mask(logits, q_start, k_start, window, diffusion)
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

    _by_kind(_step, run, q_start, k_start, bq, bk, causal=causal, window=window, diffusion=diffusion)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.maximum(l, 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l_safe)


def _inner_tile(n_own, n_other, own, other, window, *, keys, causal, diffusion=None):
    """(innermost grid extent, index of the other sequence's tile at grid
    step (i, j)) of a call whose outer tiles are `own` wide: every tile and
    the step itself without a window and without a mask; with one, the
    visible tiles alone, counted from the first, the index held inside the
    sequence (a step past the last visible tile is predicated off in the
    kernel).  A causal call without a window keeps every step, and on the
    steps its kernel predicates off the index stays where the run steps
    beside them have it, so that the pipeline copies nothing for them: on
    the last visible key tile (`keys`: the off steps come last), on the first
    visible query tile (the off steps come first; held inside the sequence
    for a call with more keys than queries).  The bounds are the ones the
    kernels' `run` predicates compare with.  A block-diffusion call counts
    its visible tiles too, two runs of them (`_diffusion_step`)."""
    if diffusion is not None:
        rows = n_own * own
        return (max(_diffusion_visible(n_own, own, other, rows, diffusion, keys=keys)),
                lambda i, j: _diffusion_step(i, j, own, other, rows, diffusion, keys=keys)[0])
    if window is not None:
        first = functools.partial(_first_visible, own=own, other=other, window=window, keys=keys)
        return (max(_visible(n_own, n_other, own, other, window, keys=keys)),
                lambda i, j: jnp.minimum(first(i) + j, n_other - 1))
    if not causal:
        return n_other, lambda i, j: j
    if keys:  # the last key tile that `_fwd_kernel`'s and `_bwd_dq_kernel`'s `run` let through: k_start <= q_start + bq - 1
        return n_other, lambda i, j: _lower(j, (i * own + own - 1) // other)
    # (i * own) // other is the first query tile that `_bwd_dkv_kernel`'s `run` lets through:
    # q_start + bq - 1 >= k_start, with k_start = i * own and bq = other
    return n_other, lambda i, j: _lower(_higher(j, (i * own) // other), n_other - 1)


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, window=None, diffusion=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]  # q and k share one head size, v and the output another
    # Kernels work in [B, H, S, D].
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_k, k_tile = _inner_tile(sq // block_q, sk // block_k, block_q, block_k, window, keys=True, causal=causal, diffusion=diffusion)
    grid = (b, h, sq // block_q, n_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, window=window, diffusion=diffusion, rows=sq)
    out, lse = _pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, k_tile(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, hi, qi, ki: (bi, hi, k_tile(qi, ki), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
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
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale: float, causal: bool, window: Optional[int] = None, diffusion: Optional[BlockDiffusion] = None,
    rows: Optional[int] = None,
):
    # q/dq [1, 1, bq, D]; k [1, 1, bk, D]; v [1, 1, bk, Dv]; do [1, 1, bq, Dv];
    # lse/delta [1, 1, bq, 1].
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    q_start = qi * bq
    k_start = ki * bk
    if window is not None:  # as the forward: counted from the first visible key tile
        k_start = (_first_visible(qi, bq, bk, window, keys=True) + ki) * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (k_start <= q_start + bq - 1) if causal else True
    if diffusion is not None:  # as the forward: the visible key tiles, noisy then clean
        k_tile, run = _diffusion_step(qi, ki, bq, bk, rows, diffusion, keys=True)
        k_start = k_tile * bk

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
            logits = _edge_mask(logits, q_start, k_start, window, diffusion)
        p = jnp.exp(logits - lse)  # masked -> exp(-inf) = 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = p * (dp - delta)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _by_kind(_step, run, q_start, k_start, bq, bk, causal=causal, window=window, diffusion=diffusion)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, scale: float, causal: bool, window: Optional[int] = None, q_tiles: Optional[int] = None,
    diffusion: Optional[BlockDiffusion] = None, rows: Optional[int] = None,
):
    # Grid (b, h, kv_tile, q_tile) — q innermost so k/v blocks stay resident.
    # k/dk [1, 1, bk, D]; v/dv [1, 1, bk, Dv]; q [1, 1, bq, D]; do [1, 1, bq, Dv];
    # lse/delta [1, 1, bq, 1].
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    k_start = ki * bk
    q_start = qi * bq
    if window is not None:  # counted from the first query tile that sees this key tile
        q_start = (_first_visible(ki, bk, bq, window, keys=False) + qi) * bq

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    run = (q_start + bq - 1 >= k_start) if causal else True
    if window is not None:  # neither past the newest query of the window nor past the sequence
        run = run & (q_start <= k_start + bk - 1 + window - 1) & (q_start < q_tiles * bq)
    if diffusion is not None:  # the query tiles that see this key tile, noisy then clean
        q_tile, run = _diffusion_step(ki, qi, bk, bq, rows, diffusion, keys=False)
        q_start = q_tile * bq

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
            logits = _edge_mask(logits, q_start, k_start, window, diffusion)
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

    _by_kind(_step, run, q_start, k_start, bq, bk, causal=causal, window=window, diffusion=diffusion)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, *, causal, scale, block_q, block_k, window=None, diffusion=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_k, k_tile = _inner_tile(sq // block_q, sk // block_k, block_q, block_k, window, keys=True, causal=causal, diffusion=diffusion)
    n_q, q_tile = _inner_tile(sk // block_k, sq // block_q, block_k, block_q, window, keys=False, causal=causal, diffusion=diffusion)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, computed outside.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)[..., None]  # [B, H, Sq, 1]

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, window=window, diffusion=diffusion, rows=sq)
    dq = _pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(b, h, sq // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, k_tile(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, hi, qi, ki: (bi, hi, k_tile(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_q, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(qt, kt, vt, dot, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, window=window, q_tiles=sq // block_q, diffusion=diffusion, rows=sq)
    dk, dv = _pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(b, h, sk // block_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, q_tile(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dv), lambda bi, hi, ki, qi: (bi, hi, q_tile(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, ki, qi: (bi, hi, q_tile(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, ki, qi: (bi, hi, q_tile(ki, qi), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
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

    Which steps copy: a causal call visits every (q tile, k tile) pair of its
    rectangular grids and predicates off the pairs above the diagonal; their
    index maps stay on the diagonal's tile through those steps
    (`_inner_tile`), so only a step that runs brings in a key and value tile
    (forward, dq) or a query, dO, log-sum-exp and delta tile (dkv): 136 of a
    head's 256 forward steps at 16,384 positions, 272 of its 512 backward
    steps (`causal_steps_copying_pct`).  A non-causal call runs and copies on
    every step.

    `window` (static; needs `causal` and equal sequence lengths): query i
    sees keys i - window + 1 .. i, and a tile no query of the call sees is
    never visited (module docstring).  A windowed call gets tiles no larger
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
        causal = False  # the kernels' causal paths are not traced: the mask and the visited tiles are the block-diffusion ones
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
