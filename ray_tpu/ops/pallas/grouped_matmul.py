"""Grouped (ragged) matmul kernels for the expert layer, in Pallas for TPU.

The rows of `lhs` [m, k] are sorted into G consecutive groups of
`group_sizes` rows; group g multiplies its own `rhs[g]`:

- `moe_gmm(lhs, rhs [G, k, n], group_sizes)` -> [m, n]: activations x expert
  weights (and, with `transpose_rhs`, cotangents x expert weights transposed:
  the gradient of the activations);
- `moe_tgmm(lhs [m, k], rhs [m, n], group_sizes)` -> [G, k, n]: per group
  `lhs_g^T @ rhs_g`, the gradient of the expert weights;
- `grouped_matmul` ties the three calls into one `custom_vjp`.

Started from the megablox kernels that ship with jax
(`jax/experimental/pallas/ops/tpu/megablox/gmm.py`, Apache 2.0, The JAX
Authors), cut to what the expert layer uses: no group offset, no
accumulation into an existing output, tiles that divide k and n (`_tile`: a
power-of-two multiple of 128 where one above 128 divides, else the largest
multiple of 128 that does, 2688 -> 896; a width that is no multiple of 128,
1856, as ONE whole block).  The idea is
theirs: the grid walks row tiles of `tm` rows; a tile that straddles a group
boundary is visited once per group it holds, with a row mask, and the tile ->
group map is computed from `group_sizes` in XLA and handed to the kernel as
scalar prefetch, so one compiled kernel serves any routing.

`moe_gmm` writes only rows that belong to a group: where `group_sizes` sums
to less than m (an expert-parallel rank computing its own experts' rows
only), the rows behind the last group hold whatever was in memory and the
caller masks them.

Chosen over `jax.lax.ragged_dot` for TPU by measurement at the shapes of
`olmoe-1chip.seq4k` (65,536 rows, 64 groups from the real router, the three
projections, forward + backward: 20.8 ms against 30.0 ms; PERF.md section 6,
PR 26).  Off the TPU the layer uses an XLA form of the same schedule
(`ops/grouped_matmul.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.flash_attention import _pallas_call

# (rows, contraction, output) tile of `moe_gmm`; `moe_tgmm` contracts over
# `tm` rows into a [tk, tn] tile.  Timed on the v5e at the cell's shapes (PR
# 26): 512x1024x1024 and 256x1024x1024 lead within 1.5% of each other,
# 512-wide k or n tiles lose 10-13%, 128^3 is 11x slower; 2048-wide k does
# not fit VMEM.
_TM, _TK, _TN = 512, 1024, 1024
# The dimensions that are no multiple of 128 and still go as ONE block
# (Nemotron-3-Nano's expert width 1856 = 29 x 64: a block may be a whole
# dimension whatever its size, and no smaller tile of it is a lane multiple):
# whole sublane tiles of at least one lane tile, up to what VMEM holds.
_WHOLE = range(128, 2048 + 1, 8)
# What Mosaic gives a kernel unasked (16 MiB of the v5e's 128), and what the
# kernels ask for when their blocks need more: PERF.md section 6, PR 44.
_VMEM_DEFAULT, _VMEM_ASKED = 16 * 2 ** 20, 64 * 2 ** 20


def _tile(dim: int, largest: int) -> int:
    """The tile of one dimension (rows come in multiples of 128, `supported`,
    so they take the first two rules alone).  The largest power-of-two multiple
    of 128 up to `largest` that divides dim, where one above 128 does (every
    width of OLMoE and Kimi Linear: 2048, 1024, 2304 -> 256); else the largest
    multiple of 128 that does (2688 = 21 x 128 -> 896, where powers of two
    find only the 128 that PR 26 timed at 11x slower); a dim that is no
    multiple of 128, whole."""
    t = largest
    while t > 128 and dim % t:
        t //= 2
    if t > 128 or dim == 128:
        return t
    if dim % 128 == 0:
        return max(c for c in range(128, largest + 1, 128) if dim % c == 0)
    if dim not in _WHOLE:
        raise ValueError(f"grouped matmul needs dimensions in multiples of 128, or of 8 from {_WHOLE[0]} to {_WHOLE[-1]}, "
                         f"got {dim}")
    return dim


def supported(m: int, k: int, n: int) -> bool:
    """Whether the kernels take these shapes (else the caller uses the XLA form)."""
    return m % 128 == 0 and all(d % 128 == 0 or d in _WHOLE for d in (k, n))


def _compiler_params(vmem_bytes: int):
    """The grid's semantics, and a VMEM limit only where the blocks need more
    than Mosaic's own (so the kernels of the accepted widths compile as they did)."""
    limit = {} if vmem_bytes <= _VMEM_DEFAULT else {"vmem_limit_bytes": _VMEM_ASKED}
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"), **limit)


def _group_metadata(group_sizes: jax.Array, m: int, tm: int, visit_empty_groups: bool):
    """Which group and which row tile each step of the grid's tile axis works
    on.  Returns (group_offsets [G+1], group_ids [tiles + G - 1], m_tile_ids
    [tiles + G - 1], the number of steps to run).  A tile is visited once by
    each group that has rows in it; consecutive visits of one tile are
    adjacent, as revisiting an output block requires.  With
    `visit_empty_groups` an empty group still gets one step (moe_tgmm has to
    write its zeros)."""
    n_groups = group_sizes.shape[0]
    group_ends = jnp.cumsum(group_sizes)
    group_offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), group_ends]).astype(jnp.int32)
    group_starts = group_offsets[:-1]
    # tiles per group: its end rounded up to a tile, its start rounded down
    rounded = (group_ends + tm - 1) // tm * tm - group_starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 1 if visit_empty_groups else 0, rounded // tm)
    tiles_m = m // tm
    steps = tiles_m + n_groups - 1  # every tile once + one more per group boundary inside a tile
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), group_tiles,
                           total_repeat_length=steps)
    # a tile is visited once by the group that owns its first row, and once
    # more by every group that starts inside it
    starts_inside = (group_starts % tm != 0) & (group_sizes != 0)
    if visit_empty_groups:
        starts_inside = starts_inside | (group_sizes == 0)
    extra = jnp.zeros(tiles_m, jnp.int32).at[jnp.where(starts_inside, group_starts // tm, tiles_m)].add(
        1, mode="drop")
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                            total_repeat_length=steps)
    return group_offsets, group_ids, m_tile_ids, jnp.sum(group_tiles)


def _row_mask(step, group_offsets, group_ids, m_tile_ids, tm: int, width: int):
    """[tm, width] mask of the rows of this step's tile that are its group's."""
    group = group_ids[step]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0) + m_tile_ids[step] * tm
    return (rows >= group_offsets[group]) & (rows < group_offsets[group + 1])


def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
            transpose_rhs: bool = False, tiles=None) -> jax.Array:
    """lhs [m, k] x rhs [G, k, n] (or [G, n, k] with `transpose_rhs`) -> [m, n]
    in lhs's dtype, accumulated in float32.  `tiles`: (tm, tk, tn) in place of
    the rule's, for the script that times candidates."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiles or (_tile(m, _TM), _tile(k, _TK), _tile(n, _TN))
    tiles_k = k // tk
    *metadata, n_steps = _group_metadata(group_sizes, m, tm, visit_empty_groups=False)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(group_offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref, acc_ref):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                                            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # rows of other groups keep what an earlier visit of this tile wrote
            mask = _row_mask(step, group_offsets, group_ids, m_tile_ids, tm, tn)
            out_ref[...] = jnp.where(mask, acc_ref[...], out_ref[...].astype(jnp.float32)
                                     ).astype(out_ref.dtype)

    def rhs_index(n_i, step, k_i, group_offsets, group_ids, m_tile_ids):
        return (group_ids[step], n_i, k_i) if transpose_rhs else (group_ids[step], k_i, n_i)

    return _pallas_call(
        kernel, name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, step, k_i, go, gi, mt: (mt[step], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, step, k_i, go, gi, mt: (mt[step], n_i)),
            grid=(n // tn, n_steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        # two buffers of each block in lhs's dtype, the float32 accumulator and the stored tile widened
        compiler_params=_compiler_params(
            2 * (tm * tk + tk * tn + tm * tn) * lhs.dtype.itemsize + 2 * tm * tn * 4),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * (n // tn) + k * n * (m // tm + rhs.shape[0]) + m * n)
            * lhs.dtype.itemsize),
    )(*metadata, lhs, rhs)


def moe_tgmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *, tiles=None) -> jax.Array:
    """Per group g: lhs_g^T [k, rows_g] x rhs_g [rows_g, n] -> [G, k, n] in
    rhs's dtype, accumulated in float32; zeros for an empty group.  `tiles` as `moe_gmm`'s."""
    m, k = lhs.shape
    n = rhs.shape[1]
    n_groups = group_sizes.shape[0]
    tm, tk, tn = tiles or (_tile(m, _TM), _tile(k, _TK), _tile(n, _TN))
    *metadata, n_steps = _group_metadata(group_sizes, m, tm, visit_empty_groups=True)

    def kernel(group_offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref, acc_ref):
        step = pl.program_id(2)
        group = group_ids[step]
        last = step == pl.num_programs(2) - 1
        first_of_group = (step == 0) | (group_ids[jnp.maximum(step - 1, 0)] != group)
        last_of_group = last | (group_ids[jnp.where(last, step, step + 1)] != group)

        @pl.when(first_of_group)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            # rows of the tile that belong to other groups multiply as zeros
            # (selected, and lhs transposed, in float32: the v5e's vector unit
            # has no bf16)
            def mine(ref, width):
                mask = _row_mask(step, group_offsets, group_ids, m_tile_ids, tm, width)
                return jnp.where(mask, ref[...].astype(jnp.float32), 0.0)

            acc_ref[...] += jax.lax.dot(mine(lhs_ref, tk).T.astype(lhs_ref.dtype),
                                        mine(rhs_ref, tn).astype(rhs_ref.dtype),
                                        preferred_element_type=jnp.float32)

        @pl.when(last_of_group)
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return _pallas_call(
        kernel, name="moe_tgmm",
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, step, go, gi, mt: (mt[step], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, step, go, gi, mt: (mt[step], n_i)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda n_i, k_i, step, go, gi, mt: (gi[step], k_i, n_i)),
            grid=(n // tn, k // tk, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        # two buffers of each block and the float32 accumulator
        compiler_params=_compiler_params(2 * (tm * tk + tm * tn + tk * tn) * lhs.dtype.itemsize + tk * tn * 4),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * (n // tn) + rhs.size * (k // tk) + n_groups * k * n)
            * lhs.dtype.itemsize),
    )(*metadata, lhs, rhs)


@jax.custom_vjp
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """lhs [m, k] x rhs [G, k, n] by groups of rows -> [m, n]; differentiable
    in both operands."""
    return moe_gmm(lhs, rhs, group_sizes)


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return moe_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = moe_gmm(g, rhs, group_sizes, transpose_rhs=True)
    d_rhs = moe_tgmm(lhs, g.astype(rhs.dtype), group_sizes)
    return d_lhs.astype(lhs.dtype), d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
