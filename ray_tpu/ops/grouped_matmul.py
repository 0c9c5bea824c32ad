"""Grouped (ragged) matmul, the expert layer's one heavy op.

`grouped_matmul(lhs [m, k], rhs [G, k, n], group_sizes [G]) -> [m, n]`: the
rows of `lhs` lie in G consecutive groups and group g multiplies `rhs[g]`.
Rows behind the last group (where `group_sizes` sums to less than m) are
not defined.  Differentiable in both operands.

Like attention, the form follows the platform a step is LOWERED for
(`kernel_pair.dispatch`), not the process's backend: the Pallas kernels
(`ops/pallas/grouped_matmul.py`) for TPU when the shapes are ones they take
(rows in multiples of 128; k and n each a multiple of 128, tiled, or a
multiple of 8 from 128 to 2,048, as one whole block: Nemotron-3-Nano's 1856), the
XLA form below everywhere else.  Nothing selects between them, and a shape
the kernels refuse is never taken in silence: the XLA form then runs under the
scope `REFUSED_SCOPE`, which a step lowered for TPU carries in its HLO, the
shape is counted in `refused_shapes` and logged once.  For TPU
the kernels were chosen over `jax.lax.ragged_dot` by measurement (PERF.md
section 6, PR 26).  Off the TPU `ragged_dot` is no candidate: jax lowers it
there to one masked dense matmul PER GROUP over all rows, G times the work
(64x at OLMoE's 64 experts: 12-15 s for one CPU rehearsal step of the
benchmark's toy size).
"""

from __future__ import annotations

import logging
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_pair
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# (m, k, n) -> times a step was traced with a shape the kernels refuse, so
# that on a TPU too it runs the XLA form; the scope those calls carry.
refused_shapes: Dict[Tuple[int, int, int], int] = {}
REFUSED_SCOPE = "grouped_matmul/kernels_refused"

_XLA_TILE = 64  # rows per tile of the XLA form: at most m + (G - 1) * 64 rows are multiplied


def _visits(group_sizes: jax.Array, m: int):
    """The kernels' schedule for the XLA form: row tiles of `tm` rows, a tile
    visited once per group that has rows in it.  (tm, group of each visit,
    tile of each visit, [visits, tm] mask of the tile's rows that are the
    visit's group's; padding visits are all-False)."""
    from ray_tpu.ops.pallas import grouped_matmul as kernels

    tm = _XLA_TILE
    while m % tm:
        tm //= 2
    offsets, group_ids, tile_ids, n_visits = kernels._group_metadata(
        group_sizes, m, tm, visit_empty_groups=False)
    rows = tile_ids[:, None] * tm + jnp.arange(tm, dtype=jnp.int32)[None, :]
    mine = ((rows >= offsets[group_ids][:, None]) & (rows < offsets[group_ids + 1][:, None])
            & (jnp.arange(group_ids.shape[0]) < n_visits)[:, None])
    return tm, group_ids, tile_ids, mine


def _tiles(x: jax.Array, tm: int, tile_ids: jax.Array, mine: jax.Array) -> jax.Array:
    """x [m, k] -> [visits, tm, k]: each visit's tile, other groups' rows zeroed."""
    return jnp.where(mine[..., None], x.reshape(-1, tm, x.shape[1])[tile_ids], 0)


@jax.custom_vjp
def grouped_matmul_xla(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The kernels' schedule as plain XLA ops: each visit one slice of a
    batched matmul against its group's weights, the visits of a tile added
    up.  The backward is the same two products the kernels' is (autodiff's
    own would scatter-add rows and whole weight matrices)."""
    m = lhs.shape[0]
    tm, group_ids, tile_ids, mine = _visits(group_sizes, m)
    out = jnp.einsum("vtk,vkn->vtn", _tiles(lhs, tm, tile_ids, mine), rhs[group_ids],
                     preferred_element_type=jnp.float32)
    out = jnp.zeros((m // tm, tm, rhs.shape[2]), jnp.float32).at[tile_ids].add(out)
    return out.reshape(m, rhs.shape[2]).astype(lhs.dtype)


def _xla_fwd(lhs, rhs, group_sizes):
    return grouped_matmul_xla(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _xla_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = grouped_matmul_xla(g.astype(lhs.dtype), rhs.swapaxes(1, 2), group_sizes)
    tm, group_ids, tile_ids, mine = _visits(group_sizes, lhs.shape[0])
    per_visit = jnp.einsum("vtk,vtn->vkn", _tiles(lhs, tm, tile_ids, mine),
                           g.astype(lhs.dtype).reshape(-1, tm, g.shape[1])[tile_ids],
                           preferred_element_type=jnp.float32)
    of_group = jax.nn.one_hot(group_ids, rhs.shape[0], dtype=jnp.float32)  # [visits, G]
    d_rhs = jnp.einsum("vg,vkn->gkn", of_group, per_visit)
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul_xla.defvjp(_xla_fwd, _xla_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    # imported here, as attention imports its kernels: a dense model's process never loads Pallas for this
    from ray_tpu.ops.pallas import grouped_matmul as kernels

    shape = (lhs.shape[0], rhs.shape[1], rhs.shape[2])
    if kernels.supported(*shape):
        return kernel_pair.dispatch(True, kernels.grouped_matmul, grouped_matmul_xla, lhs, rhs, group_sizes)
    if shape not in refused_shapes:
        logger.warning("grouped_matmul: the TPU kernels refuse m, k, n = %s; the XLA form runs on every platform", shape)
    refused_shapes[shape] = refused_shapes.get(shape, 0) + 1
    with tracing.scope(REFUSED_SCOPE):
        return grouped_matmul_xla(lhs, rhs, group_sizes)
