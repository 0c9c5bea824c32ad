"""Logical-axis sharding rules: DP/FSDP/TP/SP/EP as data, not wrappers.

The reference expresses parallelism strategy as *wrapper choice* —
DistributedDataParallel vs FullyShardedDataParallel selected by a string
(ray: python/ray/train/torch/train_loop_utils.py:92-98).  TPU-native, a
strategy is just a table mapping logical array axes ("embed", "mlp", "heads",
"batch", ...) to mesh axes; XLA inserts the collectives.  Switching DP → FSDP
→ TP → 3D is a rules change, no model code change.
"""

from __future__ import annotations

import math

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, MeshAxis]


# Logical axis vocabulary used by models/ (see models/transformer.py).
# Parameter axes and activation axes are distinct namespaces (act_*): under
# FSDP, params shard their embed dim over `fsdp` while activations shard
# batch over ("data", "fsdp") — same mesh axis, different logical axes, so
# a single rules table can't alias them.
#
#   embed/heads/kv_heads/head_dim/mlp/vocab/expert — parameter dims
#   layers — scan-over-layers leading axis (sharded over `pipeline` by
#            pp_rules; unsharded elsewhere)
#   A Mamba-2 mixer's leaves (models/mixers/mamba2.py, stack `mamba_layers`) use
#   `embed` on the model side of their two projections and no name on the
#   mixer's inner width: fsdp shards them, tp replicates them (the scan's
#   heads are not split over `tensor`).
#   act_batch/act_seq/act_embed/act_heads/act_kv_heads/act_head_dim/
#   act_mlp/act_vocab — activation dims

_BASE: Rules = {
    # params
    "embed": None,
    "heads": None,
    "kv_heads": None,
    "head_dim": None,
    "mlp": None,
    "vocab": None,
    "expert": None,
    "layers": None,
    # activations
    "act_batch": ("data", "fsdp"),
    "act_seq": None,
    "act_embed": None,
    "act_heads": None,
    "act_kv_heads": None,
    "act_head_dim": None,
    "act_mlp": None,
    "act_vocab": None,
    "act_expert": None,
}


def dp_rules() -> Rules:
    """Pure data parallel: replicate params, shard batch."""
    return dict(_BASE)


def fsdp_rules() -> Rules:
    """ZeRO-3 analogue: shard every large param dim over the fsdp axis.

    XLA all-gathers params per layer and reduce-scatters grads — the compiled
    equivalent of the reference's FSDP wrapper (train_loop_utils.py:92-98).
    """
    r = dict(_BASE)
    r.update(embed="fsdp", mlp=None, vocab=None)
    return r


def tp_rules() -> Rules:
    """Megatron-style tensor parallel over the tensor axis (absent in the
    reference — SURVEY.md §2.4 lists TP as not built-in)."""
    r = dict(_BASE)
    r.update(
        heads="tensor", kv_heads="tensor", mlp="tensor", vocab="tensor",
        act_heads="tensor", act_kv_heads="tensor", act_mlp="tensor",
        act_vocab="tensor",
    )
    return r


def fsdp_tp_rules() -> Rules:
    """2D: params sharded over fsdp × tensor (the standard pod recipe)."""
    r = tp_rules()
    r.update(embed="fsdp")
    return r


def sp_rules() -> Rules:
    """Context/sequence parallel: shard activations along seq (ring attention
    pairs with this — ops/ring_attention.py)."""
    r = fsdp_tp_rules()
    r.update(act_seq="seq")
    return r


def pp_rules() -> Rules:
    """Pipeline parallel: the scan-over-layers param stack shards over the
    `pipeline` axis — each pipeline-stage device holds L/P layers, and the
    model dispatches the GPipe microbatch schedule
    (parallel/pipeline.py) instead of a plain layer scan."""
    r = dict(_BASE)
    r.update(layers="pipeline")
    return r


def pp_fsdp_rules() -> Rules:
    """Pipeline x FSDP: layer stack over `pipeline`, params-at-rest sharded
    over `fsdp` WITHIN each stage (all-gathered per stage per step, grads
    reduce-scattered back — ZeRO-style optimizer-state sharding on top of
    the GPipe schedule; parallel/pipeline.py fsdp_dims)."""
    r = dict(_BASE)
    r.update(layers="pipeline", embed="fsdp")
    return r


def ep_rules() -> Rules:
    """Expert parallel for MoE layers."""
    r = fsdp_tp_rules()
    r.update(expert="expert", act_expert="expert")
    return r


PRESETS = {
    "dp": dp_rules,
    "fsdp": fsdp_rules,
    "tp": tp_rules,
    "fsdp_tp": fsdp_tp_rules,
    "sp": sp_rules,
    "pp": pp_rules,
    "pp_fsdp": pp_fsdp_rules,
    "ep": ep_rules,
}


def resolve_rules(strategy: Union[str, Rules]) -> Rules:
    if isinstance(strategy, str):
        try:
            return PRESETS[strategy]()
        except KeyError:
            raise ValueError(f"unknown strategy {strategy!r}; options {sorted(PRESETS)}")
    return dict(strategy)


def pipeline_axes(rules: Optional[Rules], mesh: Optional[Mesh], n_layers: int) -> Optional[Tuple[str, Optional[str]]]:
    """(the mesh axis the layer stack pipelines over, the one fsdp-at-rest
    param axis or None) when the rules shard the LAYER STACK over a real (>1)
    mesh axis, so that the model runs the GPipe microbatch schedule instead
    of a plain scan (each stage device holds n_layers/P layers); None
    otherwise.

    Explicit pp intent: misconfigurations are ERRORS, not silent fallbacks:
    replicated layers instead of pipelining would only surface as OOM/low
    MFU at scale."""
    if rules is None or rules.get("layers") is None:
        return None
    ax = rules["layers"]
    ax = ax[0] if isinstance(ax, tuple) else ax
    size = mesh.shape[ax] if ax in mesh.axis_names else 1
    if size <= 1:
        return None
    if n_layers % size != 0:
        raise ValueError(
            f"strategy 'pp': n_layers={n_layers} not divisible by "
            f"pipeline axis size {size}"
        )
    sharded_params = [
        k for k in ("embed", "heads", "kv_heads", "head_dim", "mlp",
                    "vocab", "expert")
        if rules.get(k) is not None
    ]
    # fsdp-at-rest composes with pp (strategy "pp_fsdp"): the sharded param
    # axes are all-gathered per stage per step inside the schedule.  TP-style
    # axes (which also shard activations) do NOT: gathering them would
    # silently undo the tensor split.
    act_axes = set()
    for k, v in rules.items():
        if k.startswith("act_") and k != "act_batch" and v is not None:
            act_axes.update(v if isinstance(v, tuple) else (v,))
    pp_fsdp_axes = set()
    bad = []
    for k in sharded_params:
        v = rules[k]
        if isinstance(v, tuple) or v == ax or v in act_axes:
            bad.append(k)
        else:
            pp_fsdp_axes.add(v)
    if bad:
        raise ValueError(
            "strategy 'pp' composes with data sharding and ONE "
            "fsdp-at-rest param axis (strategy 'pp_fsdp'); param "
            f"dims {bad} shard over activation/tensor axes the "
            "pipeline schedule cannot gather away"
        )
    if len(pp_fsdp_axes) > 1:
        raise ValueError(
            "strategy 'pp' composes with at most ONE fsdp-at-rest "
            f"param axis, got {sorted(pp_fsdp_axes)} across "
            f"{sharded_params}"
        )
    return ax, (pp_fsdp_axes.pop() if pp_fsdp_axes else None)


def logical_to_spec(logical_axes: Sequence[Optional[str]], rules: Rules) -> P:
    """Map a tuple of logical axis names (None = unsharded) to a PartitionSpec."""
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


def tree_shardings(logical_tree, rules: Rules, mesh: Mesh):
    """Map a pytree of logical-axes tuples to a pytree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def _fit_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop mesh axes (innermost first) from any spec entry whose axis-size
    product does not divide the corresponding dim. Replicates instead of
    erroring for e.g. GQA kv_heads < tensor-axis size."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    new_entries = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            new_entries.append(None)
            continue
        axes = list(entry) if isinstance(entry, tuple) else [entry]
        while axes and dim % math.prod(sizes[a] for a in axes) != 0:
            axes.pop()
        new_entries.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return P(*new_entries)


def fit_shardings(shape_tree, sharding_tree):
    """Shape-validate a sharding tree (see _fit_spec)."""

    def fit_one(shape_leaf, sharding: NamedSharding) -> NamedSharding:
        shape = getattr(shape_leaf, "shape", shape_leaf)
        return NamedSharding(sharding.mesh, _fit_spec(shape, sharding.spec, sharding.mesh))

    return jax.tree_util.tree_map(
        fit_one, shape_tree, sharding_tree,
        is_leaf=lambda x: hasattr(x, "shape"),
    )


def with_logical_constraint(
    x,
    logical_axes: Sequence[Optional[str]],
    rules: Rules,
    mesh: Optional[Mesh] = None,
):
    """Activation sharding hint inside jit (lax.with_sharding_constraint).

    With an explicit mesh the constraint is a shape-fitted NamedSharding
    (axes that don't divide the dim are dropped, matching fit_shardings);
    otherwise a bare PartitionSpec relying on the enclosing `with mesh:`
    scope.
    """
    spec = logical_to_spec(logical_axes, rules)
    if mesh is not None:
        spec = _fit_spec(x.shape, spec, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)
