"""Pipeline parallelism: GPipe-style microbatch pipelining over the
`pipeline` mesh axis.

Absent from the reference entirely (SURVEY §2.4: PP not built in) — built
TPU-first: each pipeline-axis device holds ONE stage's parameters;
microbatches stream through the stages with `ppermute` hops over ICI, and
the whole schedule is a single `lax.scan` inside `shard_map`, so XLA
overlaps each stage's matmuls with its neighbor transfers and reverse-mode
AD differentiates straight through the schedule (backward pipeline for
free — ppermute's transpose is the reverse ring).

Composes with the other axes: the batch dim shards over ("data", "fsdp")
as usual; stages over "pipeline".
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _feasible_microbatches(B: int, requested: int) -> int:
    """Largest feasible microbatch count <= requested (the local batch
    must split evenly); warns when an EXPLICIT user value is truncated."""
    M = max(min(requested, B), 1)
    while B % M:
        M -= 1
    if M != requested:
        import warnings

        warnings.warn(
            f"pipeline: n_microbatches={requested} infeasible for local "
            f"batch {B}; using {M} (at M=1 the schedule degrades to "
            f"sequential stages — resize the batch for real pipelining)"
        )
    return M


def _derive_microbatches(mesh, x, batch_axes, n_stages: int) -> int:
    """Default M: the largest DIVISOR of the local batch <= 4P — 4P keeps
    the GPipe bubble (P-1)/(M+P-1) near 20% without shrinking microbatches
    into MXU-starving slivers, and a divisor is exactly feasible (no
    truncation warning for our own derivation)."""
    import math

    local_b = x.shape[0] // max(
        math.prod(mesh.shape[a] for a in batch_axes), 1
    )
    return max(
        (m for m in range(1, min(4 * n_stages, local_b) + 1)
         if local_b % m == 0),
        default=1,
    )


def _gather_params(params, gather_dims):
    """all_gather the fsdp-sharded leaves (see _pipeline_body docstring).
    gather_dims leaves are (dim_index, mesh_axis) tuples or None."""
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(gather_dims)
    gathered = [
        p if gd is None else jax.lax.all_gather(p, gd[1], axis=gd[0], tiled=True)
        for p, gd in zip(flat_p, flat_g)
    ]
    return jax.tree_util.tree_unflatten(treedef, gathered)


def _pipeline_body(
    stage_params,
    x: jax.Array,
    *,
    fn: Callable,
    n_microbatches: int,
    axis: str,
    gather_dims=None,
):
    """Per-shard body (inside shard_map).

    stage_params: this stage's params with a leading length-1 stage dim.
    x: this data-shard's batch [B_local, ...]; only stage 0 consumes it,
    but every stage holds it (replicated over the pipeline axis).
    gather_dims: optional pytree congruent with stage_params of
    (dim, mesh_axis) or None per leaf — fsdp-at-rest composition: the leaf
    arrives sharded on `dim` over `mesh_axis` and is all-gathered here
    before the stage scan (its AD transpose is a reduce-scatter, so grads
    land sharded again — ZeRO-style param/optimizer sharding with one
    gather per stage per step).
    Returns y [B_local, ...] replicated over the pipeline axis.
    """
    n_stages = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    if gather_dims is not None:
        params = _gather_params(params, gather_dims)

    B = x.shape[0]
    if B < 1:
        raise ValueError("pipeline stage received an empty batch")
    M = _feasible_microbatches(B, n_microbatches)
    micro = x.reshape(M, B // M, *x.shape[1:])

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    zero_mb = jnp.zeros_like(micro[0])
    outs0 = jnp.zeros_like(micro)

    def step(carry, t):
        recv, outs = carry
        mb_idx = jnp.clip(t, 0, M - 1)
        feed = jax.lax.dynamic_index_in_dim(micro, mb_idx, keepdims=False)
        # Stage 0 injects fresh microbatches while they last; every other
        # stage consumes what its predecessor sent last tick.
        inject = jnp.logical_and(stage == 0, t < M)
        inp = jnp.where(inject, feed, recv)
        out = fn(params, inp)
        # Last stage banks finished microbatches (valid for t >= P-1).
        k = t - (n_stages - 1)
        bank = jnp.logical_and(stage == n_stages - 1, k >= 0)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(bank, out, jax.lax.dynamic_index_in_dim(outs, jnp.clip(k, 0, M - 1), keepdims=False)),
            jnp.clip(k, 0, M - 1),
            0,
        )
        recv_next = jax.lax.ppermute(out, axis, perm)
        return (recv_next, outs), None

    (recv, outs), _ = jax.lax.scan(
        step, (zero_mb, outs0), jnp.arange(M + n_stages - 1)
    )
    # Results live on the last stage; broadcast so every stage returns the
    # same value (out_specs replicate over the pipeline axis).
    outs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
    )
    return outs.reshape(B, *x.shape[1:])


def pipeline_apply(
    fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: Optional[int] = None,
    axis: str = "pipeline",
    batch_axes: Sequence[str] = ("data", "fsdp"),
    fsdp_dims=None,
    fsdp_axis: str = "fsdp",
):
    """Apply `fn` (one stage's computation: fn(params, x) -> y, same shape)
    as a pipeline of P stages.

    stacked_params: pytree with a leading stage dim of size P (the pipeline
    mesh-axis size), e.g. stacked layer weights [P, ...].
    x: global batch [B, ...]; B shards over batch_axes; the microbatch
    schedule runs inside each data shard.
    n_microbatches: None derives M = min(4 * P, local batch) — 4P keeps the
    GPipe bubble (P-1)/(M+P-1) near 20% without shrinking microbatches
    into MXU-starving slivers.
    fsdp_dims: optional pytree congruent with stacked_params of per-leaf
    dim index (into the STACKED leaf, so >= 1) to shard over `fsdp_axis`
    at rest — pp x fsdp composition: params live sharded, are all-gathered
    per stage per step, and their grads reduce-scatter back (ZeRO-style).
    Leaves with None (or dims that don't divide) stay replicated.
    """
    n_stages = mesh.shape[axis]
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    if n_microbatches is None:
        n_microbatches = _derive_microbatches(mesh, x, batch_axes, n_stages)

    fsdp_size = mesh.shape[fsdp_axis] if fsdp_axis in mesh.axis_names else 1

    def leaf_plan(p, d):
        """(in_spec, gather_dim) for one stacked leaf."""
        if d is None or fsdp_size <= 1 or p.shape[d] % fsdp_size != 0:
            return P(axis), None
        entries = [axis] + [None] * (d - 1) + [fsdp_axis]
        # gather dim is d-1 inside the body (stage dim dropped there)
        return P(*entries), (d - 1, fsdp_axis)

    if fsdp_dims is not None:
        flat_p, treedef = jax.tree_util.tree_flatten(stacked_params)
        flat_d = treedef.flatten_up_to(fsdp_dims)
        plans = [leaf_plan(p, d) for p, d in zip(flat_p, flat_d)]
        param_spec = jax.tree_util.tree_unflatten(treedef, [s for s, _ in plans])
        gather_dims = jax.tree_util.tree_unflatten(treedef, [g for _, g in plans])
    else:
        param_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
        gather_dims = None
    xspec = P(batch_axes if batch_axes else None)
    body = functools.partial(
        _pipeline_body,
        fn=fn,
        n_microbatches=n_microbatches,
        axis=axis,
        gather_dims=gather_dims,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_spec, xspec),
        out_specs=xspec,
        check_vma=False,
    )(stacked_params, x)


# ---------------------------------------------------------------------------
# 1F1B schedule (no reference counterpart — SURVEY §2.4 names pp as
# TPU-native work; the schedule itself is the PipeDream-flush / Megatron
# non-interleaved 1F1B).


def _1f1b_body(
    stage_params,
    x: jax.Array,
    target,
    *,
    fn: Callable,
    loss_fn: Callable,
    n_microbatches: int,
    axis: str,
):
    """Per-shard fused forward+backward 1F1B schedule.

    GPipe differentiates the forward scan with autodiff, so every one of
    the M in-flight microbatch activations (plus scan residuals across
    M+P-1 ticks) is live at the backward's start — activation memory grows
    linearly with M.  1F1B starts each microbatch's backward as soon as
    the last stage finishes its forward, so a stage holds at most
    2(P-1-s)+1 <= 2P-1 in-flight inputs: the residual ring here is sized
    by the PIPELINE DEPTH, not the microbatch count.  The backward is
    explicit (jax.vjp per slot, recomputing the stage forward — remat of
    one stage per microbatch), cotangents ride the reverse ring, and
    parameter gradients accumulate locally, so the whole fwd+bwd schedule
    is ONE lockstep lax.scan of M + 2P - 2 ticks.

    Tick roles (lockstep SPMD — every device executes both slots, masked
    when idle): F slot at stage s handles microbatch m = t - s; B slot
    handles m = t - (2P - 2 - s); the last stage's B follows its F in the
    SAME tick (loss cotangent computed in place).

    Returns (loss_sum/M, stage_grads) with grads carrying the stage dim.
    """
    n_stages = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    B = x.shape[0]
    M = _feasible_microbatches(B, n_microbatches)
    micro = x.reshape(M, B // M, *x.shape[1:])
    tgt_micro = target.reshape(M, B // M, *target.shape[1:])

    R = 2 * n_stages - 1  # residual ring: max in-flight per stage
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    zero_mb = jnp.zeros_like(micro[0])
    ring0 = jnp.zeros((R,) + micro.shape[1:], micro.dtype)
    grad0 = jax.tree_util.tree_map(jnp.zeros_like, params)

    def step(carry, t):
        recv_f, recv_b, ring, gacc, loss_acc = carry

        # ---- F slot: stage s runs microbatch m_f = t - s ----
        m_f = t - stage
        f_active = jnp.logical_and(m_f >= 0, m_f < M)
        feed = jax.lax.dynamic_index_in_dim(
            micro, jnp.clip(m_f, 0, M - 1), keepdims=False
        )
        x_in = jnp.where(stage == 0, feed, recv_f)
        y_out = fn(params, x_in)
        # Bank this slot's input for the backward (ring-indexed by m).
        slot = jnp.clip(m_f, 0, M - 1) % R
        ring = jax.lax.dynamic_update_index_in_dim(
            ring,
            jnp.where(
                f_active,
                x_in,
                jax.lax.dynamic_index_in_dim(ring, slot, keepdims=False),
            ),
            slot,
            0,
        )

        # ---- B slot: stage s runs microbatch m_b = t - (2P-2-s) ----
        m_b = t - (2 * n_stages - 2 - stage)
        b_active = jnp.logical_and(m_b >= 0, m_b < M)
        bslot = jnp.clip(m_b, 0, M - 1) % R
        x_saved = jax.lax.dynamic_index_in_dim(ring, bslot, keepdims=False)
        tgt = jax.lax.dynamic_index_in_dim(
            tgt_micro, jnp.clip(m_b, 0, M - 1), keepdims=False
        )

        # Backward via remat'd vjp of this stage's forward — ONE stage
        # backward per tick: the cotangent is SELECTED first (last stage
        # seeds it from the loss of the microbatch it just finished — its
        # m_f == m_b this tick; other stages use the ring delivery).
        is_last = stage == n_stages - 1
        y_pred, pull_stage = jax.vjp(fn, params, x_saved)
        loss_here, pull_loss = jax.vjp(lambda yy: loss_fn(yy, tgt), y_pred)
        (dy_loss,) = pull_loss(jnp.ones_like(loss_here))
        dy = jnp.where(is_last, dy_loss, recv_b)
        dp, dx = pull_stage(dy)
        gacc = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(b_active, d, jnp.zeros_like(d)),
            gacc, dp,
        )
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(b_active, is_last), loss_here, 0.0
        )

        recv_f_next = jax.lax.ppermute(y_out, axis, perm_fwd)
        recv_b_next = jax.lax.ppermute(
            jnp.where(b_active, dx, jnp.zeros_like(dx)), axis, perm_bwd
        )
        return (recv_f_next, recv_b_next, ring, gacc, loss_acc), None

    T = M + 2 * n_stages - 2
    (_, _, _, gacc, loss_acc), _ = jax.lax.scan(
        step,
        (zero_mb, zero_mb, ring0, grad0, jnp.zeros(())),
        jnp.arange(T),
    )
    # Loss lives on the last stage; grads live per stage.  Broadcast the
    # loss; re-attach the stage dim to the grads.
    loss = jax.lax.psum(
        jnp.where(stage == n_stages - 1, loss_acc, 0.0), axis
    ) / M
    grads = jax.tree_util.tree_map(lambda g: (g / M)[None], gacc)
    return loss, grads


def pipeline_train_step_1f1b(
    fn: Callable,
    loss_fn: Callable,
    stacked_params,
    x: jax.Array,
    target,
    mesh: Mesh,
    *,
    n_microbatches: Optional[int] = None,
    axis: str = "pipeline",
    batch_axes: Sequence[str] = ("data", "fsdp"),
):
    """Fused 1F1B training step: returns (mean_loss, stacked_grads).

    Selectable alternative to differentiating pipeline_apply (GPipe): same
    numbers, bounded activation memory (see _1f1b_body).  `loss_fn(y,
    target) -> scalar` is the PER-MICROBATCH mean loss evaluated by the
    last stage; gradients come back with the leading stage dim, mean-
    normalized over microbatches, and psum'd over the batch axes (data-
    parallel reduction included, like any SPMD train step)."""
    n_stages = mesh.shape[axis]
    batch_axes = tuple(
        a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1
    )
    if n_microbatches is None:
        n_microbatches = _derive_microbatches(mesh, x, batch_axes, n_stages)

    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    xspec = P(batch_axes if batch_axes else None)

    def body(p, xx, tt):
        loss, grads = _1f1b_body(
            p, xx, tt, fn=fn, loss_fn=loss_fn,
            n_microbatches=n_microbatches, axis=axis,
        )
        # Data-parallel reduction over the batch axes.
        for a in batch_axes:
            loss = jax.lax.pmean(loss, a)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, a), grads
            )
        return loss, grads

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_spec, xspec, xspec),
        out_specs=(P(), param_spec),
        check_vma=False,
    )(stacked_params, x, target)
