"""Language-model training step: loss, optimizer state, pjit factory.

This is the compiled SPMD "inner loop" that the Train library (ray_tpu.train)
drives from host actors — the TPU replacement for the reference's
DDP-wrapped user loop (python/ray/train/torch/train_loop_utils.py:92-98 +
NCCL allreduce).  Gradient reduction is not a runtime call: the mesh sharding
of params/batch makes XLA emit reduce-scatter/all-reduce over ICI.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.mixers import MIXERS
from ray_tpu.models.moe import router_losses
from ray_tpu.models.transformer import (
    LOGITS_AXES,
    TransformerConfig,
    _constrainer,
    check_placement,
    forward,
    init_params,
    mtp_forward,
    mtp_rows,
    param_axes,
    trunk,
)
from ray_tpu.parallel.mesh import build_mesh
from ray_tpu.parallel.sharding import (
    Rules,
    fit_shardings,
    logical_to_spec,
    resolve_rules,
    tree_shardings,
)
from ray_tpu.train.run_record import DISPATCH, MAKE_BATCH, StepClock, note_step_counters
from ray_tpu.util import tracing
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cross_entropy_loss(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token cross entropy of logits in hand: logits [B,S,V] (any
    float dtype; the softmax runs in theirs), targets [B,S], mask [B,S] or
    None.  The plain form: differentiated by JAX it keeps a [B,S,V]
    log-softmax and scatters into a [B,S,V] zero tensor.  A training step
    uses `head_cross_entropy`, which this is the reference of."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def head_cross_entropy(constrain, x, head, targets, mask):
    """`cross_entropy_loss(einsum(x, head).astype(f32), targets, mask)` as ONE
    function with its backward written by hand: x [B,S,d] and head [d,V] in
    the model's dtype (`transformer.trunk`'s), targets [B,S], mask [B,S] or
    None -> the float32 scalar.

    The only [B,S,V] arrays that reach HBM are in x's dtype: the logits (the
    matmul's result, rounded as `forward` rounds it) and, in the backward,
    their cotangent.  Row max, log-sum-exp, the softmax and the loss are
    float32 inside the passes that read them; the target's logit is picked by
    compare-and-select against the column index (a gather's transpose is a
    scatter into a [B,S,V] zero tensor).  Residuals: x, head, the logits, the
    float32 [B,S] log-sum-exp.  `constrain` places the logits and their
    cotangent as the sharding rules say (`transformer._constrainer`).  The
    matmuls are named `lm_head`, the passes `loss`, in both directions."""
    return _head_cross_entropy_fwd(constrain, x, head, targets, mask)[0]


def _target_columns(logits, targets):
    """bool [B,S,V]: the target's column in each row."""
    return jax.lax.broadcasted_iota(targets.dtype, logits.shape, 2) == targets[..., None]


def _row_weights(targets, mask):
    """float32 [B,S]: what a row's negative log-likelihood weighs in the loss."""
    if mask is None:
        return jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
    mask = mask.astype(jnp.float32)
    return mask / jnp.maximum(jnp.sum(mask), 1.0)


def _head_cross_entropy_fwd(constrain, x, head, targets, mask):
    with tracing.scope("lm_head"):
        logits = constrain(jnp.einsum("bse,ev->bsv", x, head), LOGITS_AXES)
    with tracing.scope("loss"):
        wide = logits.astype(jnp.float32)
        row_max = jnp.max(wide, axis=-1)
        lse = row_max + jnp.log(jnp.sum(jnp.exp(wide - row_max[..., None]), axis=-1))
        target_logit = jnp.sum(jnp.where(_target_columns(logits, targets), wide, 0.0), axis=-1)
        loss = jnp.sum((lse - target_logit) * _row_weights(targets, mask))
    return loss, (x, head, logits, lse, targets, mask)


def _head_cross_entropy_bwd(constrain, residuals, g):
    x, head, logits, lse, targets, mask = residuals
    with tracing.scope("loss"):
        # Not behind a barrier: XLA fuses this pass into the operand of both
        # matmuls below (the softmax computed twice, in registers) and never
        # writes the cotangent, which measured 3.9 ms a step faster than
        # writing it once (granite-h-micro's cell, PERF.md section 6, PR 34).
        softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        dlogits = jnp.where(_target_columns(logits, targets), softmax - 1.0, softmax)
        dlogits = dlogits * (g * _row_weights(targets, mask))[..., None]
        dlogits = constrain(dlogits.astype(logits.dtype), LOGITS_AXES)
    with tracing.scope("lm_head"):
        dx = jnp.einsum("bsv,ev->bse", dlogits, head)
        dhead = jnp.einsum("bse,bsv->ev", x, dlogits)
    return dx, dhead, None, None


head_cross_entropy.defvjp(_head_cross_entropy_fwd, _head_cross_entropy_bwd)


# The step counters of the run's record (train/run_record.py), among the step's metrics: the key tiles a
# windowed flash forward visits and the grid steps of a causal one that copy (PR 55); what a layer that holds a
# share of its experts was given (`models/moe.py` `router_losses`: rows per held expert, mean and busiest, the
# busiest expert's load over the mean, and the share of the T*K assignments whose rows the share's buffers moved);
# the multi-token-prediction module's cross entropy.
WINDOW_TILES = "attn_window_tiles_visited_pct"
CAUSAL_STEPS = "attn_causal_steps_copying_pct"
STEP_COUNTERS = (WINDOW_TILES, CAUSAL_STEPS, "moe_held_rows_mean", "moe_held_rows_max", "moe_load_max_over_mean", "moe_rows_moved_share",
                 "mtp_loss")


def _window_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`WINDOW_TILES`: the key tiles the windowed flash forward visits over
    those a causal call would, the mean over the windowed layers, from the
    block sizes in use at this length (what the kernels would do: off TPU the
    XLA forms run and visit no tile).  Known when the step is traced; nothing
    for a model without a window or a length no tile divides."""
    from ray_tpu.ops.pallas.flash_attention import window_tiles_visited_pct

    visited = [window_tiles_visited_pct(seq, w) for w in config.layer_windows or () if w is not None]
    if not visited or None in visited:
        return {}
    return {WINDOW_TILES: sum(visited) / len(visited)}


def _causal_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`CAUSAL_STEPS`: the grid steps of the causal flash forward that make
    its pipeline copy a key and a value tile, as % of a head's steps
    (`flash_attention.causal_steps_copying_pct`, from the map the kernel is
    given), the mean over the layers whose core is a causal attention call
    without a window (`Mixer.flash_heads`), at the tiles their head sizes
    give at this length.  Known when the step is traced, as `WINDOW_TILES`,
    and like it a statement about the kernels at this length: it is noted
    whichever form the dispatch gives the step (`ops.attention`; off the
    chip and under the ring no flash kernel runs).  Nothing for a model
    without such a layer or a length no tile divides."""
    from ray_tpu.ops.pallas.flash_attention import causal_forward_tiles, causal_steps_copying_pct

    heads = [MIXERS[mixer].flash_heads(config) for i, (mixer, _) in enumerate(config.layer_pairs())
             if config.layer_variant(i)[0] is None]
    tiles = [causal_forward_tiles(seq, *sizes) for sizes in heads if sizes is not None]
    if not tiles or None in tiles:
        return {}
    copying = {t: causal_steps_copying_pct(seq, *t, keys=True) for t in set(tiles)}  # once a tiling, not a layer
    return {CAUSAL_STEPS: sum(copying[t] for t in tiles) / len(tiles)}


def _mtp_term(params, h, head, batch, config, rules, mesh):
    """(the multi-token-prediction module's cross entropy, its block's router
    statistics or None).  With `batch["targets"]` the token after each
    position, t_{i+1}, the module takes their embeddings beside the trunk's
    output `h` (`transformer.mtp_rows`) and its rows go through the model's
    own `head` against the targets shifted once more, t_{i+2}: the mean over
    the positions that have one (the last has none) and the batch's mask
    admits.  Head and loss are named inside `mtp`."""
    targets = batch["targets"]
    x, stats = mtp_rows(params, h, targets, config, rules=rules, mesh=mesh)
    after_next = jnp.roll(targets, -1, axis=1)
    has_one = jnp.broadcast_to(jnp.arange(targets.shape[1]) < targets.shape[1] - 1, targets.shape)
    if batch.get("mask") is not None:
        has_one = has_one & (jnp.roll(batch["mask"], -1, axis=1) > 0)
    with tracing.scope("mtp"):
        return head_cross_entropy(_constrainer(rules, mesh), x, head, after_next, has_one), stats


# What jax calls the three programs `LMTrainContext` builds (the `fun_name` of their `jax::trace` /
# `jax::lower` / `jax::compile` spans in the run's record, `jit(...)` stripped), and what a reader of the
# record may call them.
PROGRAMS = {"_init": "init", "_forward": "apply", "_train_step": "step"}


def default_optimizer(
    learning_rate: float = 3e-4, weight_decay: float = 0.1, **kw
) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay, **kw),
    )


class LMTrainContext:
    """Sharded init/train-step bundle for one (config, mesh, rules) triple.

    Holds the jitted functions with in/out shardings attached so the host
    code never calls device_put by hand.
    """

    def __init__(
        self,
        config: TransformerConfig,
        mesh: Optional[Mesh] = None,
        strategy: str | Rules = "fsdp",
        optimizer: Optional[optax.GradientTransformation] = None,
    ):
        self.config = config
        self.mesh = mesh if mesh is not None else build_mesh()
        self.rules = resolve_rules(strategy)
        self.optimizer = optimizer or default_optimizer()
        self._step_clock = StepClock()
        check_placement(config, self.rules, self.mesh)  # refuse by name now, not when the step is traced

        raw_shardings = tree_shardings(param_axes(config), self.rules, self.mesh)
        abstract_params = jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0)))
        self.param_shardings = fit_shardings(abstract_params, raw_shardings)
        # Optimizer state must be PINNED to the param shardings, not left to
        # propagation: XLA happily replicates adam moments (measured with
        # pp_fsdp), silently forfeiting the ZeRO optimizer-state sharding
        # that is fsdp's whole memory win.  Optax states mirror the param
        # tree, so a moment leaf is matched to its parameter by tree PATH:
        # the tail of its path is the parameter's (shapes cannot tell an
        # expert layer's `w_gate` from its `w_up`).  Leaves that mirror no
        # parameter (step counts) are replicated.
        self.repl = NamedSharding(self.mesh, P())
        by_path = {
            path: (pleaf.shape, psh)
            for (path, pleaf), psh in zip(
                jax.tree_util.tree_flatten_with_path(abstract_params)[0],
                jax.tree_util.tree_leaves(self.param_shardings),
            )
        }

        def pin(path, leaf):
            for start in range(len(path)):
                shape, sharding = by_path.get(path[start:], (None, None))
                if shape == leaf.shape:
                    return sharding
            return self.repl if leaf.ndim == 0 else None

        abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
        self.opt_shardings = jax.tree_util.tree_map_with_path(pin, abstract_opt)
        self.batch_sharding = NamedSharding(
            self.mesh, logical_to_spec(("act_batch", "act_seq"), self.rules)
        )

        cfg, rules, opt = self.config, self.rules, self.optimizer

        def _init(key):
            params = init_params(cfg, key)
            opt_state = opt.init(params)
            return {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}

        self._init = jax.jit(
            _init,
            out_shardings={
                "params": self.param_shardings,
                "opt_state": self.opt_shardings,
                "step": self.repl,
            },
        )

        def _loss(params, batch):
            """(the objective that is differentiated, its terms).  Dense: the
            cross entropy and no terms.  With experts: cross entropy +
            `router_aux_loss_coef` * load balancing + `router_z_loss_coef` *
            z-loss (formulas in models/moe.py), and the terms unweighted.
            With `mtp_depth`: + `mtp_loss_weight` * the module's cross entropy
            (`_mtp_term`), `ce_loss` and `mtp_loss` among the terms, the
            module's block one more layer of the router statistics.  Beside the
            terms ride the attention kernels' counters, constants of the
            traced step (`_window_counters`, `_causal_counters`)."""
            constrain = _constrainer(rules, self.mesh)
            x, head, router_stats = trunk(
                params, batch["tokens"], cfg, rules=rules, mesh=self.mesh)
            ce = head_cross_entropy(constrain, x, head, batch["targets"], batch.get("mask"))
            mtp = {}
            if cfg.mtp_depth:
                mtp["mtp_loss"], stats = _mtp_term(params, x, head, batch, cfg, rules, self.mesh)
                if stats is not None:
                    router_stats = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b], axis=0), router_stats, stats)
            with tracing.scope("loss"):
                seq = batch["tokens"].shape[1]
                counters = {**_window_counters(cfg, seq), **_causal_counters(cfg, seq)}
                loss = ce + cfg.mtp_loss_weight * mtp["mtp_loss"] if mtp else ce
                if router_stats is None:
                    return loss, {"ce_loss": ce, **mtp, **counters} if mtp else counters
                terms = router_losses(router_stats, cfg)
                loss = (loss + cfg.router_aux_loss_coef * terms["moe_lb_loss"]
                        + cfg.router_z_loss_coef * terms["moe_z_loss"])
                return loss, {"ce_loss": ce, **mtp, **terms, **counters}

        self._loss = _loss

        def _train_step(state, batch):
            # On the host only (no op's name changes): what runs after `_loss` returns, the backward
            # pass's transposition and partial evaluation, is under a name in the trace's `scopes`.
            with tracing.scope("autodiff", host_only=True):
                (loss, terms), grads = jax.value_and_grad(_loss, has_aux=True)(
                    state["params"], batch)
            with tracing.scope("optimizer"):
                updates, opt_state = opt.update(grads, state["opt_state"], state["params"])
                params = optax.apply_updates(state["params"], updates)
                grad_norm = optax.global_norm(grads)
            metrics = {
                "loss": loss,
                "grad_norm": grad_norm,
                "step": state["step"] + 1,
                **terms,
            }
            return (
                {"params": params, "opt_state": opt_state, "step": state["step"] + 1},
                metrics,
            )

        # State out_shardings pinned, not propagated: GSPMD was measured to
        # replicate adam moments when left to choose, silently forfeiting
        # ZeRO optimizer-state sharding after the first step.
        self._train_step = jax.jit(
            _train_step,
            out_shardings=(
                {
                    "params": self.param_shardings,
                    "opt_state": self.opt_shardings,
                    "step": self.repl,
                },
                self.repl,
            ),
            donate_argnums=(0,),
        )

        def _forward(params, tokens):
            return forward(params, tokens, cfg, rules=rules, mesh=self.mesh)

        self._forward = jax.jit(_forward)

        def _forward_mtp(params, tokens, next_tokens):
            return mtp_forward(params, tokens, next_tokens, cfg, rules=rules, mesh=self.mesh)

        self._forward_mtp = jax.jit(_forward_mtp)

    # -- public API -------------------------------------------------------
    def init_state(self, seed: int = 0) -> Dict[str, Any]:
        # A lifecycle span too: once per run, and in every run's record.
        with tracing.annotate("init_state", lifecycle=True), self.mesh:
            return self._init(jax.random.PRNGKey(seed))

    def make_batch(self, batch) -> Dict[str, jax.Array]:
        """Shard a host batch (pytree of [B, S] numpy arrays, every process
        holding the same global batch) onto the mesh.  make_array_from_callback
        hands each device its shard, which also works when the mesh spans
        processes (multi-host SPMD)."""
        import numpy as np

        def put(x):
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, self.batch_sharding, lambda idx: x[idx]
            )

        return jax.tree_util.tree_map(put, batch)

    def train_step(self, state, batch) -> Tuple[Dict, Dict]:
        # Always on: the step's period, entry to entry, for the run record's
        # stalled steps (train/run_record.py); no span unless tracing is on.
        clock = self._step_clock
        clock.enter()
        if not all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(batch)):
            t0 = time.perf_counter()
            with tracing.annotate("train_step/make_batch"):
                batch = self.make_batch(batch)
            clock.mark(MAKE_BATCH, t0)
        t0 = time.perf_counter()
        with tracing.annotate("train_step/dispatch"), self.mesh:
            state, metrics = self._train_step(state, batch)
        clock.mark(DISPATCH, t0)
        # counters of the run's record: kept as device scalars, fetched at a poll, under the index of
        # this call (the clock's count of periods closed: 0 at the first)
        counters = {k: metrics[k] for k in STEP_COUNTERS if k in metrics}
        if counters:
            note_step_counters(counters, step=clock.steps)
        return state, metrics

    def apply(self, params, tokens) -> jax.Array:
        with self.mesh:
            return self._forward(params, tokens)

    def apply_mtp(self, params, tokens, next_tokens) -> jax.Array:
        """The multi-token-prediction module's logits [B, S, V] for the token
        after the next (`mtp_depth` 1), from `tokens` and the token after each."""
        if not self.config.mtp_depth:
            raise ValueError("apply_mtp needs a model with a multi-token-prediction module (mtp_depth=1)")
        with self.mesh:
            return self._forward_mtp(params, tokens, next_tokens)
