"""Language-model training step: loss, optimizer state, pjit factory.

This is the compiled SPMD "inner loop" that the Train library (ray_tpu.train)
drives from host actors — the TPU replacement for the reference's
DDP-wrapped user loop (python/ray/train/torch/train_loop_utils.py:92-98 +
NCCL allreduce).  Gradient reduction is not a runtime call: the mesh sharding
of params/batch makes XLA emit reduce-scatter/all-reduce over ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.moe import router_losses
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    forward_with_router_stats,
    init_params,
    param_axes,
)
from ray_tpu.parallel.mesh import build_mesh
from ray_tpu.parallel.sharding import (
    Rules,
    fit_shardings,
    logical_to_spec,
    resolve_rules,
    tree_shardings,
)
from ray_tpu.util import tracing
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cross_entropy_loss(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token cross entropy. logits [B,S,V] f32, targets [B,S]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


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
            z-loss (formulas in models/moe.py), and the terms unweighted."""
            logits, router_stats = forward_with_router_stats(
                params, batch["tokens"], cfg, rules=rules, mesh=self.mesh)
            with jax.named_scope("loss"):
                ce = cross_entropy_loss(logits, batch["targets"], batch.get("mask"))
                if router_stats is None:
                    return ce, {}
                terms = router_losses(router_stats, cfg)
                loss = (ce + cfg.router_aux_loss_coef * terms["moe_lb_loss"]
                        + cfg.router_z_loss_coef * terms["moe_z_loss"])
                return loss, {"ce_loss": ce, **terms}

        self._loss = _loss

        def _train_step(state, batch):
            (loss, terms), grads = jax.value_and_grad(_loss, has_aux=True)(
                state["params"], batch)
            with jax.named_scope("optimizer"):
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

    # -- public API -------------------------------------------------------
    def init_state(self, seed: int = 0) -> Dict[str, Any]:
        with tracing.annotate("init_state"), self.mesh:
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
        if not all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(batch)):
            with tracing.annotate("train_step/make_batch"):
                batch = self.make_batch(batch)
        with tracing.annotate("train_step/dispatch"), self.mesh:
            state, metrics = self._train_step(state, batch)
        return state, metrics

    def apply(self, params, tokens) -> jax.Array:
        with self.mesh:
            return self._forward(params, tokens)
