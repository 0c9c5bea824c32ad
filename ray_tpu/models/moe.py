"""Mixture-of-Experts block: expert-parallel FFN for the transformer.

No counterpart in the reference (SURVEY §2.4: EP absent) — built TPU-first:
experts live on a leading `expert` dim sharded over the `expert` mesh axis
(ep_rules, parallel/sharding.py); routing is top-k softmax gating and the
token shuffle compiles to all-to-alls over ICI when XLA partitions the
gather/scatter by expert.

Dense-compute formulation (einsum over a one-hot dispatch mask rather than
ragged gather): identical math to token-dropping MoE with capacity, and
every op is a static-shape matmul the MXU likes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import Rules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    d_model: int = 64
    d_ff: int = 128
    # tokens each expert processes per batch = capacity_factor * T * k / E
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01  # load-balance loss weight (Switch-style)


def moe_param_axes(cfg: MoEConfig) -> Dict:
    return {
        "router": ("embed", "expert"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def init_moe_params(cfg: MoEConfig, key: jax.Array, dtype=jnp.float32) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = cfg.d_model ** -0.5
    return {
        "router": (jax.random.normal(k1, (cfg.d_model, cfg.n_experts)) * scale).astype(dtype),
        "w_gate": (jax.random.normal(k2, (cfg.n_experts, cfg.d_model, cfg.d_ff)) * scale).astype(dtype),
        "w_up": (jax.random.normal(k3, (cfg.n_experts, cfg.d_model, cfg.d_ff)) * scale).astype(dtype),
        "w_down": (jax.random.normal(k4, (cfg.n_experts, cfg.d_ff, cfg.d_model)) * scale).astype(dtype),
    }


@jax.named_scope("layer/mlp")  # the transformer layer's FFN scope (PERF.md section 3)
def moe_ffn(
    params: Dict,
    x: jax.Array,
    cfg: MoEConfig,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] → (y [B, S, D], aux_loss scalar).

    Dispatch: top-k router → per-expert capacity-limited one-hot combine
    tensor → einsum dispatch/experts/combine.  With ep_rules the expert dim
    of params+intermediates shards over the `expert` axis and XLA inserts
    the token all-to-alls.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    capacity = max(int(cfg.capacity_factor * T * K / E), K)

    def constrain(h, axes):
        if rules is None:
            return h
        return with_logical_constraint(h, axes, rules, mesh)

    tokens = x.reshape(T, D)
    logits = tokens @ params["router"].astype(x.dtype)  # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # Top-k expert choice per token.
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # Capacity: position of each token within its chosen expert's queue;
    # tokens past capacity drop (standard Switch behavior).
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, K, E]
    position_in_expert = (
        jnp.cumsum(onehot.reshape(T * K, E), axis=0).reshape(T, K, E) - 1.0
    )
    within_cap = position_in_expert < capacity
    onehot = onehot * within_cap

    # combine [T, E, C]: weight of each token at its slot in each expert.
    pos = jnp.einsum("tke,tke->tk", position_in_expert, onehot).astype(jnp.int32)
    slot_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T,K,C]
    combine = jnp.einsum(
        "tk,tke,tkc->tec", gate_vals.astype(jnp.float32), onehot, slot_onehot
    )
    dispatch = (combine > 0).astype(x.dtype)  # [T, E, C]

    # Expert compute: [E, C, D] batched matmuls, expert dim sharded.
    expert_in = jnp.einsum("td,tec->ecd", tokens, dispatch)
    expert_in = constrain(expert_in, ("act_expert", None, "act_embed"))
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))
    expert_out = constrain(expert_out, ("act_expert", None, "act_embed"))

    y = jnp.einsum("ecd,tec->td", expert_out, combine.astype(x.dtype))

    # Switch load-balance aux loss: E * sum_e(frac_tokens_e * frac_probs_e).
    frac_tokens = onehot[:, 0, :].mean(axis=0)  # top-1 assignment share
    frac_probs = probs.mean(axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(frac_tokens * frac_probs)

    return y.reshape(B, S, D).astype(x.dtype), aux
