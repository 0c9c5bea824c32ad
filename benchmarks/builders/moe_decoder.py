"""kind = "moe_decoder": a pre-norm RMSNorm / RoPE / QK-norm decoder whose
every FFN is a dropless top-k mixture of SwiGLU experts (OLMoE), run through
the program's `TransformerConfig` + `LMTrainContext` like the dense kind.

The builder's four names, plus the expert layer's own counts for
`moe_experts_roofline`.  Needed operations count ACTIVE matmul weights: a
token multiplies its K experts, not all E; the router is a matmul and
counts; recompute is never credited.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.builders import dense_decoder
from benchmarks.lib import flops

# Published (Hugging Face) key -> TransformerConfig field, beside the dense kind's.
_KEYS = {
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "router_aux_loss_coef": "router_aux_loss_coef",
    "router_z_loss_coef": "router_z_loss_coef",
}


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    if config.get("clip_qkv") is not None or config.get("attention_bias"):
        raise ValueError("moe_decoder expresses no qkv clipping and no attention bias")
    kw = dense_decoder.model_kwargs(config, seq_len)  # d_ff = ONE expert's width
    kw.update({field: config[key] for key, field in _KEYS.items()})
    kw["qk_norm"] = True
    return kw


def build(config: Dict[str, Any], seq_len: int, devices) -> Tuple[Any, Any]:
    """(TransformerConfig, LMTrainContext) on `devices` (the worker's chips,
    or a described topology's for an AOT compile); `dense_decoder.build` with
    this kind's keyword arguments."""
    import jax.numpy as jnp

    from ray_tpu.models import LMTrainContext, TransformerConfig, default_optimizer
    from ray_tpu.parallel import MeshSpec, build_mesh

    kw = model_kwargs(config, seq_len)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    cfg = TransformerConfig(**kw)
    train = config["train"]
    if train["optimizer"] != "default_optimizer":
        raise ValueError(f"unknown optimizer {train['optimizer']!r}")
    mesh = build_mesh(MeshSpec(**train["mesh"]), devices=list(devices)[:train["chips"]])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer())
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference_moe

    return reference_moe.logits(config, params, tokens, last=last)


def active_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul weights a token multiplies: per layer wq, wk, wv, wo, the
    router and K experts' three matrices; `lm_head`; no embedding table."""
    d, hd = config["hidden_size"], flops.head_dim(config)
    attn = d * hd * (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])
    router = d * config["num_experts"]
    experts = config["num_experts_per_tok"] * 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + router + experts) + d * config["vocab_size"]


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6*(L*(4*d^2 + d*E + K*3*d*F) + d*V) + 6*L*S*H*D for MHA at
    head_dim * heads = d (GQA narrows wk/wv as in the dense count)."""
    return 6.0 * active_matmul_params(config) + flops.attention_flops_per_token(config, seq_len)


attention_flops_per_token = flops.attention_flops_per_token


def expert_flops_per_token(config: Dict[str, Any]) -> float:
    """The three grouped matmuls of every layer, forward + backward, per token."""
    return (6.0 * config["num_hidden_layers"] * config["num_experts_per_tok"] * 3
            * config["hidden_size"] * config["intermediate_size"])


def expert_weight_bytes(config: Dict[str, Any], bytes_per_weight: int = 2) -> int:
    """Bytes of ALL experts' weights of every layer: what a step reads at
    least once per direction, whatever the routing."""
    return (config["num_hidden_layers"] * config["num_experts"] * 3 * config["hidden_size"]
            * config["intermediate_size"] * bytes_per_weight)
