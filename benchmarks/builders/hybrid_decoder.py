"""kind = "hybrid_decoder": Granite 4.0-H's stack (`model_type:
granitemoehybrid` with no experts): Mamba-2 and NoPE grouped-query attention
layers in the published order, a dense SwiGLU after each, muP multipliers on
the embeddings, the residual branches, the softmax and the logits, tied
embeddings; run through the program's `TransformerConfig` + `LMTrainContext`
like the other kinds.

The builder's four names, plus the scan's own count for `ssm_scan_roofline`.
Needed operations: every matmul weight once (Mamba-2's two projections, the
attention layers' four, the FFN, the tied head), causal attention in the
ATTENTION layers only, and the scan at the PUBLISHED chunk; recompute is
never credited.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.lib import flops

# Published (Hugging Face) key -> TransformerConfig field.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "shared_intermediate_size": "d_ff",  # the only FFN: the model has no experts
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "attention_multiplier": "attention_scale",
}
# What the program's Mamba-2 layer and attention express, and nothing else.
_REQUIRED = {
    "num_local_experts": 0, "mamba_n_groups": 1, "attention_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "hidden_act": "silu", "normalization_function": "rmsnorm",
}


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The kinds of the layers that run: the first `num_hidden_layers`
    entries of the published `layer_types`."""
    kinds = list(config["layer_types"])[: config["num_hidden_layers"]]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    return kinds


def mamba_heads(config: Dict[str, Any]) -> int:
    """`mamba_expand * hidden_size / mamba_d_head`: `mamba_n_heads` at the
    published width, and what follows the width in a rehearsal."""
    return config["mamba_expand"] * config["hidden_size"] // config["mamba_d_head"]


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"hybrid_decoder expresses {_REQUIRED} only, got {differ}")
    if flops.head_dim(config) * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("hybrid_decoder needs head_dim == hidden_size / num_attention_heads")
    kw = {field: config[key] for key, field in _KEYS.items()}
    nope = config["position_embedding_type"] == "nope"
    train = config["train"]
    kw.update(
        layer_types=tuple(layer_kinds(config)),
        ssm_heads=mamba_heads(config),
        rope_theta=None if nope else config["rope_theta"],
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],
    )
    return kw


def build(config: Dict[str, Any], seq_len: int, devices) -> Tuple[Any, Any]:
    """(TransformerConfig, LMTrainContext) on `devices` (the worker's chips,
    or a described topology's for an AOT compile)."""
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
    # `learning_rate`, where the file's `train` gives one: the logits are
    # divided by `logits_scaling` and Adam's step does not grow with the
    # gradient, so at the other cells' rate this head learns that much slower.
    rate = {"learning_rate": train["learning_rate"]} if "learning_rate" in train else {}
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer(**rate))
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference_hybrid

    return reference_hybrid.logits(config, params, tokens, last=last)


def matmul_params(config: Dict[str, Any]) -> int:
    """N_mm: per Mamba-2 layer `in_proj` (d -> 2*d_inner + 2*N + heads) and
    `out_proj`; per attention layer wq, wk, wv, wo; per layer the SwiGLU's
    three matrices; the tied head once; no embedding table."""
    d, hd = config["hidden_size"], flops.head_dim(config)
    heads = mamba_heads(config)
    inner = heads * config["mamba_d_head"]
    mamba = d * (2 * inner + 2 * config["mamba_d_state"] + heads) + inner * d
    attention = d * hd * (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])
    ffn = 3 * d * config["shared_intermediate_size"]
    kinds = layer_kinds(config)
    return (kinds.count("mamba") * mamba + kinds.count("attention") * attention + len(kinds) * ffn
            + d * config["vocab_size"])


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention, forward + backward, per token, over the
    ATTENTION layers only: `6 * S * H * D` each (`benchmarks/lib/flops.py`)."""
    one_layer = flops.attention_flops_per_token(dict(config, num_hidden_layers=1), seq_len)
    return layer_kinds(config).count("attention") * one_layer


def ssd_flops_per_token(config: Dict[str, Any]) -> float:
    """The selective scan in its chunked form at the PUBLISHED chunk Q (256),
    causal half, forward + backward (3x forward), per token, all Mamba-2
    layers, whatever chunk the program uses.  Forward per token and head:
    within a chunk `C B^T` and `scores @ x` over (Q+1)/2 ~ Q/2 causal
    positions, 2 flops a multiply-add: `Q*N + Q*P`; the chunk state
    `x (outer) B`, `2*P*N`; the entering state read out, `2*P*N`.  So
    `3 * heads * (Q*(N + P) + 4*P*N)` a layer (ISSUE 30's count).  It takes
    `C B^T` once per HEAD; with one group the heads share it, so this is an
    upper count by `3*Q*N*(heads - 1)` a layer: 39% of the scan's count, 0.9%
    of the cell's needed FLOPs (PERF.md section 7)."""
    q, n, p = config["mamba_chunk_size"], config["mamba_d_state"], config["mamba_d_head"]
    return layer_kinds(config).count("mamba") * 3.0 * mamba_heads(config) * (q * (n + p) + 4 * p * n)


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6*N_mm + attention (the attention layers) + the scan (the Mamba-2 layers)."""
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len) + ssd_flops_per_token(config)
