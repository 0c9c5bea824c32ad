"""kind = "kimi_linear_decoder": Kimi Linear's stack (`model_type:
kimi_linear`): Kimi Delta Attention layers 3:1 with NoPE latent attention,
one leading dense SwiGLU layer and then expert layers (sigmoid router with a
stored bias, renormalised top-k times `routed_scaling_factor`, one shared
expert); run through the program's `TransformerConfig` + `LMTrainContext`
like the other kinds.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`num_experts` counts the experts HELD here (`share.first_expert_held` on),
the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_token` choices, `vocab_size` is this chip's slice.  Nothing
here or in the program stands in for the absent chips.

The builder's four names, plus the counts for `kda_scan_roofline` and
`mla_attn_roofline`.  Needed operations count ACTIVE matmul weights: every
matmul weight of the mixers, the router, the shared expert and the head once;
the routed experts at the expectation of a uniform router over ALL experts,
`num_experts_per_token * num_experts / num_experts_total` rows a token (0.5
here: a token's 8 choices fall among the 16 held of 256 with probability 1/16
each); causal attention in the MLA layers only, at the mean of its two head
sizes; the KDA recurrence in its chunked form at chunk 64.  Recompute is never
credited.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.lib import reference_kimi_linear

KDA_CHUNK = 64  # the published kernels' chunk (`fla.ops.kda`), what the scan's needed FLOPs are counted at

# What the program's layers express, and nothing else.
_REQUIRED = {
    "hidden_act": "silu", "mla_use_nope": True, "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
    "num_expert_group": 1, "topk_group": 1, "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "tie_word_embeddings": False,
}

layer_pairs = reference_kimi_linear.layer_pairs


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"kimi_linear_decoder expresses {_REQUIRED} only, got {differ}")
    linear, share, train = config["linear_attn_config"], config["share"], config["train"]
    pairs = layer_pairs(config)
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=None,  # `mla_use_nope`: a key of the source the model does not use
        layer_types=tuple(m for m, _ in pairs),
        ffn_types=tuple(f for _, f in pairs),
        kda_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=share["num_experts_total"],
        n_experts_held=config["num_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_token"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        norm_topk_prob=config["moe_renormalize"],
        router_activation="sigmoid",
        routed_scaling_factor=config["routed_scaling_factor"],
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],
    )


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
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"], optimizer=default_optimizer())
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    return reference_kimi_linear.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one mixer or FFN of each kind."""
    d, linear = config["hidden_size"], config["linear_attn_config"]
    inner, dim = linear["num_heads"] * linear["head_dim"], linear["head_dim"]
    heads, nope, rope = config["num_attention_heads"], config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, v = config["kv_lora_rank"], config["v_head_dim"]
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        # q, k, v, o; the two low-rank gates; beta
        "kda": 4 * d * inner + 2 * (d * dim + dim * inner) + d * linear["num_heads"],
        "mla": d * heads * (nope + rope) + d * (rank + rope) + rank * heads * (nope + v) + heads * v * d,
        "dense": 3 * d * config["intermediate_size"],
        "router": d * config["share"]["num_experts_total"],
        "shared": config["num_shared_experts"] * expert,
        "expert": expert,
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves of one mixer or expert layer that multiply nothing."""
    linear = config["linear_attn_config"]
    inner = linear["num_heads"] * linear["head_dim"]
    return {
        # three convolutions, A_log, dt_bias, the gated norm's scale
        "kda": 3 * inner * linear["short_conv_kernel_size"] + linear["num_heads"] + inner + linear["head_dim"],
        "mla": config["kv_lora_rank"],  # the latent's norm
        "experts": config["share"]["num_experts_total"],  # e_score_correction_bias
    }


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer, every expert, every row)."""
    d, share = config["hidden_size"], config["share"]
    if uncut:
        config = dict(config, num_hidden_layers=share["num_hidden_layers_total"],
                      num_experts=share["num_experts_total"], vocab_size=share["vocab_size_total"])
    sizes, other = _sizes(config), _other_params(config)
    total = 2 * d * config["vocab_size"] + d  # embedding, head, final norm
    for mixer, ffn in layer_pairs(config):
        total += sizes[mixer] + other[mixer] + 2 * d
        if ffn == "dense":
            total += sizes["dense"]
        else:
            total += sizes["router"] + other["experts"] + sizes["shared"] + config["num_experts"] * sizes["expert"]
    return total


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over all
    experts: K * held / total (0.5 at 8 of 256 with 16 held)."""
    return config["num_experts_per_token"] * config["num_experts"] / config["share"]["num_experts_total"]


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul weights a token multiplies (module docstring); no embedding table."""
    sizes = _sizes(config)
    total = float(config["hidden_size"] * config["vocab_size"])
    for mixer, ffn in layer_pairs(config):
        total += sizes[mixer]
        if ffn == "dense":
            total += sizes["dense"]
        else:
            total += sizes["router"] + sizes["shared"] + routed_rows_per_token(config) * sizes["expert"]
    return total


def mla_layers(config: Dict[str, Any]) -> int:
    return sum(1 for mixer, _ in layer_pairs(config) if mixer == "mla")


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention, forward + backward, per token, over the MLA
    layers only.  `benchmarks/lib/flops.py`'s count, `6 * S * H * D` a layer,
    with D the mean of the q/k head size and the v head size: QK^T runs over
    `nope + rope` (192), PV over `v_head_dim` (128), so `3 * S * H * (192 +
    128)`.  The readers that divide this by `num_hidden_layers` (the three
    `flash_*_roofline`) read a fifth of the truth in this configuration, whose
    flash kernels all lie in one layer of five; `mla_attn_roofline` is the
    share that means what it says here."""
    d_mean = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]) / 2
    return mla_layers(config) * 6.0 * seq_len * config["num_attention_heads"] * d_mean


def kda_scan_flops_per_token(config: Dict[str, Any]) -> float:
    """The KDA recurrence in its chunked form at chunk C = 64, causal half
    where a product is triangular, forward + backward (3x forward), per token,
    all KDA layers, whatever chunk the program uses.  Forward per token and
    head, K = V = `head_dim`, 2 flops a multiply-add: the two [C, C] matrices
    `k k^T` and `q k^T` over C/2 causal positions each, `2 * C*K`; the unit
    lower-triangular solve for K + V right-hand columns, `C * (K + V)`; the
    causal `qk @ U`, `C * V`; and four [K, V] products with the chunk state,
    `W S`, `(q * decay) S`, `K^T U` and the state's own decay-and-add, counted
    as three matmuls, `6 * K * V`.  So `3 * H * (C * (3K + 2V) + 6 * K * V)` a
    layer.  The decays themselves (exponentials, one per channel and level)
    are not matmul work and are not counted."""
    linear = config["linear_attn_config"]
    k = v = linear["head_dim"]
    per_head = KDA_CHUNK * (3 * k + 2 * v) + 6 * k * v
    kda = sum(1 for mixer, _ in layer_pairs(config) if mixer == "kda")
    return kda * 3.0 * linear["num_heads"] * per_head


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights + attention (the MLA layers) + the KDA recurrence."""
    return (6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)
            + kda_scan_flops_per_token(config))
