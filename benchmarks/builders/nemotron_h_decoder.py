"""kind = "nemotron_h_decoder": Nemotron-H's stack (`model_type: nemotron_h`,
NVIDIA-Nemotron-3-Nano-30B-A3B): blocks that are ONE of a Mamba-2 mixer (8 B/C
groups), a GQA attention without rotary embedding (head size 128 on a
2688-wide stream) or an expert layer alone (sigmoid router with a stored
bias, renormalised top-6 times `routed_scaling_factor`, two-matrix relu2
experts, a shared expert of a width of its own); run through the program's
`TransformerConfig` + `LMTrainContext` like the other kinds, which PAIRS the
blocks (`reference_nemotron_h.layer_pairs`: `M E` -> (mamba, experts), `* E`
-> (attention, experts), an `M` with no `E` behind it -> (mamba, none)).

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`n_routed_experts` counts the experts HELD here (`share.first_expert_held`
on), the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_tok` choices, `vocab_size` is this chip's slice.  Nothing
here or in the program stands in for the absent chips.

The builder's four names, plus the counts the cell's rooflines are made of.
Needed operations count ACTIVE matmul weights: every matmul weight of the
mixers, the router, the shared expert and the head once; the routed experts
at the expectation of a uniform router over ALL experts, `num_experts_per_tok
* n_routed_experts / num_experts_total` rows a token (0.75 here); causal
attention in the `*` blocks only; the selective scan in its grouped chunked
form at the published chunk, `C B^T` once a GROUP.  Recompute is never
credited.  `relu2_experts_roofline` does NOT use the expectation: it counts
the rows the traced steps gave the held experts (`expert_matmul_flops`).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.lib import reference_nemotron_h

# What the program's layers express, and nothing else.
_REQUIRED = {
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "tie_word_embeddings": False, "attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
    "use_bias": False, "use_conv_bias": True, "sliding_window": None, "residual_in_fp32": False,
}

pattern = reference_nemotron_h.pattern
layer_pairs = reference_nemotron_h.layer_pairs


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"nemotron_h_decoder expresses {_REQUIRED} only, got {differ}")
    share, train = config["share"], config["train"]
    pairs = layer_pairs(config)
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=len(pairs),  # the program's layers are the PAIRS; `num_hidden_layers` counts single blocks
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],  # no block of the model is a dense FFN; read by none
        norm_eps=config["layer_norm_epsilon"],
        tie_embeddings=False,
        rope_theta=None,  # `rope_theta`, `partial_rotary_factor`: keys the nemotron_h attention does not use
        layer_types=tuple(m for m, _ in pairs),
        ffn_types=tuple(f for _, f in pairs),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"],
        ssm_conv=config["conv_kernel"],
        ssm_groups=config["n_groups"],
        n_experts=share["num_experts_total"],
        n_experts_held=config["n_routed_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        shared_expert_d_ff=config["moe_shared_expert_intermediate_size"],
        expert_kind="relu2",
        routed_branch_init=True,  # `assumed.initial_values`: the six routed outputs of a token start as ONE residual branch
        norm_topk_prob=True,
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
    return reference_nemotron_h.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def mamba_sizes(config: Dict[str, Any]) -> Tuple[int, int]:
    """(d_inner, the channels the convolution runs over): heads x head size (`expand` is not used), + 2 G N."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    return inner, inner + 2 * config["n_groups"] * config["ssm_state_size"]


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one block of each kind (`E`: without its routed experts) and of one routed expert."""
    d = config["hidden_size"]
    inner, conv = mamba_sizes(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "M": d * (inner + conv + config["mamba_num_heads"]) + inner * d,  # in_proj, out_proj
        "*": 2 * d * q + 2 * d * kv,  # q, o; k, v
        "router": d * config["share"]["num_experts_total"],
        "shared": config["n_shared_experts"] * 2 * d * config["moe_shared_expert_intermediate_size"],
        "expert": 2 * d * config["moe_intermediate_size"],
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves of one block that multiply nothing, its own norm's scale apart."""
    inner, conv = mamba_sizes(config)
    return {
        # the convolution and its bias; dt_bias, A_log, D; the gated norm's scale
        "M": conv * config["conv_kernel"] + conv + 3 * config["mamba_num_heads"] + inner,
        "*": 0,
        "E": config["share"]["num_experts_total"],  # e_score_correction_bias
    }


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every block, every expert, every row)."""
    d, share = config["hidden_size"], config["share"]
    if uncut:
        config = dict(config, num_hidden_layers=share["num_hidden_layers_total"],
                      n_routed_experts=share["num_experts_total"], vocab_size=share["vocab_size_total"])
    sizes, other = _sizes(config), _other_params(config)
    total = 2 * d * config["vocab_size"] + d  # embedding, head, final norm
    for kind in pattern(config):
        total += other[kind] + d  # and the block's own norm
        if kind == "E":
            total += sizes["router"] + sizes["shared"] + config["n_routed_experts"] * sizes["expert"]
        else:
            total += sizes[kind]
    return total


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over
    all experts: K * held / total (0.75 at 6 of 128 with 16 held)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["share"]["num_experts_total"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding table)."""
    sizes, blocks = _sizes(config), pattern(config)
    experts = blocks.count("E")
    return {
        "mamba_proj": float(blocks.count("M") * sizes["M"]),
        "attn_proj": float(blocks.count("*") * sizes["*"]),
        "router": float(experts * sizes["router"]),
        "shared_expert": float(experts * sizes["shared"]),
        "routed_experts": experts * routed_rows_per_token(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def active_matmul_params(config: Dict[str, Any]) -> float:
    return sum(matmul_params_by_part(config).values())


def attention_layers(config: Dict[str, Any]) -> int:
    return pattern(config).count("*")


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention, forward + backward, per token, over the `*`
    blocks only: `6 * S * H * D` each (`benchmarks/lib/flops.py`'s count) at
    the model's own head size.  The readers that divide this by
    `num_hidden_layers` (the three `flash_*_roofline`) read a ninth of the
    truth in this configuration, whose flash kernels all lie in one block of
    nine; `gqa16_attn_roofline` is the share that means what it says here."""
    return attention_layers(config) * 6.0 * seq_len * config["num_attention_heads"] * config["head_dim"]


def ssd_flops_per_token(config: Dict[str, Any]) -> float:
    """The selective scan in its GROUPED chunked form at the published chunk Q
    (`chunk_size`, 128), causal half, forward + backward (3x forward), per
    token, all Mamba-2 blocks, whatever chunk the program uses.  Forward per
    token, 2 flops a multiply-add: `C B^T` over Q/2 causal positions ONCE A
    GROUP, `G * Q * N`; per head `scores @ x` over Q/2 positions, `Q * P`, the
    chunk state `x (outer) B`, `2 * P * N`, and the entering state read out,
    `2 * P * N`.  So `3 * (G * Q * N + H * (Q * P + 4 * P * N))` a block."""
    q, n, p = config["chunk_size"], config["ssm_state_size"], config["mamba_head_dim"]
    g, h = config["n_groups"], config["mamba_num_heads"]
    return pattern(config).count("M") * 3.0 * (g * q * n + h * (q * p + 4 * p * n))


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights (the routed experts at `routed_rows_per_token`) + attention + the scan."""
    return (6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)
            + ssd_flops_per_token(config))


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows
    given to held experts (summed over the expert blocks): two matrices of
    d x width a row, 2 flops a multiply-add, 3x forward."""
    return 6.0 * rows * 2 * config["hidden_size"] * config["moe_intermediate_size"]


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions, as the file's `distortion` states it."""
    parts = matmul_params_by_part(config)
    active = sum(parts.values())
    needed = needed_flops_per_token(config, seq_len)
    share = config["share"]
    return {
        "routed_rows_per_token": routed_rows_per_token(config),
        "routed_rows_per_token_model": float(config["num_experts_per_tok"]),
        "active_matmul_params": active,
        **{f"{name}_pct_of_matmul": 100.0 * value / active for name, value in parts.items()},
        "attention_pct_of_needed": 100.0 * attention_flops_per_token(config, seq_len) / needed,
        "scan_pct_of_needed": 100.0 * ssd_flops_per_token(config) / needed,
        "rows_per_held_expert_uniform": seq_len * config["num_experts_per_tok"] / share["num_experts_total"],
        "rows_per_held_expert_deployed": (share["chips_per_layer"] * seq_len * config["num_experts_per_tok"]
                                          / share["num_experts_total"]),
    }
