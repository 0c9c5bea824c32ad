"""kind = "qwen3_next_decoder": Qwen3-Next's stack (`model_type: qwen3_next`):
pre-norm layers with ZERO-CENTRED RMSNorms, the mixer a gated delta rule with
one decay a head ("Gated DeltaNet", arXiv:2412.06464; 16 key heads, 32 value
heads) in three layers of four and softmax attention in the fourth (GQA 8:1
at heads of 256, per-head QK-norm, a rope on the first quarter of each head,
the output behind a sigmoid gate from q's projection at twice the width);
every layer's FFN SwiGLU experts behind a softmax router with renormalised
top-k, plus one shared expert behind a scalar sigmoid gate.  Run through the
program's `TransformerConfig` (`layer_types` "gdn" / "attention", `rotary_dim`,
`attn_output_gate`, `norm_zero_centred`, `shared_expert_gate`) +
`LMTrainContext` like the other kinds.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`num_experts` counts the experts HELD here (`share.first_expert_held` on),
the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_tok` choices, `vocab_size` is this chip's slice.  Nothing
here or in the program stands in for the absent chips.  `train.lr_warmup_steps`
is the JOB's, stated under the file's `assumed`: it keeps the seeded router
where the seed drew it inside a 30-s window (PERF.md section 6, PRs 50, 54, 57).

The builder's four names, plus the counts the cell's rooflines are made of.
Needed operations count ACTIVE matmul weights: every matmul weight of the
mixers, the router, the shared expert with its gate and the head once; the
routed experts at the expectation of a uniform router over ALL experts,
`num_experts_per_tok * num_experts / num_experts_total` rows a token (0.625
here); causal attention in the attention layers only, `6 * S * H * D` a layer;
the delta rule in its chunked form WITH ONE DECAY A HEAD at chunk 64
(`gdn_scan_flops_per_token`), whatever implements it: what a per-channel
kernel spends on building its decayed [C, C] matrices by halves is time, not
work.  Recompute is never credited.  `q3n_experts_roofline` does NOT use the
expectation: it counts the rows the traced steps gave the held experts
(`expert_matmul_flops`).

`attention_flops_per_token` counts the attention layers alone, so the three
every-cell `flash_*_roofline`, which divide it by `num_hidden_layers` for ONE
call's work, read 2 / 8 of the truth here (as a quarter of `kimi-linear`'s
truth there); `q3n_gated_attn_roofline` is the share that means what it says.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.builders.swa_moe_decoder import learning_rate  # the job's warm-up, as `mellum2`'s file states it
from benchmarks.lib import reference_qwen3_next

GDN_CHUNK = 64  # the published kernels' chunk (`fla.ops.gated_delta_rule`), what the scan's needed FLOPs are counted at

# What the program's layers express of this family, and nothing else.
_REQUIRED = {
    "hidden_act": "silu", "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "norm_topk_prob": True,
}

layer_kinds = reference_qwen3_next.layer_kinds


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"qwen3_next_decoder expresses {_REQUIRED} only, got {differ}")
    share, train = config["share"], config["train"]
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],  # read by no layer: `mlp_only_layers` is empty
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=float(config["rope_theta"]),
        rotary_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        qk_norm="per_head",
        attn_output_gate=True,
        norm_zero_centred=True,
        layer_types=tuple(layer_kinds(config)),
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        n_experts=share["num_experts_total"],
        n_experts_held=config["num_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=1,
        shared_expert_d_ff=config["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        norm_topk_prob=config["norm_topk_prob"],
        router_activation="softmax",
        routed_branch_init=True,  # `assumed.initial_values`: a token's ten routed outputs start as ONE residual branch
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
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer(learning_rate=learning_rate(train)))
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    return reference_qwen3_next.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one mixer of each kind and of the parts of an expert block."""
    d, head = config["hidden_size"], config["head_dim"]
    qk = 2 * config["linear_num_key_heads"] * config["linear_key_head_dim"]
    v = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        # q | k | v | z; b | a; o
        "gdn": d * (qk + 2 * v) + d * 2 * config["linear_num_value_heads"] + v * d,
        # q | gate; k; v; o
        "attention": d * heads * 2 * head + 2 * d * kv_heads * head + heads * head * d,
        "router": d * config["share"]["num_experts_total"],
        "shared": 3 * d * config["shared_expert_intermediate_size"] + d,  # the SwiGLU and its scalar gate
        "expert": 3 * d * config["moe_intermediate_size"],
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves of one mixer that multiply nothing."""
    qk = 2 * config["linear_num_key_heads"] * config["linear_key_head_dim"]
    v = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return {
        # the convolutions, A_log, dt_bias, the gated norm's scale
        "gdn": (qk + v) * config["linear_conv_kernel_dim"] + 2 * config["linear_num_value_heads"] + config["linear_value_head_dim"],
        "attention": 2 * config["head_dim"],  # the two per-head norms
    }


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer, every expert, every row)."""
    d = config["hidden_size"]
    if uncut:
        config = published(config)
    sizes, other = _sizes(config), _other_params(config)
    block = sizes["router"] + sizes["shared"] + config["num_experts"] * sizes["expert"]
    total = 2 * d * config["vocab_size"] + d  # embedding, head, final norm
    for kind in layer_kinds(config):
        total += sizes[kind] + other[kind] + 2 * d + block
    return total


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over
    all experts: K * held / total (0.625 at 10 of 512 with 32 held)."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["share"]["num_experts_total"]


def expert_layers(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding table)."""
    sizes, kinds = _sizes(config), layer_kinds(config)
    return {
        "gdn_proj": float(kinds.count("gdn") * sizes["gdn"]),
        "attn_proj": float(kinds.count("attention") * sizes["attention"]),
        "router": float(len(kinds) * sizes["router"]),
        "shared": float(len(kinds) * sizes["shared"]),
        "routed_experts": len(kinds) * routed_rows_per_token(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def active_matmul_params(config: Dict[str, Any]) -> float:
    return sum(matmul_params_by_part(config).values())


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention, forward + backward, per token, over the
    ATTENTION layers only: `benchmarks/lib/flops.py`'s count, `6 * S * H * D` a
    layer (QK^T and PV over heads of `head_dim`, causal half, 3x forward)."""
    layers = layer_kinds(config).count("attention")
    return layers * 6.0 * seq_len * config["num_attention_heads"] * config["head_dim"]


def gdn_scan_flops_per_token(config: Dict[str, Any]) -> float:
    """The delta rule with ONE decay a head in its chunked form at chunk C =
    64, causal half where a product is triangular, forward + backward (3x
    forward), per token, all gdn layers, whatever chunk or kernel the program
    uses.  Forward per token and value head, K = `linear_key_head_dim`, V =
    `linear_value_head_dim`, 2 flops a multiply-add: the two [C, C] matrices
    `k k^T` and `q k^T` over C/2 causal positions each, `2 * C * K` (the
    scalar decay is a [C, C] mask on them: no matmul); the unit
    lower-triangular solve for K + V right-hand columns, `C * (K + V)`; the
    causal `qk @ U`, `C * V`; and the [K, V] products with the chunk state,
    `W S`, `q S`, `K^T U`, counted as three matmuls, `6 * K * V`.  So `3 * Hv *
    (C * (3K + 2V) + 6 * K * V)` a layer: `kimi_linear_decoder`'s count at
    these heads, since a decay per channel changes what the [C, C] matrices
    COST to build (log2 C levels of decayed operands) and not what they are."""
    k, v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    per_head = GDN_CHUNK * (3 * k + 2 * v) + 6 * k * v
    return layer_kinds(config).count("gdn") * 3.0 * config["linear_num_value_heads"] * per_head


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights + attention (the attention layers) + the delta rule (the gdn layers)."""
    return (6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)
            + gdn_scan_flops_per_token(config))


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows
    given to held experts (summed over the layers): three matrices of
    d x width a row, 2 flops a multiply-add, 3x forward."""
    return 6.0 * rows * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions, as the file's `distortion` states it (% of needed FLOPs)."""
    parts, needed = matmul_params_by_part(config), needed_flops_per_token(config, seq_len)
    share = config["share"]
    return {
        "needed_mflop_per_token": needed / 1e6,
        "attention_pct": 100.0 * attention_flops_per_token(config, seq_len) / needed,
        "gdn_scan_pct": 100.0 * gdn_scan_flops_per_token(config) / needed,
        "mixer_proj_pct": 100.0 * 6.0 * (parts["gdn_proj"] + parts["attn_proj"]) / needed,
        "router_shared_pct": 100.0 * 6.0 * (parts["router"] + parts["shared"]) / needed,
        "routed_experts_pct": 100.0 * 6.0 * parts["routed_experts"] / needed,
        "head_pct": 100.0 * 6.0 * parts["head"] / needed,
        "routed_rows_per_token": routed_rows_per_token(config),
        "routed_rows_per_token_model": float(config["num_experts_per_tok"]),
        "rows_per_held_expert_uniform": seq_len * config["num_experts_per_tok"] / share["num_experts_total"],
        "rows_per_held_expert_deployed": (share["chips_per_layer"] * seq_len * config["num_experts_per_tok"]
                                          / share["num_experts_total"]),
    }


def published(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published model's counts in this file's keys: every layer, every expert, every row of the vocabulary."""
    share = config["share"]
    return dict(config, num_hidden_layers=share["num_hidden_layers_total"], num_experts=share["num_experts_total"],
                vocab_size=share["vocab_size_total"])
