"""kind = "sparse_mla_moe_decoder": dots3-note-prev's stack (`model_type:
dots3_note`): pre-norm RMSNorm layers, each latent attention with rescaled
latents and a head-wise output gate, of TWO geometries: a FULL layer (the
config's own `q_lora_rank` .. `v_head_dim`, `rope_theta`) attends a learned
selection, DeepSeek-V3.2-Exp's indexer (`index_n_heads`, `index_head_dim`,
`index_topk`) trained by a KL term of the objective; a SLIDING layer (the
`swa_*` keys: its own heads, ranks, head sizes and theta) attends its last
`sliding_window_size` keys; then a dense SwiGLU (the first
`first_k_dense_replace` layers) or SwiGLU experts behind a sigmoid `noaux_tc`
router plus one shared expert.  Run through the program's `TransformerConfig`
("mla_sparse" / "mla_window" layers, `window_latent`, `layer_windows`,
`layer_ropes`, `index_*`, `head_share`) + `LMTrainContext` like the other kinds.

The configuration is ONE CHIP'S SHARE of a deployment whose 32 chips share
each layer (`share`): `n_routed_experts` counts the experts HELD here, the two
`*num_attention_heads` the heads HELD here (`share.head_share_index` of
`share.head_parallel`), `vocab_size` this chip's slice.  Nothing here or in the
program stands in for the absent chips.

Needed operations (`needed_flops_per_token`, what `mfu_pct` divides into)
count ACTIVE matmul weights (both kinds' projections and gate at the held
heads, the whole indexer's three projections, the dense FFN, router, shared
expert, the routed experts at `routed_rows_per_token`, the head) and, pair by
pair, the work no form can do without: the indexer's scores over the CAUSAL
pairs (64 heads of 128), the core over the SELECTED pairs (`min(t + 1,
index_topk)` a query) and the window's pairs (`min(t + 1, window)`), each
forward + backward (3x the forward).  Not counted: the target's second `q k^T`
(the KL pass), the unselected pairs a masked tile computes, recompute.

`attention_flops_per_token` / `attention_layers` are the SLIDING layers'
alone: they are what the every-cell readers divide the flash kernels' time
into, and the flash kernels run in those layers only (the sparse core's
kernels are `dsa_attn_*`, read by `dsa_attn_roofline`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.builders.swa_moe_decoder import learning_rate  # the job's warm-up, as `mellum2`'s file states it

# What the program's layers express of this family, and nothing else.
_REQUIRED = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "scoring_func": "sigmoid", "rope_scaling": None, "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "moe_layer_freq": 1,
}

TRACE = "trace_dots3"  # `benchmarks/lib/<this>.py` knows the kind's scopes (`trace_kind`)
FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The attention of each layer that runs: the first `num_hidden_layers` of the published list."""
    return config["layer_types"][:config["num_hidden_layers"]]


def ffn_kinds(config: Dict[str, Any]) -> List[str]:
    return ["dense" if i < config["first_k_dense_replace"] else "experts" for i in range(config["num_hidden_layers"])]


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names, ropes as `Rope`'s fields, the second geometry as `Latent`'s)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ or set(config["layer_types"]) - {FULL, SLIDING}:
        raise ValueError(f"sparse_mla_moe_decoder expresses {_REQUIRED} and full / sliding layers only, got {differ}")
    share, train, kinds = config["share"], config["train"], layer_kinds(config)
    ropes = {FULL: {"theta": float(config["rope_theta"])}, SLIDING: {"theta": float(config["swa_rope_theta"])}}
    ways = share["head_parallel"]
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"] * ways,  # the model's heads: `head_share` says which are held
        n_kv_heads=config["num_key_value_heads"] * ways,  # read by no layer: every head's k and v come from the one latent
        d_ff=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=None,  # every layer brings its kind's rope
        layer_types=tuple("mla_sparse" if k == FULL else "mla_window" for k in kinds),
        ffn_types=tuple(ffn_kinds(config)),
        layer_windows=tuple(config["sliding_window_size"] if k == SLIDING else None for k in kinds),
        layer_ropes=tuple(ropes[k] for k in kinds),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        window_latent=dict(heads=config["swa_num_attention_heads"] * ways, q_rank=config["swa_q_lora_rank"],
                           kv_rank=config["swa_kv_lora_rank"], nope=config["swa_qk_nope_head_dim"],
                           rope=config["swa_qk_rope_head_dim"], v=config["swa_v_head_dim"]),
        head_share=(share["head_share_index"], ways),
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        n_experts=share["num_experts_total"],
        n_experts_held=config["n_routed_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        router_activation="sigmoid",
        routed_scaling_factor=config["routed_scaling_factor"],
        routed_branch_init=True,  # `assumed.initial_values`: a token's eight routed outputs start as ONE residual branch
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],
    )


def _transformer_config(config: Dict[str, Any], seq_len: int):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.models.mixers.mla import Latent
    from ray_tpu.ops.rotary import Rope

    kw = model_kwargs(config, seq_len)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    kw["layer_ropes"] = tuple(Rope(**fields) for fields in kw["layer_ropes"])
    kw["window_latent"] = Latent(**kw["window_latent"])
    return TransformerConfig(**kw)


def build(config: Dict[str, Any], seq_len: int, devices) -> Tuple[Any, Any]:
    """(TransformerConfig, LMTrainContext) on `devices` (the worker's chips,
    or a described topology's for an AOT compile)."""
    from ray_tpu.models import LMTrainContext, default_optimizer
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = _transformer_config(config, seq_len)
    train = config["train"]
    if train["optimizer"] != "default_optimizer":
        raise ValueError(f"unknown optimizer {train['optimizer']!r}")
    mesh = build_mesh(MeshSpec(**train["mesh"]), devices=list(devices)[:train["chips"]])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer(learning_rate=learning_rate(train)))
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference_dots3_note

    return reference_dots3_note.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _latent(config: Dict[str, Any], kind: str) -> Dict[str, int]:
    """One kind's geometry at the heads this file holds."""
    pre = "" if kind == FULL else "swa_"
    return {name: config[pre + key] for name, key in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"), ("kv_rank", "kv_lora_rank"),
        ("nope", "qk_nope_head_dim"), ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"))}


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one mixer or FFN of each kind."""
    d = config["hidden_size"]
    expert = 3 * d * config["moe_intermediate_size"]

    def attention(g):  # q down and up; the latent and k_pe; k_nope | v; the gate; o
        return (d * g["q_rank"] + g["q_rank"] * g["heads"] * (g["nope"] + g["rope"]) + d * (g["kv_rank"] + g["rope"])
                + g["kv_rank"] * g["heads"] * (g["nope"] + g["v"]) + d * g["heads"] + g["heads"] * g["v"] * d)

    index = config["index_n_heads"], config["index_head_dim"]
    return {
        FULL: attention(_latent(config, FULL)), SLIDING: attention(_latent(config, SLIDING)),
        "indexer": config["q_lora_rank"] * index[0] * index[1] + d * index[1] + d * index[0],
        "dense": 3 * d * config["intermediate_size"], "router": d * config["share"]["num_experts_total"],
        "shared": config["n_shared_experts"] * expert, "expert": expert,
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves that multiply nothing: the latents' norms, the indexer's LayerNorm, the router's bias."""
    return {FULL: config["q_lora_rank"] + config["kv_lora_rank"] + 2 * config["index_head_dim"],
            SLIDING: config["swa_q_lora_rank"] + config["swa_kv_lora_rank"], "experts": config["share"]["num_experts_total"]}


def total_params(config: Dict[str, Any], uncut: bool = False, active: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published language model (every layer, expert, head and
    row); `active` counts `num_experts_per_tok` routed experts a layer."""
    d = config["hidden_size"]
    if uncut:
        config = published(config)
    sizes, other = _sizes(config), _other_params(config)
    routed = config["num_experts_per_tok"] if active else config["n_routed_experts"]
    total = 2 * d * config["vocab_size"] + d  # embedding, head, final norm
    for kind, ffn in zip(layer_kinds(config), ffn_kinds(config)):
        total += sizes[kind] + (sizes["indexer"] if kind == FULL else 0) + other[kind] + 2 * d
        total += sizes["dense"] if ffn == "dense" else sizes["router"] + other["experts"] + sizes["shared"] + routed * sizes["expert"]
    return total


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over all experts: K * held / total (0.25 here)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["share"]["num_experts_total"]


def expert_layers(config: Dict[str, Any]) -> int:
    return ffn_kinds(config).count("experts")


def held_expert_slots(config: Dict[str, Any]) -> int:
    """Held experts x expert layers: what the step counter `moe_held_rows_mean` is a mean over."""
    return config["n_routed_experts"] * expert_layers(config)


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows given to held experts."""
    return 6.0 * rows * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def mean_pairs(seq_len: int, most: int) -> float:
    """The keys a causal query attends when it keeps at most `most` (a window, a top-k), the mean over the sequence."""
    most = min(most, seq_len)
    return (most * (most + 1) / 2 + (seq_len - most) * most) / seq_len


def attention_layers(config: Dict[str, Any]) -> int:
    """The layers whose core is a flash call: the sliding ones (module docstring)."""
    return layer_kinds(config).count(SLIDING)


def window_flops_per_layer(config: Dict[str, Any], seq_len: int) -> float:
    """One sliding layer's core, forward + backward, per token: QK^T and PV are 2 * (qk + v) flops a pair and head, 3x for both directions."""
    g = _latent(config, SLIDING)
    return 6.0 * mean_pairs(seq_len, config["sliding_window_size"]) * g["heads"] * (g["nope"] + g["rope"] + g["v"])


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return attention_layers(config) * window_flops_per_layer(config, seq_len)


def selected_flops_per_layer(config: Dict[str, Any], seq_len: int) -> float:
    """One full layer's core over the SELECTED pairs, forward + backward, per token."""
    g = _latent(config, FULL)
    return 6.0 * mean_pairs(seq_len, config["index_topk"]) * g["heads"] * (g["nope"] + g["rope"] + g["v"])


def selected_bytes_per_layer(config: Dict[str, Any], seq_len: int, bytes_per: int = 2) -> float:
    """What the core's three passes must move per token at the least: q, k, v and o read or written once a pass
    (forward; dq; dk and dv), the mask's row of `seq_len` int8 once a pass."""
    g = _latent(config, FULL)
    row = g["heads"] * (2 * (g["nope"] + g["rope"]) + 2 * g["v"]) * bytes_per
    return 3.0 * (row + seq_len)


def index_score_flops_per_layer(config: Dict[str, Any], seq_len: int) -> float:
    """The indexer's scores over the CAUSAL pairs, forward + backward, per token."""
    return 6.0 * mean_pairs(seq_len, seq_len) * config["index_n_heads"] * config["index_head_dim"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding table)."""
    sizes, kinds, ffns = _sizes(config), layer_kinds(config), ffn_kinds(config)
    n_experts = ffns.count("experts")
    return {
        "full_proj": float(kinds.count(FULL) * sizes[FULL]), "sliding_proj": float(kinds.count(SLIDING) * sizes[SLIDING]),
        "indexer_proj": float(kinds.count(FULL) * sizes["indexer"]),
        "dense": float(ffns.count("dense") * sizes["dense"]), "router": float(n_experts * sizes["router"]),
        "shared": float(n_experts * sizes["shared"]),
        "routed_experts": n_experts * routed_rows_per_token(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def pair_flops_by_part(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    full = layer_kinds(config).count(FULL)
    return {"index_scores": full * index_score_flops_per_layer(config, seq_len),
            "selected_core": full * selected_flops_per_layer(config, seq_len),
            "window_core": attention_flops_per_token(config, seq_len)}


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights + the three kinds of pair work (module docstring)."""
    return 6.0 * sum(matmul_params_by_part(config).values()) + sum(pair_flops_by_part(config, seq_len).values())


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions (% of needed FLOPs), as PERF.md section 4 states it."""
    needed = needed_flops_per_token(config, seq_len)
    out = {"needed_mflop_per_token": needed / 1e6, "forward_mflop_per_token": needed / 3e6}
    out.update({f"{name}_pct": 100.0 * 6.0 * value / needed for name, value in matmul_params_by_part(config).items()})
    out.update({f"{name}_pct": 100.0 * value / needed for name, value in pair_flops_by_part(config, seq_len).items()})
    g = _latent(config, FULL)
    out["dense_causal_core_pct_of_this_needed"] = (100.0 * layer_kinds(config).count(FULL) * 6.0 * mean_pairs(seq_len, seq_len)
                                                   * g["heads"] * (g["nope"] + g["rope"] + g["v"]) / needed)
    out["selected_pairs_pct_of_causal"] = 100.0 * mean_pairs(seq_len, config["index_topk"]) / mean_pairs(seq_len, seq_len)
    out["routed_rows_per_token"] = routed_rows_per_token(config)
    out["rows_per_held_expert_uniform"] = seq_len * config["num_experts_per_tok"] / config["share"]["num_experts_total"]
    return out


def published(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published language model's counts in this file's keys: every layer, expert, head and row."""
    share = config["share"]
    return dict(config, num_hidden_layers=share["num_hidden_layers_total"], n_routed_experts=share["num_experts_total"],
                vocab_size=share["vocab_size_total"], num_attention_heads=share["num_attention_heads_total"],
                swa_num_attention_heads=share["swa_num_attention_heads_total"])
