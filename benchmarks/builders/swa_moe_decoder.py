"""kind = "swa_moe_decoder": Mellum 2's stack (`model_type: mellum`,
JetBrains/Mellum2-12B-A2.5B-Instruct): pre-norm RMSNorm layers, each GQA
attention (a head size of the model's own, RMSNorm per head of q and k) and
then a dropless mixture of SwiGLU experts (softmax router, renormalised
top-k, no shared expert); `layer_types` says of each layer whether it is
"sliding_attention" (a causal window of `sliding_window` keys, the default
rope) or "full_attention" (every earlier key, the YaRN rope), and
`rope_parameters` holds one rope for each kind.  Run through the program's
`TransformerConfig` (`layer_windows`, `layer_ropes`) + `LMTrainContext` like
the other kinds: ONE parameter stack, two compiled bodies a period.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`num_experts` counts the experts HELD here (`share.first_expert_held` on),
the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_tok` choices, `vocab_size` is this chip's slice.  Nothing
here or in the program stands in for the absent chips.  Two things are the
JOB's, stated under the file's `assumed`, so that a seed draws the weights
and not the work (PERF.md section 6, PR 50): the router's four blocks of 16
columns start equal (`router_share_init`: every token starts with 2 of its
8 choices on this chip), and `train.lr_warmup_steps` warms the rate up to
`default_optimizer`'s own (`learning_rate`), which keeps them near there.

The builder's four names, plus the counts the cell's rooflines are made of.
Needed operations count ACTIVE matmul weights: attention's projections, the
router and the head once; the routed experts at the expectation of a uniform
router over ALL experts, `num_experts_per_tok * num_experts /
num_experts_total` rows a token (2 here); causal attention at the keys a
query really sees: `(S + 1) / 2`, taken as `S / 2`, in a full layer,
`mean_keys_seen` (992 at 16,384 with a window of 1,024) in a window layer.
Recompute is never credited.  `m2_experts_roofline` does NOT use the
expectation: it counts the rows the traced steps gave the held experts
(`expert_matmul_flops`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

# What the program's layers express of this family, and nothing else.
_REQUIRED = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "norm_topk_prob": True,
    "use_sliding_window": True,
}
KINDS = ("sliding_attention", "full_attention")


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The layers that run: the first `num_hidden_layers` of the published `layer_types`."""
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types gives {kinds!r} for {config['num_hidden_layers']} layers of {KINDS}")
    return kinds


def rope_kwargs(rope: Dict[str, Any]) -> Dict[str, Any]:
    """One entry of `rope_parameters` as the fields of the program's `ops.rotary.Rope`."""
    if rope["rope_type"] == "default":
        return {"theta": float(rope["rope_theta"])}
    if rope["rope_type"] != "yarn":
        raise ValueError(f"swa_moe_decoder expresses the default rope and YaRN, got {rope['rope_type']!r}")
    return {"theta": float(rope["rope_theta"]), "factor": float(rope["factor"]),
            "original_max_position": rope["original_max_position_embeddings"],
            "beta_fast": float(rope.get("beta_fast", 32)), "beta_slow": float(rope.get("beta_slow", 1)),
            "attention_factor": rope.get("attention_factor")}


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names, each layer's rope as `Rope`'s fields)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ or set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError(f"swa_moe_decoder expresses {_REQUIRED} and sparse layers only, got {differ}, "
                         f"mlp_layer_types {sorted(set(config['mlp_layer_types']))}")
    if config["qk_norm"] not in ("per_head", None):
        raise ValueError(f"qk_norm is 'per_head' or null, got {config['qk_norm']!r}")
    share, train, kinds = config["share"], config["train"], layer_kinds(config)
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],  # a dense width no layer of the model uses; read by none
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=None,  # every layer brings its own rope
        layer_windows=tuple(config["sliding_window"] if k == "sliding_attention" else None for k in kinds),
        layer_ropes=tuple(rope_kwargs(config["rope_parameters"][k]) for k in kinds),
        qk_norm=config["qk_norm"] or False,
        n_experts=share["num_experts_total"],
        n_experts_held=config["num_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        norm_topk_prob=True,
        router_activation="softmax",
        router_aux_loss_coef=config["router_aux_loss_coef"],
        routed_branch_init=True,  # `assumed.initial_values`: a token's eight routed outputs start as ONE residual branch
        router_share_init=True,  # `assumed.initial_values`: the router's four blocks of 16 start equal, 2 choices a share
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
    from ray_tpu.ops.rotary import Rope
    from ray_tpu.parallel import MeshSpec, build_mesh

    kw = model_kwargs(config, seq_len)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    kw["layer_ropes"] = tuple(Rope(**fields) for fields in kw["layer_ropes"])
    cfg = TransformerConfig(**kw)
    train = config["train"]
    if train["optimizer"] != "default_optimizer":
        raise ValueError(f"unknown optimizer {train['optimizer']!r}")
    mesh = build_mesh(MeshSpec(**train["mesh"]), devices=list(devices)[:train["chips"]])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer(learning_rate=learning_rate(train)))
    return cfg, ctx


def learning_rate(train: Dict[str, Any]):
    """`default_optimizer`'s own rate, reached by a linear warm-up over
    `train.lr_warmup_steps` steps (an optax schedule; the first step runs at
    rate / steps): the job's recipe, `assumed.optimizer_hyperparameters`."""
    import inspect

    import optax

    from ray_tpu.models import default_optimizer

    rate = inspect.signature(default_optimizer).parameters["learning_rate"].default
    steps = train["lr_warmup_steps"]
    return optax.linear_schedule(rate / steps, rate, steps)


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference_mellum

    return reference_mellum.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one layer's attention and router, and of one routed expert."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "attn": 2 * d * q + 2 * d * kv,  # q, o; k, v
        "router": d * config["share"]["num_experts_total"],
        "expert": 3 * d * config["moe_intermediate_size"],
    }


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer, every expert, every row)."""
    d, share = config["hidden_size"], config["share"]
    if uncut:
        config = dict(config, num_hidden_layers=share["num_hidden_layers_total"],
                      num_experts=share["num_experts_total"], vocab_size=share["vocab_size_total"])
    sizes = _sizes(config)
    norms = 2 * d + (2 * config["head_dim"] if config["qk_norm"] == "per_head" else 0)
    layer = sizes["attn"] + sizes["router"] + config["num_experts"] * sizes["expert"] + norms
    return 2 * d * config["vocab_size"] + d + config["num_hidden_layers"] * layer  # embedding, head, final norm


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over
    all experts: K * held / total (2 at 8 of 64 with 16 held)."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["share"]["num_experts_total"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding table)."""
    sizes, layers = _sizes(config), config["num_hidden_layers"]
    return {
        "attn_proj": float(layers * sizes["attn"]),
        "router": float(layers * sizes["router"]),
        "routed_experts": layers * routed_rows_per_token(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def active_matmul_params(config: Dict[str, Any]) -> float:
    return sum(matmul_params_by_part(config).values())


def mean_keys_seen(seq_len: int, window: Optional[int]) -> float:
    """Keys a causal query sees, its own counted, the mean over a sequence
    from position 0: `S / 2` with no window (the accepted count's `(S + 1) / 2`
    taken as `S / 2`), else the exact mean of `min(i + 1, window)`."""
    if window is None or window >= seq_len:
        return seq_len / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def _attention_flops(config: Dict[str, Any], seq_len: int, kind: str) -> float:
    """Causal softmax attention of the layers of one kind, forward + backward
    (3x forward), per token: QK^T and PV are 2 * H * D flops a key each."""
    window = config["sliding_window"] if kind == "sliding_attention" else None
    per_layer = 12.0 * mean_keys_seen(seq_len, window) * config["num_attention_heads"] * config["head_dim"]
    return layer_kinds(config).count(kind) * per_layer


def window_attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return _attention_flops(config, seq_len, "sliding_attention")


def full_attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return _attention_flops(config, seq_len, "full_attention")


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Both kinds of layer.  The readers that divide this by
    `num_hidden_layers` (the three `flash_*_roofline`) read a blend of a
    window layer's call and a full layer's, 1 : 8 in needed work;
    `m2_window_attn_roofline` and `m2_full_attn_roofline` are the shares that
    mean what they say here."""
    return window_attention_flops_per_token(config, seq_len) + full_attention_flops_per_token(config, seq_len)


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights (the routed experts at `routed_rows_per_token`) + attention at the keys seen."""
    return 6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows
    given to held experts (summed over the layers): three matrices of
    d x width a row, 2 flops a multiply-add, 3x forward."""
    return 6.0 * rows * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions, as the file's `distortion` states it."""
    parts = matmul_params_by_part(config)
    needed = needed_flops_per_token(config, seq_len)
    share = config["share"]
    all_full = dict(config, layer_types=["full_attention"] * len(config["layer_types"]))
    return {
        "needed_mflop_per_token": needed / 1e6,
        "needed_mflop_per_token_were_every_layer_full": needed_flops_per_token(all_full, seq_len) / 1e6,
        **{f"{name}_pct_of_needed": 100.0 * 6.0 * value / needed for name, value in parts.items()},
        "window_attention_pct_of_needed": 100.0 * window_attention_flops_per_token(config, seq_len) / needed,
        "full_attention_pct_of_needed": 100.0 * full_attention_flops_per_token(config, seq_len) / needed,
        "routed_rows_per_token": routed_rows_per_token(config),
        "routed_rows_per_token_model": float(config["num_experts_per_tok"]),
        "rows_per_held_expert_uniform": seq_len * config["num_experts_per_tok"] / share["num_experts_total"],
        "rows_per_held_expert_deployed": (share["chips_per_layer"] * seq_len * config["num_experts_per_tok"]
                                          / share["num_experts_total"]),
    }
