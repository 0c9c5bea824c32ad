"""kind = "cca_moe_decoder": ZAYA1-8B's stack (`model_type: zaya`): pre-norm
RMSNorm layers, each Compressed Convolutional Attention (q, k, v in latents
narrower than the stream, two causal convolutions over the q|k latent, the q-k
mean, a value shift, unit-norm q and k with a learned temperature, a rope on
half of each head) and then top-1 of `num_experts` SwiGLU experts behind a
router that is a network whose state runs from layer to layer, every join
through a learned scale and bias, a tied head.  Run through the program's
`TransformerConfig` ("cca" layers, `router_kind="mlp"`, `residual_scaling`) +
`LMTrainContext` like the other kinds.

The configuration is ONE CHIP of a deployment that divides NO layer: whole
layers as pipeline stages, and the tied table's rows divided over the same
chips (`share.vocab_parallel`).  `num_hidden_layers` counts the layers of
this stage, `vocab_size` this chip's slice; every expert and every head is
here.  Nothing here or in the program stands in for the absent chips.

Needed operations (`needed_flops_per_token`, what `mfu_pct` divides into)
count ACTIVE matmul weights (the four projections, the grouped convolution's
maps, the router's four maps, ONE expert, the head's slice) and the causal
core at half the pairs (`flops.attention_flops_per_token`'s convention),
forward + backward.  Not counted: the depthwise convolution, the norms, the
experts a token does not choose, recompute.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.builders.swa_moe_decoder import learning_rate  # the warm-up of the rate the file states (`train.lr_warmup_steps`)
from benchmarks.lib import flops

# What the program's layers express of this family, and nothing else.
_REQUIRED = {"attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": True, "lm_head_bias": False,
             "sliding_window": None, "num_experts_per_tok": 1}

TRACE = "trace_zaya"  # `benchmarks/lib/<this>.py` knows the kind's scopes (`trace_kind`)
KIND = "hybrid"  # the one kind of layer the published list holds: CCA under the "hybrid" rope, no window


def heads(config: Dict[str, Any]) -> Tuple[int, int]:
    """(query heads, key heads) as they run: the file's, but never fewer than the two key heads the value shift needs
    (the harness's rehearsal overrides the counts to 2 / 1; `head_dim` stays the published one, so the latents are
    heads x 128 whatever the stream's width)."""
    kv = max(config["num_key_value_heads"], 2)
    return max(config["num_attention_heads"], kv), kv


def expert_width(config: Dict[str, Any]) -> int:
    """An expert's width as it runs: the file's, but never wider than the stream (published: 2048 = 2048).  The harness's
    rehearsal narrows the stream to 256 and leaves `moe_intermediate_size`: an expert 8x the stream's width joins it
    sqrt(8) larger than the model's does, and ONE flipped top-1 choice among its 512 compared tokens is then over the
    tolerance by itself."""
    return min(config["moe_intermediate_size"], config["hidden_size"])


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ or set(config["layer_types"]) != {KIND}:
        raise ValueError(f"cca_moe_decoder expresses {_REQUIRED} and '{KIND}' layers only, got {differ or config['layer_types'][:4]}")
    train, rope = config["train"], config["rope_parameters"][KIND]
    n_heads, n_kv_heads = heads(config)
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        attn_head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"],  # read by no layer: every FFN is the experts'
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=True,
        rope_theta=float(rope["rope_theta"]),
        rotary_dim=int(config["head_dim"] * rope["partial_rotary_factor"]),
        layer_types=("cca",) * config["num_hidden_layers"],
        cca_taps=(config["cca_time0"], config["cca_time1"]),
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=expert_width(config),
        router_kind="mlp",
        router_hidden=config["router_hidden_size"],
        router_activation="softmax",
        norm_topk_prob=False,  # K = 1: the normalised gate would be 1 and the router would get no gradient
        residual_scaling=True,
        router_bias_update_rate=train.get("router_bias_update_rate", 0.0),  # the rule that keeps the 16 experts in use (`assumed.router_bias_update`)
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],
    )


def _transformer_config(config: Dict[str, Any], seq_len: int):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    kw = model_kwargs(config, seq_len)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
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
    # the job's rate: `default_optimizer`'s own, reached by the warm-up the file states (`train.lr_warmup_steps`)
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"], optimizer=default_optimizer(learning_rate=learning_rate(train)))
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference_zaya

    return reference_zaya.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one layer's parts."""
    d, hd, r = config["hidden_size"], config["head_dim"], config["router_hidden_size"]
    n_heads, n_kv_heads = heads(config)
    return {
        "cca_proj": d * hd * (n_heads + 2 * n_kv_heads) + n_heads * hd * d,  # W_Q, W_K, W_V, W_O
        "cca_conv2": config["cca_time1"] * (n_heads + n_kv_heads) * hd * hd,
        "router": d * r + 2 * r * r + r * config["num_experts"],
        "expert": 3 * d * expert_width(config),
    }


def _other_params(config: Dict[str, Any]) -> int:
    """One layer's stored leaves that are no matmul's weight: the depthwise convolution and both convolutions' biases,
    tau, the router's three biases, gamma, norm scale and stored choice bias, the two norms, the eight residual vectors."""
    d, hd, r = config["hidden_size"], config["head_dim"], config["router_hidden_size"]
    n_heads, n_kv_heads = heads(config)
    latent = (n_heads + n_kv_heads) * hd
    return (config["cca_time0"] + 2) * latent + n_kv_heads + 5 * r + config["num_experts"] + 2 * d + 8 * d


def total_params(config: Dict[str, Any], uncut: bool = False, active: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer and row; the table counted
    once: it is tied); `active` counts `num_experts_per_tok` experts a layer."""
    if uncut:
        config = published(config)
    sizes = _sizes(config)
    experts = config["num_experts_per_tok"] if active else config["num_experts"]
    layer = sizes["cca_proj"] + sizes["cca_conv2"] + sizes["router"] + experts * sizes["expert"] + _other_params(config)
    return config["num_hidden_layers"] * layer + config["hidden_size"] * config["vocab_size"] + config["hidden_size"]


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the experts here multiply per token: all of a token's K choices, every expert being here."""
    return float(config["num_experts_per_tok"])


def expert_flops_per_token(config: Dict[str, Any]) -> float:
    """The three grouped matmuls of every layer, forward + backward, per token: what `moe_experts_roofline` divides
    into for a kind that holds EVERY expert (`trace_kind.experts_roofline_pct`: every assignment is a held row)."""
    return 6.0 * config["num_hidden_layers"] * routed_rows_per_token(config) * _sizes(config)["expert"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """The causal core at the QUERY heads as they run, forward + backward, all layers (`flops.attention_flops_per_token`)."""
    return flops.attention_flops_per_token(dict(config, num_attention_heads=heads(config)[0]), seq_len)


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding lookup; the tied table multiplies once, as the head)."""
    sizes, layers = _sizes(config), config["num_hidden_layers"]
    return {
        "cca_proj": float(layers * sizes["cca_proj"]), "cca_conv2": float(layers * sizes["cca_conv2"]),
        "router": float(layers * sizes["router"]), "experts": layers * routed_rows_per_token(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights + the causal core (module docstring)."""
    return 6.0 * sum(matmul_params_by_part(config).values()) + attention_flops_per_token(config, seq_len)


def mix_bytes_per_layer(config: Dict[str, Any], bytes_per: int = 2) -> float:
    """What one layer's q|k mixing must move per token at the least: the latent read and q, k written once forward,
    their cotangents read and the latent's written once backward; [H + G, D] in the model's dtype each.  The same
    count whether XLA's fusions or a later kernel do it; the convolutions' weights (0.66 MB) are not counted."""
    n_heads, n_kv_heads = heads(config)
    return 4.0 * (n_heads + n_kv_heads) * config["head_dim"] * bytes_per


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions (% of needed FLOPs), as PERF.md section 4 states it; beside each
    share here the whole model's (`*_pct_uncut`: 40 layers beside the whole table)."""
    out: Dict[str, float] = {}
    for suffix, cfg in (("", config), ("_uncut", published(config))):
        needed = needed_flops_per_token(cfg, seq_len)
        out["needed_mflop_per_token" + suffix] = needed / 1e6
        out["forward_mflop_per_token" + suffix] = needed / 3e6
        out.update({f"{name}_pct{suffix}": 100.0 * 6.0 * value / needed for name, value in matmul_params_by_part(cfg).items()})
        out["causal_core_pct" + suffix] = 100.0 * attention_flops_per_token(cfg, seq_len) / needed
    out["rows_per_expert_uniform"] = seq_len * routed_rows_per_token(config) / config["num_experts"]
    return out


def published(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published model's counts in this file's keys: every layer and every row of the table."""
    share = config["share"]
    return dict(config, num_hidden_layers=share["num_hidden_layers_total"], vocab_size=share["vocab_size_total"])
