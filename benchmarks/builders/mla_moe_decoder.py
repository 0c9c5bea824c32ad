"""kind = "mla_moe_decoder": GLM-4.7-Flash's stack (`model_type:
glm4_moe_lite`; DeepSeek-V3's block, arXiv:2412.19437, at its own sizes):
pre-norm RMSNorm layers, each latent attention with a low-rank q and a rotary
part beside a non-rotary part of every head, then a dense SwiGLU (the first
`first_k_dense_replace` layers) or SwiGLU experts behind a sigmoid router with
a stored bias (`noaux_tc`, one group), renormalised top-k times
`routed_scaling_factor`, plus one shared expert; and
`num_nextn_predict_layers` = 1 multi-token-prediction module behind the trunk,
whose cross entropy joins the objective times `train.mtp_loss_weight`.  Run
through the program's `TransformerConfig` (`q_lora_rank`, `mla_rope`,
`mtp_depth`) + `LMTrainContext` like the other kinds.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`n_routed_experts` counts the experts HELD here (`share.first_expert_held`
on), the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_tok` choices, `vocab_size` is this chip's slice, and the
module's block holds the same share as a layer of the stack.  Nothing here or
in the program stands in for the absent chips.  Two things are the JOB's,
stated under the file's `assumed`, so that a seed draws the weights and not
the work (PERF.md section 6, PRs 50 and 54): the router's blocks of
`n_routed_experts` columns start equal (`router_share_init`: every token
starts with one of its four choices on each of the four expert-parallel
shares), and `train.lr_warmup_steps` warms the rate up to
`default_optimizer`'s own, which keeps them near there.

The builder's four names, plus the counts the cell's rooflines are made of.
Needed operations count ACTIVE matmul weights: latent attention's five
projections, the dense FFN, the router, the shared expert and the head once;
the routed experts at the expectation of a uniform router over ALL experts,
`num_experts_per_tok * n_routed_experts / num_experts_total` rows a token
(1 here); causal attention `3 * S * H * (qk + v)` a token and layer; and the
MODULE's block, `eh_proj`, attention and its own pass through the head, all
needed work of the objective.  Recompute is never credited.
`glm_experts_roofline` does NOT use the expectation: it counts the rows the
traced steps gave the held experts (`expert_matmul_flops`).

`attention_flops_per_token` counts the module's attention with the stack's
(`attn_kernel_roofline`, which divides by ALL the kernels' time, is the latent
attention's roofline: every layer has one head shape).  The three
`flash_*_roofline` divide it by `num_hidden_layers`, which does not count the
module, for ONE call's work: they read (L + 1) / L of the truth here, 10 / 9.

`reference_logits` does more than its name: the loop compares `ctx.apply`
alone, so here, on the same sequences and positions, the program's MODULE
logits (`transformer.mtp_forward`, what `_loss` runs, jitted) are compared
with the reference's, the error printed on a `[bench] mtp reference` line, and
a module over the loop's own tolerance (`mtp_tolerance`) raises: the cell
then has no result.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

from benchmarks.builders.swa_moe_decoder import learning_rate  # the job's warm-up, as `mellum2`'s file states it

# What the program's layers express of this family, and nothing else.
_REQUIRED = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_scaling": None, "partial_rotary_factor": 1, "num_nextn_predict_layers": 1,
}


def ffn_kinds(config: Dict[str, Any]) -> List[str]:
    """The FFN of each layer that runs: `first_k_dense_replace` dense ones, experts behind them."""
    return ["dense" if i < config["first_k_dense_replace"] else "experts" for i in range(config["num_hidden_layers"])]


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names, the rope as `Rope`'s fields)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"mla_moe_decoder expresses {_REQUIRED} only, got {differ}")
    share, train = config["share"], config["train"]
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],  # read by no layer: every head's k and v come from the one latent
        d_ff=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=None,  # latent attention rotates by `mla_rope`
        layer_types=("mla",) * config["num_hidden_layers"],
        ffn_types=tuple(ffn_kinds(config)),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_rope={"theta": float(config["rope_theta"])},
        n_experts=share["num_experts_total"],
        n_experts_held=config["n_routed_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        router_activation="sigmoid",
        routed_scaling_factor=config["routed_scaling_factor"],
        routed_branch_init=True,  # `assumed.initial_values`: a token's four routed outputs start as ONE residual branch
        router_share_init=True,  # `assumed.initial_values`: the router's four blocks of 16 start equal, 1 choice a share
        mtp_depth=config["num_nextn_predict_layers"],
        mtp_loss_weight=train["mtp_loss_weight"],
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],
    )


def _transformer_config(config: Dict[str, Any], seq_len: int):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.ops.rotary import Rope

    kw = model_kwargs(config, seq_len)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    kw["mla_rope"] = Rope(**kw["mla_rope"])
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
    """Plain-reference logits [N, last, V] for token sequences [N, S]; on the
    way, the MODULE's comparison (module docstring).  The token after each
    position is the sequence rolled by one: the last position takes the
    first token, in program and reference alike."""
    import functools

    import jax
    import numpy as np

    from benchmarks.lib import reference_glm_moe_lite as ref
    from ray_tpu.models import transformer

    tokens = np.asarray(tokens)
    after = np.roll(tokens, -1, axis=1)
    want, want_mtp = ref.both_logits(config, params, tokens, after, last=last)
    program = jax.jit(functools.partial(transformer.mtp_forward, config=_transformer_config(config, tokens.shape[1])))
    errors = []
    for i in range(tokens.shape[0]):
        got = program(params, tokens[i: i + 1], after[i: i + 1])
        errors.append(ref.rel_rms_error(got[0, -last:], want_mtp[i]))
        del got
    tol = mtp_tolerance(config)
    ok = bool(np.all(np.isfinite(errors)) and max(errors) <= tol)
    print("[bench] mtp reference " + json.dumps(
        {"rel_rms_error": errors, "tolerance": tol, "positions": last, "seqs": len(errors), "ok": ok}), flush=True)
    if not ok:
        raise RuntimeError(f"the multi-token-prediction module's logits differ from the plain reference: rel rms error "
                           f"{errors} > tolerance {tol}")
    return want


def mtp_tolerance(config: Dict[str, Any]) -> float:
    """The largest relative RMS error the module's logits may have: the loop's
    own limit on the main logits at the published width; at another width (a
    rehearsal) the file's looser one (`reference_check.why`)."""
    from benchmarks.lib import reference

    check = config["reference_check"]
    if config["hidden_size"] == check["published_hidden_size"]:
        return reference.tolerance(config["num_hidden_layers"])
    return check["mtp_tolerance_at_other_widths"]


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one mixer or FFN of each kind, and of the module's projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, rank = config["q_lora_rank"], config["kv_lora_rank"]
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        # q down and up; the latent and k_pe; k_nope | v; o
        "mla": d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope) + rank * heads * (nope + v) + heads * v * d,
        "dense": 3 * d * config["intermediate_size"],
        "router": d * config["share"]["num_experts_total"],
        "shared": config["n_shared_experts"] * expert,
        "expert": expert,
        "eh_proj": 2 * d * d,
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves that multiply nothing: the two latents' norms, the router's bias, the module's three norms."""
    return {"mla": config["q_lora_rank"] + config["kv_lora_rank"], "experts": config["share"]["num_experts_total"],
            "mtp": 3 * config["hidden_size"]}


def total_params(config: Dict[str, Any], uncut: bool = False, mtp: bool = True) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer, every expert, every row);
    `mtp=False` leaves the multi-token-prediction module out."""
    d = config["hidden_size"]
    if uncut:
        config = published(config)
    sizes, other = _sizes(config), _other_params(config)
    layer = {"dense": sizes["mla"] + other["mla"] + 2 * d + sizes["dense"],
             "experts": (sizes["mla"] + other["mla"] + 2 * d + sizes["router"] + other["experts"] + sizes["shared"]
                         + config["n_routed_experts"] * sizes["expert"])}
    kinds = ffn_kinds(config)
    total = 2 * d * config["vocab_size"] + d + sum(layer[kind] for kind in kinds)  # embedding, head, final norm
    if mtp and config["num_nextn_predict_layers"]:
        total += sizes["eh_proj"] + other["mtp"] + layer[kinds[-1]]
    return total


# -- needed operations --------------------------------------------------------------


def routed_rows_per_token(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per token under a uniform router over
    all experts: K * held / total (1 at 4 of 64 with 16 held)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["share"]["num_experts_total"]


def expert_layers(config: Dict[str, Any]) -> int:
    """Layers with experts that a step runs, the module's block among them."""
    kinds = ffn_kinds(config)
    return kinds.count("experts") + (config["num_nextn_predict_layers"] if kinds[-1] == "experts" else 0)


def attention_layers(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a token multiplies, by part (no embedding table); `mtp`
    is the whole module: its projection, its block and its pass through the head."""
    sizes, kinds = _sizes(config), ffn_kinds(config)
    experts = sizes["router"] + sizes["shared"] + routed_rows_per_token(config) * sizes["expert"]
    head = float(config["hidden_size"] * config["vocab_size"])
    block = sizes["mla"] + (experts if kinds[-1] == "experts" else sizes["dense"])
    return {
        "mla_proj": float(len(kinds) * sizes["mla"]),
        "dense": float(kinds.count("dense") * sizes["dense"]),
        "router": float(kinds.count("experts") * sizes["router"]),
        "shared": float(kinds.count("experts") * sizes["shared"]),
        "routed_experts": kinds.count("experts") * routed_rows_per_token(config) * sizes["expert"],
        "head": head,
        "mtp": config["num_nextn_predict_layers"] * (sizes["eh_proj"] + block + head),
    }


def active_matmul_params(config: Dict[str, Any]) -> float:
    return sum(matmul_params_by_part(config).values())


def attention_flops_per_layer(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention of ONE layer, forward + backward, per token:
    `benchmarks/lib/flops.py`'s count, `6 * S * H * D`, with D the mean of the
    q/k head size and the v head size: QK^T runs over `nope + rope`, PV over
    `v_head_dim` (256 and 256 here), so `3 * S * H * (qk + v)`."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 3.0 * seq_len * config["num_attention_heads"] * (qk + config["v_head_dim"])


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Every layer's attention and the module's block's (module docstring)."""
    return attention_layers(config) * attention_flops_per_layer(config, seq_len)


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * active matmul weights (the routed experts at `routed_rows_per_token`) + causal attention, the module counted."""
    return 6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows
    given to held experts (summed over the layers): three matrices of
    d x width a row, 2 flops a multiply-add, 3x forward."""
    return 6.0 * rows * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions, as the file's `distortion` states it (% of needed FLOPs)."""
    parts, needed = matmul_params_by_part(config), needed_flops_per_token(config, seq_len)
    sizes, share = _sizes(config), config["share"]
    mtp_routed = routed_rows_per_token(config) * sizes["expert"] if ffn_kinds(config)[-1] == "experts" else 0.0
    module = config["num_nextn_predict_layers"]
    return {
        "needed_mflop_per_token": needed / 1e6,
        "attention_pct": 100.0 * attention_flops_per_token(config, seq_len) / needed,
        "mla_proj_pct": 100.0 * 6.0 * (parts["mla_proj"] + module * sizes["mla"]) / needed,
        "routed_experts_pct": 100.0 * 6.0 * (parts["routed_experts"] + module * mtp_routed) / needed,
        "heads_pct": 100.0 * 6.0 * (1 + module) * parts["head"] / needed,
        "mtp_pct": 100.0 * (6.0 * parts["mtp"] + module * attention_flops_per_layer(config, seq_len)) / needed,
        "routed_rows_per_token": routed_rows_per_token(config),
        "routed_rows_per_token_model": float(config["num_experts_per_tok"]),
        "rows_per_held_expert_uniform": seq_len * config["num_experts_per_tok"] / share["num_experts_total"],
        "rows_per_held_expert_deployed": (share["expert_parallel"] * seq_len * config["num_experts_per_tok"]
                                          / share["num_experts_total"]),
    }


def published(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published model's counts in this file's keys: every layer, every expert, every row of the vocabulary."""
    share = config["share"]
    return dict(config, num_hidden_layers=share["num_hidden_layers_total"], n_routed_experts=share["num_experts_total"],
                vocab_size=share["vocab_size_total"])
