"""kind = "block_diffusion_moe_decoder": SDAR-30B-A3B-Chat's stack
(`model_type: sdar_moe`, JetLM/SDAR-30B-A3B-Chat; SDAR, arXiv:2510.06303):
Qwen3-MoE's block to the key (pre-norm RMSNorm layers, each GQA attention at
a head size of the model's own with an RMSNorm per head of q and k, then a
dropless mixture of SwiGLU experts behind a softmax router with renormalised
top-k, no shared expert, `decoder_sparse_step` 1) trained and sampled as a
BLOCK-DIFFUSION model: the sequence is cut into blocks of
`assumed.block_length` tokens, attention is causal between blocks and
two-sided inside one, and the training step sends a noisy copy of each
sequence through the stack beside the clean one (`[x_t ‖ x_0]`, 2S rows a
sequence of S) under the three-part mask, with the block-diffusion NELBO on
the noisy half.  Run through the program's `TransformerConfig`
(`diffusion_block`) + `LMTrainContext` like the other kinds.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
`num_experts` counts the experts HELD here (`share.first_expert_held` on),
the router keeps the published `share.num_experts_total` outputs and its
`num_experts_per_tok` choices, `vocab_size` is this chip's slice.  Nothing
here or in the program stands in for the absent chips.  Two things are the
JOB's, stated under the file's `assumed`, so that a seed draws the weights and
not the work (PERF.md section 6, PR 50): the router's blocks of `num_experts`
columns start equal (`router_share_init`), and `train.lr_warmup_steps` warms
the rate up to `default_optimizer`'s own.

A TOKEN here is a DATA token: the loop's `tokens_per_step` is `batch x seq`,
the sequence's S tokens, and the 2S rows they become are the model's
business.  Needed operations a data token: `6 x (2 x L x (attention's
projections + router + K * held / E x one expert) + d x V_slice)` (both
copies pass every layer; the head multiplies the noisy copy alone) `+ 12 x L
x (S + B) x H x D` (the mask's true pairs, S^2 + S * B a sequence of which
QK^T and PV are 2 * H * D flops each, forward + backward; nothing for the
masked part of a visited tile, nothing for recompute).
`sdar_experts_roofline` does NOT use the expectation: it counts the rows the
traced steps gave the held experts (`expert_matmul_flops`).

`reference_logits` does more than its name: the loop compares `ctx.apply` (the
plain forward, block-causal over one copy) alone, so here, on the same
sequences, the program's TIMED forward (`ctx.apply_diffusion` of the context
`build` made, the one the loop times: `trunk(noisy=...)` as `_loss` runs it,
`[x_t ‖ x_0]` with the noise of a fixed key) is compared with the reference's
explicit-mask forward on the last `last` noisy rows, the error printed on a
`[bench] diffusion reference` line, and a forward over the tolerance raises:
the cell then has no result.  The loop hands `reference_logits` no context, so
`build` keeps the newest one it made (`_BUILT`).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Tuple

from benchmarks.builders.swa_moe_decoder import learning_rate  # the job's warm-up, as `mellum2`'s file states it

# What the program's layers express of this family, and nothing else.
_REQUIRED = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "norm_topk_prob": True,
    "use_sliding_window": False, "sliding_window": None, "mlp_only_layers": [], "decoder_sparse_step": 1,
    "rope_scaling": None,
}
NOISE_KEY = 20251006  # the fixed key of the timed forward's comparison (`reference_logits`)
_BUILT: Dict[int, Any] = {}  # sequence length -> the context `build` made last, for `reference_logits`


def block_length(config: Dict[str, Any]) -> int:
    return int(config["assumed"]["block_length"]["value"])


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"block_diffusion_moe_decoder expresses {_REQUIRED} only, got {differ}")
    if config["qk_norm"] != "per_head":
        raise ValueError(f"qk_norm is 'per_head' (the Qwen3 family's), got {config['qk_norm']!r}")
    block = block_length(config)
    if block < 1 or seq_len % block:
        raise ValueError(f"assumed.block_length {block} does not divide the sequence's {seq_len} tokens")
    share, train, schedule = config["share"], config["train"], config["assumed"]["noise_schedule"]
    if schedule["kind"] != "linear":
        raise ValueError(f"the program draws the linear schedule's noise (weight 1/t), got {schedule['kind']!r}")
    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],  # a dense width no layer of the model uses (`mlp_only_layers` is empty); read by none
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=False,
        rope_theta=float(config["rope_theta"]),
        qk_norm="per_head",
        n_experts=share["num_experts_total"],
        n_experts_held=config["num_experts"],
        first_expert_held=share["first_expert_held"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        norm_topk_prob=True,
        router_activation="softmax",
        router_aux_loss_coef=config["router_aux_loss_coef"],
        routed_branch_init=True,  # `assumed.initial_values`: a row's eight routed outputs start as ONE residual branch
        router_share_init=True,  # `assumed.initial_values`: the router's eight blocks of 16 start equal, 1 choice a share
        diffusion_block=block,
        diffusion_mask_id=config["vocab_size"] - 1,  # `assumed.mask_token_id`: the last row of the slice
        diffusion_eps=float(schedule["eps"]),
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
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer(learning_rate=learning_rate(train)))
    _BUILT.clear()
    _BUILT[seq_len] = ctx
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] of the PLAIN forward for token
    sequences [N, S]; on the way, the TIMED forward's comparison (module
    docstring): the noise is the reference's own copy of the recipe, from the
    fixed key `NOISE_KEY`; the program's side is the built context's
    `apply_diffusion` (a context of its own when `build` made none at this
    length: a test that calls this alone)."""
    import jax
    import numpy as np

    from benchmarks.lib import reference_sdar as ref

    tokens = np.asarray(tokens)
    ctx = _BUILT.get(tokens.shape[1]) or build(config, tokens.shape[1], jax.devices())[1]
    want = ref.logits(config, params, tokens, last=last)
    noisy, _, _ = ref.noise(jax.random.PRNGKey(NOISE_KEY), jax.numpy.asarray(tokens), block=block_length(config),
                            mask_id=ref.mask_id(config), eps=float(config["assumed"]["noise_schedule"]["eps"]))
    noisy = np.asarray(noisy)
    want_noisy = ref.training_logits(config, params, noisy, tokens, last=last)
    errors = []
    for i in range(tokens.shape[0]):
        got = ctx.apply_diffusion(params, noisy[i: i + 1], tokens[i: i + 1])
        errors.append(ref.rel_rms_error(got[0, -last:], want_noisy[i]))
        del got
    tol = diffusion_tolerance(config)
    ok = bool(np.all(np.isfinite(errors)) and max(errors) <= tol)
    print("[bench] diffusion reference " + json.dumps(
        {"rel_rms_error": errors, "tolerance": tol, "rows": 2 * tokens.shape[1], "positions": last, "seqs": len(errors),
         "masked_share": float(np.mean(noisy != tokens)), "ok": ok}), flush=True)
    if not ok:
        raise RuntimeError(f"the training forward's logits on [x_t | x_0] differ from the plain reference: rel rms error "
                           f"{errors} > tolerance {tol}")
    return want


def diffusion_tolerance(config: Dict[str, Any]) -> float:
    """The largest relative RMS error the timed forward's logits may have: the
    loop's own limit on the plain forward at the published width; at another
    width (a rehearsal) the file's looser one (`reference_check.why`)."""
    from benchmarks.lib import reference

    check = config["reference_check"]
    if config["hidden_size"] == check["published_hidden_size"]:
        return reference.tolerance(config["num_hidden_layers"])
    return check["tolerance_at_other_widths"]


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


def _uncut(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published model's three cut keys back in place."""
    share = config["share"]
    return dict(config, num_hidden_layers=share["num_hidden_layers_total"], num_experts=share["num_experts_total"],
                vocab_size=share["vocab_size_total"])


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (every layer, every expert, every row)."""
    if uncut:
        config = _uncut(config)
    d, sizes = config["hidden_size"], _sizes(config)
    norms = 2 * d + 2 * config["head_dim"]
    layer = sizes["attn"] + sizes["router"] + config["num_experts"] * sizes["expert"] + norms
    return 2 * d * config["vocab_size"] + d + config["num_hidden_layers"] * layer  # embedding, head, final norm


def active_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """The parameters one token of the model touches (the "A3B" of the
    published name): attention, the router and `num_experts_per_tok` experts a
    layer, the embedding table and the head."""
    if uncut:
        config = _uncut(config)
    sizes = _sizes(config)
    layer = sizes["attn"] + sizes["router"] + config["num_experts_per_tok"] * sizes["expert"]
    return 2 * config["hidden_size"] * config["vocab_size"] + config["num_hidden_layers"] * layer


# -- needed operations, a DATA token --------------------------------------------------


def routed_rows_per_row(config: Dict[str, Any]) -> float:
    """Rows the held experts multiply per row of the stack under a uniform
    router over all experts: K * held / total (1 at 8 of 128 with 16 held)."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["share"]["num_experts_total"]


def matmul_params_by_part(config: Dict[str, Any]) -> Dict[str, float]:
    """Matmul weights a DATA token multiplies, by part (no embedding table):
    its noisy and its clean row pass every layer, its noisy row the head."""
    sizes, layers = _sizes(config), config["num_hidden_layers"]
    return {
        "attn_proj": 2.0 * layers * sizes["attn"],
        "router": 2.0 * layers * sizes["router"],
        "routed_experts": 2.0 * layers * routed_rows_per_row(config) * sizes["expert"],
        "head": float(config["hidden_size"] * config["vocab_size"]),
    }


def active_matmul_params(config: Dict[str, Any]) -> float:
    return sum(matmul_params_by_part(config).values())


def mask_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs the training mask of one sequence holds: the clean
    copy's block-causal rows, `sum_i (b(i) + 1) * B`, the noisy copy's own
    blocks, `S * B`, and its view of the clean blocks before, `sum_i b(i) *
    B`: `S^2 + S * B` in all."""
    return seq_len * seq_len + seq_len * block


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Softmax attention under the block-diffusion mask, forward + backward
    (3x forward), a DATA token, all layers: QK^T and PV are 2 * H * D flops a
    pair each, `mask_pairs / S = S + B` pairs a data token."""
    pairs = mask_pairs(seq_len, block_length(config)) / seq_len
    return 12.0 * config["num_hidden_layers"] * pairs * config["num_attention_heads"] * config["head_dim"]


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * the matmul weights a data token multiplies + attention at the mask's true pairs."""
    return 6.0 * active_matmul_params(config) + attention_flops_per_token(config, seq_len)


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls' needed FLOPs, forward + backward, for `rows` rows
    given to held experts (summed over the layers): three matrices of
    d x width a row, 2 flops a multiply-add, 3x forward."""
    return 6.0 * rows * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def distortion(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """What the cut does to the model's proportions, as the file's `distortion` states it."""
    def shares(c):
        needed = needed_flops_per_token(c, seq_len)
        out = {f"{name}_pct": 100.0 * 6.0 * value / needed for name, value in matmul_params_by_part(c).items()}
        out["attention_pct"] = 100.0 * attention_flops_per_token(c, seq_len) / needed
        return needed, out

    needed, here = shares(config)
    needed_model, model = shares(_uncut(config))
    share = config["share"]
    rows = 2 * seq_len * config["num_experts_per_tok"] / share["num_experts_total"]
    return {"needed_mflop_per_token": needed / 1e6, **here,
            "needed_mflop_per_token_model": needed_model / 1e6, **{k + "_model": v for k, v in model.items()},
            "rows_per_held_expert_uniform": rows, "rows_per_held_expert_deployed": share["chips_per_layer"] * rows}
