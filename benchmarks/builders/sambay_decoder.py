"""kind = "sambay_decoder": Phi-4-mini-flash-reasoning's stack (`model_type:
phi4flash`, the SambaY decoder-hybrid-decoder): Mamba-1 layers alternating
with differential attention (a 512 window, one full layer), then Gated Memory
Units that read one Mamba-1 layer's scan output alternating with differential
cross-attention over the full layer's K and V; LayerNorm with bias, biases on
attention's projections, no positional encoding, a tied head; run through the
program's `TransformerConfig` + `LMTrainContext` like the other kinds.

The configuration is ONE CHIP'S SHARE of a deployment: `vocab_size` is one of
`share.chips_per_layer` vocabulary-parallel slices, `num_hidden_layers` counts
the layers that run, `layer_indices` names them by their published indices.
Nothing here or in the program stands in for the absent chips.

What follows a rehearsal's overrides (`run.py`'s `REHEARSAL_CONFIG`: d 256,
2 / 1 heads, 2 layers): the heads follow the WIDTH at the published head size
64 and the published ratio 2 : 1 (`reference_sambay.heads`: 4 and 2 at d 256;
one kv head cannot pair), the Mamba width `expand * hidden_size`, `dt_rank`
`ceil(hidden_size / 16)`, and the layers are the first `num_hidden_layers`
entries of `layer_indices` (a two-layer rehearsal is one Mamba-1 and one
window layer: it reaches no memory, GMU or cross layer, which the CPU tests
cover).

The builder's four names, plus the counts the new rooflines divide by.  Needed
operations: 6 x every matmul weight (the head's slice once, no embedding
table); causal differential attention, forward + backward, at `keys *
(64 + 128)` a map and position and direction-weight 3, `keys` the mean number
a query sees (S / 2 in the full and cross layers, the window less its ramp in
the windowed ones); the scan at `9 * channels * N` a token, layer and
direction.  Recompute is never credited.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.lib import reference_sambay

HEAD_DIM = reference_sambay.HEAD_DIM
S6_EXPAND, S6_STATE, S6_CONV = 2, 16, 4  # not keys of the source: `assumed.mamba` in the configuration file

# What the program's layers express, and nothing else.
_REQUIRED = {"hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
             "mb_per_layer": 2, "embd_pdrop": 0, "resid_pdrop": 0}

layer_kinds = reference_sambay.layer_kinds
heads = reference_sambay.heads


def s6_inner(config: Dict[str, Any]) -> int:
    return S6_EXPAND * config["hidden_size"]


def dt_rank(config: Dict[str, Any]) -> int:
    return -(-config["hidden_size"] // 16)


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    differ = {k: config.get(k) for k, v in _REQUIRED.items() if config.get(k) != v}
    if differ:
        raise ValueError(f"sambay_decoder expresses {_REQUIRED} only, got {differ}")
    kinds, train = layer_kinds(config), config["train"]
    indices = [l for _, l in kinds]
    q_heads, kv_heads = heads(config)

    def position_of(index):  # in THIS stack, of a published index; None when the cut leaves it out
        return indices.index(index) if index in indices else None

    return dict(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=q_heads,
        n_kv_heads=kv_heads,
        d_ff=config["intermediate_size"],
        norm_eps=config["layer_norm_eps"],
        norm_kind="layer",
        attn_bias=True,
        tie_embeddings=True,
        rope_theta=None,  # the source has none
        layer_types=tuple(kind for kind, _ in kinds),
        layer_ids=tuple(indices),
        layer_windows=tuple(
            config["sliding_window"] if kind == "diff_attention" and l < reference_sambay.MEMORY_LAYER else None
            for kind, l in kinds),
        s6_inner=s6_inner(config),
        s6_state=S6_STATE,
        s6_conv=S6_CONV,
        s6_dt_rank=dt_rank(config),
        s6_memory_layer=position_of(reference_sambay.MEMORY_LAYER),
        kv_source_layer=position_of(reference_sambay.KV_LAYER),
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
    return reference_sambay.logits(config, params, tokens, last=last)


# -- parameters -------------------------------------------------------------------


def _sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights of one mixer of each kind, and of the FFN."""
    d, inner, rank = config["hidden_size"], s6_inner(config), dt_rank(config)
    q_heads, kv_heads = heads(config)
    q_wide, kv_wide = q_heads * HEAD_DIM, 2 * kv_heads * HEAD_DIM
    return {
        "s6": d * 2 * inner + inner * (rank + 2 * S6_STATE) + rank * inner + inner * d,  # W_in, W_x, W_dt, W_out
        "diff_attention": d * (q_wide + kv_wide) + q_wide * d,  # W_qkv, W_o
        "gmu": 2 * d * inner,  # W_1, W_2
        "diff_cross": 2 * d * q_wide,  # W_q, W_o
        "ffn": 3 * d * config["intermediate_size"],
    }


def _other_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Stored leaves of one mixer that multiply nothing."""
    d, inner = config["hidden_size"], s6_inner(config)
    q_heads, kv_heads = heads(config)
    q_wide, kv_wide = q_heads * HEAD_DIM, 2 * kv_heads * HEAD_DIM
    lambdas_and_norm = 4 * HEAD_DIM + 2 * HEAD_DIM
    return {
        "s6": inner * (S6_CONV + 1) + inner + inner * S6_STATE + inner,  # convolution, dt_bias, A_log, D
        "diff_attention": q_wide + kv_wide + d + lambdas_and_norm,  # b_qkv, b_o
        "gmu": 0,
        "diff_cross": q_wide + d + lambdas_and_norm,  # b_q, b_o
    }


def total_params(config: Dict[str, Any], uncut: bool = False) -> int:
    """Every stored parameter of the configuration as it runs here; with
    `uncut`, of the published model (all 32 layers, every row of the table)."""
    if uncut:
        share = config["share"]
        config = dict(config, num_hidden_layers=share["num_hidden_layers_total"],
                      vocab_size=share["vocab_size_total"],
                      layer_indices=list(range(share["num_hidden_layers_total"])))
    d = config["hidden_size"]
    sizes, other = _sizes(config), _other_params(config)
    total = d * config["vocab_size"] + 2 * d  # the tied table, the final LayerNorm
    for kind, _ in layer_kinds(config):
        total += sizes[kind] + other[kind] + sizes["ffn"] + 4 * d  # two LayerNorms with bias
    return total


# -- needed operations --------------------------------------------------------------


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul weights a token multiplies: every mixer's, every FFN's, the tied
    head's slice once; no embedding table."""
    sizes = _sizes(config)
    return (sum(sizes[kind] + sizes["ffn"] for kind, _ in layer_kinds(config))
            + config["hidden_size"] * config["vocab_size"])


def _attention_layers(config: Dict[str, Any]) -> Tuple[int, int]:
    """(windowed, full-causal: the full layer and the cross layers) among the layers that run."""
    kinds = layer_kinds(config)
    windowed = sum(1 for kind, l in kinds if kind == "diff_attention" and l < reference_sambay.MEMORY_LAYER)
    return windowed, sum(1 for kind, _ in kinds if kind in ("diff_attention", "diff_cross")) - windowed


def _map_flops_per_token(config: Dict[str, Any], keys: float) -> float:
    """One layer's differential attention, forward + backward (3 x forward),
    per token: `n_heads` softmax maps, each `2 * keys * 64` for q k^T and
    `2 * keys * 128` for the values of twice the width, `keys` the mean
    number of keys a query sees."""
    return 3.0 * heads(config)[0] * 2.0 * keys * (HEAD_DIM + 2 * HEAD_DIM)


def swa_attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """The windowed layers: a query sees `min(i + 1, w)` keys, w less the
    ramp of the first w positions on the mean."""
    w = min(config["sliding_window"], seq_len)
    return _attention_layers(config)[0] * _map_flops_per_token(config, w - w * (w - 1) / (2.0 * seq_len))


def full_attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """The full layer and the cross layers: a query sees (S + 1) / 2 keys on the mean."""
    return _attention_layers(config)[1] * _map_flops_per_token(config, (seq_len + 1) / 2.0)


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """All differential attention of the layers that run.  The readers that
    divide this by `num_hidden_layers` (the three `flash_*_roofline`) read a
    blend in this configuration, whose flash kernels lie in five layers of ten
    and do unequal work; `swa_attn_roofline` and `full_attn_roofline` are the
    shares that mean what they say here."""
    return swa_attention_flops_per_token(config, seq_len) + full_attention_flops_per_token(config, seq_len)


def s6_layers(config: Dict[str, Any]) -> int:
    return sum(1 for kind, _ in layer_kinds(config) if kind == "s6")


def s6_scan_flops_per_token(config: Dict[str, Any]) -> float:
    """The selective scan at `9 * channels * N` a token, layer and direction
    (Mamba's own count of its forward, arXiv:2312.00752 section 3.3; the
    backward, a reversed scan of the same shape, counted as much), all Mamba-1
    layers.  Elementwise work: no part of it is a matmul."""
    return s6_layers(config) * 2.0 * 9.0 * s6_inner(config) * S6_STATE


def s6_scan_bytes_per_token(config: Dict[str, Any]) -> float:
    """What a fused scan must move a token, all Mamba-1 layers, both
    directions: forward x, z (bf16) and dt (float32) read, y (bf16) written,
    B and C (bf16) read; backward the same arrays again and a cotangent for
    each of them (twice the forward).  The states never leave the chip's
    on-core memory in this count."""
    forward = s6_inner(config) * (2 + 2 + 4 + 2) + 2 * S6_STATE * 2
    return s6_layers(config) * 3.0 * forward


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6 * matmul weights + differential attention + the scan."""
    return (6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)
            + s6_scan_flops_per_token(config))
