"""kind = "dense_decoder": a pre-norm RMSNorm / RoPE / SwiGLU / GQA decoder,
run through the program's `TransformerConfig` + `LMTrainContext`.

A builder is the one place that knows how a configuration file of its kind
becomes the program's objects, which plain reference checks it and which
function counts its needed operations.  A later model kind brings
`benchmarks/builders/<kind>.py` with the same four names.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.lib import flops

# Published (Hugging Face) key -> TransformerConfig field.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_kwargs(config: Dict[str, Any], seq_len: int) -> Dict[str, Any]:
    """TransformerConfig keyword arguments as plain data (dtypes as names)."""
    if config.get("sliding_window") or config.get("hidden_act", "silu") != "silu":
        raise ValueError("dense_decoder expresses full attention and SwiGLU(silu) only")
    if flops.head_dim(config) * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("dense_decoder needs head_dim == hidden_size / num_attention_heads")
    kw = {field: config[key] for key, field in _KEYS.items()}
    train = config["train"]
    kw.update(
        max_seq_len=seq_len,
        dtype=train["compute_dtype"],
        param_dtype=train["param_dtype"],
        remat=True,
        remat_policy=train["remat_policy"],  # null = full per-layer recompute
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
    chips = train["chips"]
    mesh = build_mesh(MeshSpec(**train["mesh"]), devices=list(devices)[:chips])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=train["strategy"],
                         optimizer=default_optimizer())
    return cfg, ctx


def reference_logits(config: Dict[str, Any], params, tokens, last: int):
    """Plain-reference logits [N, last, V] for token sequences [N, S]."""
    from benchmarks.lib import reference

    return reference.logits(config, params, tokens, last=last)


needed_flops_per_token = flops.needed_flops_per_token
attention_flops_per_token = flops.attention_flops_per_token
