"""Operations a training step NEEDS, from shapes alone.  The yardstick for
`mfu_pct` and `attn_kernel_roofline_pct`: recomputed work (remat, the flash
backward's second forward) is never credited, attention is the CAUSAL count,
and a table lookup (the embedding) is not a matmul.

All functions take the configuration as the plain dict of a
`benchmarks/configs/*.json` file (the published key names) and import
nothing from the program under test.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def head_dim(config: Dict[str, Any]) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def matmul_params_per_layer(config: Dict[str, Any]) -> int:
    """Weights that are the right-hand side of a matmul in one decoder layer:
    wq, wk, wv, wo and the three SwiGLU matrices.  Norm scales are not."""
    d, hd = config["hidden_size"], head_dim(config)
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * hd * (2 * h + 2 * hkv)
    mlp = 3 * d * config["intermediate_size"]
    return attn + mlp


def matmul_params(config: Dict[str, Any]) -> int:
    """N_mm: every matmul weight including `lm_head`, EXCLUDING the embedding
    table (a gather).  With tied embeddings the table still multiplies once,
    as the head."""
    head = config["hidden_size"] * config["vocab_size"]
    return config["num_hidden_layers"] * matmul_params_per_layer(config) + head


def total_params(config: Dict[str, Any]) -> int:
    """Every stored parameter (state-size arithmetic, not FLOPs)."""
    d, v = config["hidden_size"], config["vocab_size"]
    embed = d * v
    head = 0 if config.get("tie_word_embeddings") else d * v
    norms = config["num_hidden_layers"] * 2 * d + d
    layers = config["num_hidden_layers"] * matmul_params_per_layer(config)
    return embed + head + norms + layers


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Causal softmax attention, forward + backward, per token, all layers.

    Forward per token and layer: QK^T and PV are 2*H*D flops per attended
    key each; a causal query at position t attends t+1 keys, (S+1)/2 on
    average, taken as S/2.  So forward = 4 * (S/2) * H * D = 2*S*H*D, and
    forward + backward (2x forward) = 6*S*H*D.  H is the QUERY head count:
    GQA shares keys, not arithmetic."""
    h, hd = config["num_attention_heads"], head_dim(config)
    return 6.0 * config["num_hidden_layers"] * seq_len * h * hd


def needed_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """6*N_mm + 6*L*S*H*D (forward 2, backward 4, per matmul weight)."""
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of one chip of exactly this `device_kind`; KeyError if the
    table has no such kind."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "benchmarks/lib/peaks.json with its source"
        )
    return table[device_kind]
