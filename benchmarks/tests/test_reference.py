"""The plain reference against the program's forward at a tiny size on the
CPU, and the tolerance against what it has to catch."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.builders import dense_decoder  # noqa: E402
from benchmarks.lib import reference  # noqa: E402

TINY = {
    "kind": "dense_decoder", "hidden_size": 256, "intermediate_size": 512,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 320, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "train": {"chips": 1, "mesh": {"data": 1}, "strategy": "dp",
              "param_dtype": "float32", "compute_dtype": "float32",
              "optimizer": "default_optimizer", "remat_policy": "qkv_attn"},
}
SEQ = 1024  # two query blocks, so the blocked causal mask is exercised


def _program_logits(config, seed=0, quantize=None):
    cfg, ctx = dense_decoder.build(config, SEQ, jax.devices()[:1])
    params = ctx.init_state(seed=seed)["params"]
    if quantize is not None:
        params = jax.tree_util.tree_map(quantize, params)
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (1, SEQ), dtype=np.int32)
    return params, tokens, ctx.apply(params, tokens)[0]


def test_reference_agrees_with_program_forward_in_float32():
    params, tokens, got = _program_logits(TINY)
    want = reference.logits(TINY, params, tokens, last=SEQ)[0]
    assert want.shape == got.shape == (SEQ, TINY["vocab_size"])
    assert reference.rel_rms_error(got, want) < 2e-5


def test_last_positions_equal_the_tail_of_the_full_result():
    params, tokens, _ = _program_logits(TINY)
    full = reference.logits(TINY, params, tokens, last=SEQ)[0]
    tail = reference.logits(TINY, params, tokens, last=256)[0]
    np.testing.assert_array_equal(np.asarray(full[-256:]), np.asarray(tail))


def test_bf16_program_passes_and_what_must_fail_fails():
    bf16 = dict(TINY, train=dict(TINY["train"], param_dtype="bfloat16", compute_dtype="bfloat16"))
    tol = reference.tolerance(bf16["num_hidden_layers"])
    params, tokens, got = _program_logits(bf16)
    want = reference.logits(bf16, params, tokens, last=SEQ)[0]
    assert reference.rel_rms_error(got, want) < tol

    # a dropped causal mask
    unmasked = reference.logits(bf16, params, tokens, last=SEQ, causal=False)[0]
    assert reference.rel_rms_error(unmasked, want) > 5 * tol

    # int8 weights (per-tensor symmetric fake quantization) through the program
    def int8(a):
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a.astype(jnp.float32))) / 127.0
        return (jnp.round(a.astype(jnp.float32) / scale) * scale).astype(a.dtype)

    _, _, got_q = _program_logits(bf16, quantize=int8)
    assert reference.rel_rms_error(got_q, want) > 2 * tol


@pytest.mark.parametrize("layers,want", [(4, 0.024), (12, 0.012 * 12 ** 0.5), (24, 0.012 * 24 ** 0.5)])
def test_tolerance_values(layers, want):
    assert reference.tolerance(layers) == pytest.approx(want)
