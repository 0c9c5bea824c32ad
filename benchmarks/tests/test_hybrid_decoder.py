"""kind "hybrid_decoder": the configuration file against its source, the
builder's operation counts against counts worked by hand, `trace_ssm`'s
classification on path strings, and the plain reference against the program
at a tiny size through the builder (the tier-1 copy of that comparison is
tests/test_hybrid_model.py)."""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.builders import hybrid_decoder  # noqa: E402
from benchmarks.lib import reference_hybrid, trace_scopes, trace_ssm  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs", "granite-4.0-h-micro-1chip.json")) as f:
    GRANITE = json.load(f)

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# The `config` of the catalog row granite-4.0-h-micro (model-configs guide), every key.
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


def test_widths_equal_the_source_and_only_depth_is_cut():
    differ = {k for k, v in CATALOG.items() if k not in GRANITE or GRANITE[k] != v}
    assert differ == {"num_hidden_layers"} == set(GRANITE["reduced"])
    assert GRANITE["reduced"]["num_hidden_layers"] == {"from": 40, "to": GRANITE["num_hidden_layers"]} == \
        {"from": 40, "to": 10}
    assert GRANITE["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    # one whole period of the published pattern, attention at its published offset
    assert hybrid_decoder.layer_kinds(GRANITE) == PERIOD
    assert GRANITE["train"]["remat_policy"] == "qkv_attn" and GRANITE["train"]["chips"] == 1
    assert {"layer_types", "head_dim", "ffn_width", "dtypes", "initial_values", "optimizer_state_dtype",
            "document_boundaries"} <= set(GRANITE["assumed"])
    assert GRANITE["deployment"] and GRANITE["distortion"]


def test_model_kwargs_describe_the_published_block():
    kw = hybrid_decoder.model_kwargs(GRANITE, 8192)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["n_kv_heads"], kw["d_ff"]) == (2048, 10, 32, 8, 8192)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"], kw["ssm_conv"]) == (64, 64, 128, 4)
    assert kw["ssm_heads"] == GRANITE["mamba_n_heads"]  # derived from the expansion, equal at the published width
    assert kw["layer_types"] == tuple(PERIOD) and kw["rope_theta"] is None and kw["tie_embeddings"] is True
    assert (kw["embedding_multiplier"], kw["residual_multiplier"], kw["logits_scaling"], kw["attention_scale"]) == \
        (12, 0.22, 8, 1 / 64)
    for key, value in {"num_local_experts": 8, "mamba_n_groups": 2, "attention_bias": True}.items():
        with pytest.raises(ValueError):
            hybrid_decoder.model_kwargs(dict(GRANITE, **{key: value}), 8192)
    # the harness's rehearsal overrides widths and depth: heads and kinds follow
    toy = hybrid_decoder.model_kwargs(dict(GRANITE, hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
                                           num_hidden_layers=2), 256)
    assert toy["ssm_heads"] == 8 and toy["layer_types"] == ("mamba", "mamba")


def test_needed_flops_by_hand():
    # a Mamba-2 layer: in_proj 2048 x (4096 + 4096 + 2*128 + 64) = 17,432,576; out_proj 4096 x 2048 = 8,388,608
    mamba = 17_432_576 + 8_388_608
    attention = 2048 * 64 * (2 * 32 + 2 * 8)  # wq, wo at 32 heads, wk, wv at 8: 10,485,760
    ffn, head = 3 * 2048 * 8192, 2048 * 100352
    assert (mamba, attention, ffn, head) == (25_821_184, 10_485_760, 50_331_648, 205_520_896)
    assert hybrid_decoder.matmul_params(GRANITE) == 9 * mamba + attention + 10 * ffn + head == 951_713_792
    # causal attention fwd+bwd at 8,192 in the ONE attention layer: 6 x 8192 x 32 heads x 64
    assert hybrid_decoder.attention_flops_per_token(GRANITE, 8192) == 6 * 8192 * 2048 == 100_663_296
    # the scan at the published chunk: 3 x 64 heads x (256 x (128 + 64) + 4 x 64 x 128) a layer
    assert hybrid_decoder.ssd_flops_per_token(GRANITE) == 9 * 3 * 64 * (256 * 192 + 4 * 64 * 128) == 141_557_760
    needed = hybrid_decoder.needed_flops_per_token(GRANITE, 8192)
    assert needed == 6 * 951_713_792 + 100_663_296 + 141_557_760 == 5_952_503_808
    # the distortion the file states, at 10 layers and at 40
    full = dict(GRANITE, num_hidden_layers=40)
    for cfg, want in ((GRANITE, (20.7, 50.7, 23.4, 2.4, 1.7)), (full, (6.1, 60.1, 27.7, 2.8, 2.0))):
        layers = cfg["num_hidden_layers"]
        total = hybrid_decoder.needed_flops_per_token(cfg, 8192)
        shares = (6 * head, 6 * layers * ffn, 6 * 0.9 * layers * mamba, hybrid_decoder.ssd_flops_per_token(cfg),
                  hybrid_decoder.attention_flops_per_token(cfg, 8192))
        assert [round(100 * s / total, 1) for s in shares] == pytest.approx(want, abs=0.06)


PATHS = {
    "proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/ssm/proj/bse,ef->bsf/"
             "dot_general", "ssm/proj", ("layer/attn_proj", "fwd")),
    "conv-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_proj/"
                      "ssm/conv/mul", "ssm/conv", ("layer/attn_proj", "bwd")),
    "scan-recompute": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
                       "rematted_computation/layer/attn_core/ssm/scan/bchts,bcshp->bcthp/dot_general:", "ssm/scan",
                       ("layer/attn_core", "recompute")),
    "scan-inner-loop": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/ssm/scan/"
                        "while/body/mul", "ssm/scan", ("layer/attn_core", "fwd")),
    "transposed-name": ("jit(_train_step)/transpose(jvp(ssm/scan))/mul", "ssm/scan", None),
    "attention-layer": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/"
                        "bse,ehd->bshd/dot_general", None, ("layer/attn_proj", "fwd")),
    "look-alike": ("jit(_train_step)/jvp(layers)/while/body/layer/attn_core/not_ssm/scan_sum/add", None,
                   ("layer/attn_core", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_trace_ssm_takes_the_innermost_ssm_name_and_trace_scopes_still_says_the_mixer_scope(path, name, scope):
    assert trace_ssm.classify(path) == name
    if scope is not None:  # the existing reduction is unchanged by the names inside its scopes
        assert trace_scopes.classify(path) == scope


def test_readers_read_nothing_from_a_run_without_a_trace_or_without_the_names():
    from benchmarks.layer_metrics import ssm_conv_time_pct, ssm_proj_time_pct, ssm_scan_roofline, ssm_scan_time_pct

    readers = (ssm_proj_time_pct, ssm_conv_time_pct, ssm_scan_time_pct, ssm_scan_roofline)
    assert all(r.read({"trace": None}) is None and r.read({}) is None for r in readers)
    # a trace file that cannot be read is said on one line and reads as nothing
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}}
    assert all(r.read(run) is None for r in readers)
    # a recorded trace of a program without the names (a dense step): nothing, and no exception
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")
    got = trace_ssm.reduce_ssm(recorded, window_span="bench_step")
    assert got is None or not any(got["seconds"].values())


TINY = {
    "kind": "hybrid_decoder", "hidden_size": 128, "shared_intermediate_size": 256, "intermediate_size": 256,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 320, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "position_embedding_type": "nope", "tie_word_embeddings": True, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 4, "mamba_proj_bias": False, "num_local_experts": 0,
    "train": {"chips": 1, "mesh": {"data": 1}, "strategy": "dp", "param_dtype": "float32",
              "compute_dtype": "float32", "optimizer": "default_optimizer", "remat_policy": "qkv_attn"},
}


def test_reference_agrees_with_the_program_through_the_builder():
    """SEQ 1024 = four chunks of 256 and two query blocks; the published head
    size, state size and softmax scale; four of `layer_types`' five entries."""
    cfg, ctx = hybrid_decoder.build(TINY, 1024, jax.devices()[:1])
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba") and cfg.ssm_heads == 4
    params = ctx.init_state(seed=0)["params"]
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 1024), dtype=np.int32)
    want = hybrid_decoder.reference_logits(TINY, params, tokens, 256)
    got = ctx.apply(params, tokens)[0, -256:]
    assert want.shape == (1, 256, TINY["vocab_size"])
    assert reference_hybrid.rel_rms_error(got, want[0]) < 1e-4
