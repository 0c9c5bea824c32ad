"""kind "kimi_linear_decoder": the configuration file against the catalog's
row key for key, the three cuts the guide names and nothing else, the
builder's parameter and operation counts against counts worked by hand,
`trace_kimi`'s names on path strings, the readers on runs with nothing to
read, and the cell's rehearsal on the CPU (the tier-1 copy of the comparison
with the reference is tests/test_kimi_linear_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import kimi_linear_decoder as builder  # noqa: E402
from benchmarks.lib import trace_kimi, trace_moe, trace_scopes  # noqa: E402

CELL = "kimi-linear-ep16-1chip.seq16k"
with open(os.path.join(ROOT, "benchmarks", "configs", "kimi-linear-48b-a3b-ep16-1chip.json")) as f:
    KIMI = json.load(f)

# The `config` of the catalog row Kimi-Linear-48B-A3B-Instruct (model-configs guide), every key.
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}
PAIRS = [("kda", "dense"), ("kda", "experts"), ("kda", "experts"), ("mla", "experts"), ("kda", "experts")]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["config"] == CATALOG and row["source_url"] == KIMI["source"]
    differ = {k for k, v in CATALOG.items() if k not in KIMI or KIMI[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(KIMI["reduced"])
    assert KIMI["reduced"] == {"num_hidden_layers": {"from": 27, "to": 5}, "num_experts": {"from": 256, "to": 16},
                               "vocab_size": {"from": 163840, "to": 20480}}
    assert (KIMI["num_hidden_layers"], KIMI["num_experts"], KIMI["vocab_size"]) == (5, 16, 20480)
    # the guide's floors: a whole period and four layers behind the leading dense one, 8 experts, an eighth of the rows
    assert KIMI["num_hidden_layers"] - KIMI["first_k_dense_replace"] >= 4 and KIMI["num_experts"] >= 8
    assert KIMI["vocab_size"] * 8 >= CATALOG["vocab_size"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-linear-48b-a3b-ep16-1chip")
    assert sorted(entry["reduced"]) == sorted(KIMI["reduced"]) and entry["source"] == KIMI["source"]
    # the deployment the share stands for, stated beside the cuts
    share = KIMI["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (16, 256, 0)
    assert share["num_experts_total"] == CATALOG["num_experts"] == share["chips_per_layer"] * KIMI["num_experts"]
    assert share["vocab_size_total"] == CATALOG["vocab_size"] and share["num_hidden_layers_total"] == 27
    assert KIMI["train"]["chips"] == 1 and KIMI["train"]["remat_policy"] in (None, "attn", "qkv_attn")
    assert {"bias", "kda_gates", "kda_activations", "kda_chunk", "mla", "router", "dtypes", "initial_values",
            "optimizer_state_dtype", "optimizer_hyperparameters", "document_boundaries"} <= set(KIMI["assumed"])
    assert KIMI["deployment"] and "0.5 routed assignments" in KIMI["distortion"] and "512 rows" in KIMI["distortion"]


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_nine_readers():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "seq16k", "kimi-linear-48b-a3b-ep16-1chip")
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (16384, 1) and config["kind"] == "kimi_linear_decoder"
    mistral = next(w for w in bench["workloads"] if w["name"] == "mistral7b-1chip.seq16k")
    assert mistral["traffic"] == cell["traffic"]  # the two cells differ by the model alone
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == ["kda_proj_time_pct", "kda_conv_time_pct", "kda_scan_time_pct", "mla_proj_time_pct",
                   "moe_shared_time_pct", "moe_routed_time_pct", "moe_held_rows_per_expert", "kda_scan_roofline",
                   "mla_attn_roofline"]
    readers = harness.layer_metric_readers()
    assert all(readers[name].cells == [CELL] for name in own)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1  # the quarter of seven cells, rounded down


def test_model_kwargs_describe_the_published_block_and_the_share():
    kw = builder.model_kwargs(KIMI, 16384)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["d_ff"], kw["vocab_size"]) == (2304, 5, 32, 9216, 20480)
    assert list(zip(kw["layer_types"], kw["ffn_types"])) == PAIRS == builder.layer_pairs(KIMI)
    assert (kw["kda_heads"], kw["kda_head_dim"], kw["kda_conv"]) == (32, 128, 4)
    assert (kw["kv_lora_rank"], kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"]) == (512, 128, 64, 128)
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"]) == (256, 16, 0, 8)
    assert (kw["moe_d_ff"], kw["n_shared_experts"], kw["router_activation"], kw["routed_scaling_factor"]) == \
        (1024, 1, "sigmoid", 2.446)
    assert kw["norm_topk_prob"] is True and kw["rope_theta"] is None and kw["tie_embeddings"] is False
    for key, value in {"mla_use_nope": False, "moe_router_activation_func": "softmax", "num_expert_group": 8,
                       "q_lora_rank": 1536, "num_nextn_predict_layers": 1}.items():
        with pytest.raises(ValueError):
            builder.model_kwargs(dict(KIMI, **{key: value}), 16384)
    # the harness's rehearsal overrides six keys: depth 2 = KDA + dense, KDA + experts (no MLA layer), the mixers'
    # and the experts' own widths untouched
    toy = builder.model_kwargs(dict(KIMI, **harness.REHEARSAL_CONFIG), 256)
    assert list(zip(toy["layer_types"], toy["ffn_types"])) == PAIRS[:2] and toy["d_model"] == 256
    assert (toy["kda_heads"], toy["kda_head_dim"], toy["moe_d_ff"], toy["n_experts"], toy["n_experts_held"]) == \
        (32, 128, 1024, 256, 16)


def test_parameter_counts_by_hand():
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32  # q, k, v, o; two low-rank gates; beta
    kda_other = 3 * 4096 * 4 + 32 + 4096 + 128  # convolutions, A_log, dt_bias, norm
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    dense, expert, router = 3 * 2304 * 9216, 3 * 2304 * 1024, 2304 * 256
    assert (kda + kda_other, mla + 512, dense, expert, router) == (39_514_272, 29_114_880, 63_700_992, 7_077_888, 589_824)
    norms = 2 * 2304
    expert_layer = router + 256 + expert + 16 * expert  # bias, shared expert, the 16 held
    cut = (2 * 20480 * 2304 + 2304 + (kda + kda_other + dense + norms) + 3 * (kda + kda_other + expert_layer + norms)
           + (mla + 512 + expert_layer + norms))
    assert builder.total_params(KIMI) == cut == 828_926_848  # 828.9M: 6.63 GB of state and gradients at 8 B
    assert round(builder.total_params(KIMI, uncut=True) / 1e9, 2) == 49.12  # "48B"
    # the program counts the same, leaf for leaf
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    kw = builder.model_kwargs(KIMI, 16384)
    kw.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params() == cut


def test_needed_flops_by_hand():
    assert builder.routed_rows_per_token(KIMI) == 0.5  # 8 choices among 256, 16 of them held
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    expert = 3 * 2304 * 1024
    active = 2304 * 20480 + (kda + 3 * 2304 * 9216) + 3 * (kda + 2304 * 256 + expert + 0.5 * expert) \
        + (mla + 2304 * 256 + expert + 0.5 * expert)
    assert builder.active_matmul_params(KIMI) == active == 342_671_360
    # causal attention in the ONE MLA layer: 3 x S x 32 heads x (192 + 128) forward + backward
    assert builder.mla_layers(KIMI) == 1
    assert builder.attention_flops_per_token(KIMI, 16384) == 3 * 16384 * 32 * 320 == 503_316_480
    # the recurrence at chunk 64 in FOUR layers: 3 x 32 heads x (64 x (3*128 + 2*128) + 6 x 128 x 128)
    assert builder.kda_scan_flops_per_token(KIMI) == 4 * 3 * 32 * (64 * 640 + 6 * 16384) == 53_477_376
    needed = builder.needed_flops_per_token(KIMI, 16384)
    assert needed == 6 * 342_671_360 + 503_316_480 + 53_477_376 == 2_612_822_016  # 42.8 TFLOP a step of 16,384
    shares = {"mla attention": 503_316_480, "routed": 6 * 4 * 0.5 * expert, "head": 6 * 2304 * 20480,
              "mixers": 6 * (4 * kda + mla) + 503_316_480 + 53_477_376}
    assert {k: round(100 * v / needed, 1) for k, v in shares.items()} == \
        {"mla attention": 19.3, "routed": 3.3, "head": 10.8, "mixers": 64.2}
    # the three flash readers divide by ALL five layers (PERF.md section 3): a fifth of this
    assert builder.attention_flops_per_token(KIMI, 16384) / KIMI["num_hidden_layers"] == 100_663_296


PATHS = {
    "kda-proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/kda/proj/"
                 "bse,ef->bsf/dot_general", "kda/proj", ("layer/attn_proj", "fwd")),
    "kda-conv-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/"
                          "attn_proj/kda/conv/mul", "kda/conv", ("layer/attn_proj", "bwd")),
    "scan-segment-recompute": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
                               "rematted_computation/layer/attn_core/kda/scan/while/body/checkpoint/"
                               "rematted_computation/triangular_solve:", "kda/scan", ("layer/attn_core", "recompute")),
    "mla-proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/mla/proj/"
                 "bsr,rhd->bshd/dot_general", "mla/proj", ("layer/attn_proj", "fwd")),
    "shared-expert": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/shared/"
                      "bse,ef->bsf/dot_general", "moe/shared", ("layer/mlp", "fwd")),
    "routed-experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/experts/"
                       "moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "flash-kernel": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/flash_fwd/"
                     "pallas_call", None, ("flash_fwd", "fwd")),
    "look-alike": ("jit(_train_step)/jvp(layers)/while/body/layer/attn_core/not_kda/scan_sum/add", None,
                   ("layer/attn_core", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_trace_kimi_lends_trace_moe_its_names_and_gives_them_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe._COMPONENT
    with trace_kimi._names_of_trace_moe(trace_kimi.NAMES):
        assert trace_moe.classify(path) == name
    assert (trace_moe.NAMES, trace_moe._COMPONENT) == before  # the accepted reader reads what it read
    assert trace_moe.classify(path) == (name if name in trace_moe.NAMES else None)
    if scope is not None:  # the existing reduction is unchanged by the names inside its scopes
        assert trace_scopes.classify(path) == scope


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What the parent gives them (no such span, no such counter): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[m["name"]] for m in harness.load_benchmark()["per_layer"] if m.get("workloads") == [CELL]]
    assert len(own) == 9
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    # a record from before the counter (the parent's) reads as nothing
    assert readers["moe_held_rows_per_expert"].read({"run_record": {"spans": [], "stalls": []}}) is None
    assert readers["moe_held_rows_per_expert"].read(
        {"run_record": {"step_counters": {"moe_held_rows_mean": 512.0, "moe_held_rows_max": 900.0}}}) == 512.0
    # a recorded trace of a program without the names (a dense step): nothing
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")
    with trace_kimi._names_of_trace_moe(trace_kimi.NAMES):
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got is None or not any(got["seconds"].values())


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", "3",
         "--seconds", "10", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert "moe_held_rows_per_expert" in line["metric_names"]  # the counter reaches the reader through the run's record
