"""kind "nemotron_h_decoder": the configuration file against the catalog's
row key for key, the three cuts the guide names and nothing else, the
builder's parameter and operation counts against counts worked by hand,
`trace_nemotron_h`'s names on path strings, its rows from a record's series,
the readers on runs with nothing to read, and the cell's rehearsal on the CPU
(the tier-1 copy of the comparison with the reference is
tests/test_nemotron_h_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import nemotron_h_decoder as builder  # noqa: E402
from benchmarks.lib import trace_kimi, trace_moe, trace_nemotron_h, trace_scopes  # noqa: E402

CELL = "nemotron3-nano-ep8-1chip.seq8k"
NAME = "nemotron-3-nano-30b-a3b-ep8-1chip"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    NANO = json.load(f)

# The `config` of the catalog row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (model-configs guide), every key.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
PAIRS = [("mamba", "experts"), ("mamba", "experts"), ("mamba", "none"), ("attention", "experts"), ("mamba", "experts")]
OWN = ["nh_ssm_proj_time_pct", "nh_ssm_conv_time_pct", "nh_ssm_scan_time_pct", "nh_ssm_scan_roofline",
       "relu2_shared_time_pct", "relu2_routed_time_pct", "relu2_experts_roofline", "gqa16_attn_roofline",
       "nh_held_rows_per_expert", "nh_load_max_over_mean"]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert row["config"] == CATALOG and row["source_url"] == NANO["source"]
    differ = {k for k, v in CATALOG.items() if k not in NANO or NANO[k] != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(NANO["reduced"])
    assert NANO["reduced"] == {"num_hidden_layers": {"from": 52, "to": 9}, "n_routed_experts": {"from": 128, "to": 16},
                               "vocab_size": {"from": 131072, "to": 16384}}
    # the guide's floors: a whole period (the pattern's first nine blocks, 4 : 4 : 1), 8 experts, an eighth of the rows
    assert builder.pattern(NANO) == "MEMEM*EME" and NANO["n_routed_experts"] >= 8
    assert NANO["vocab_size"] * 8 >= CATALOG["vocab_size"]
    whole = CATALOG["hybrid_override_pattern"]
    assert len(whole) == 52 and (whole.count("M"), whole.count("E"), whole.count("*")) == (23, 23, 6)
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(NANO["reduced"]) and entry["source"] == NANO["source"]
    share = NANO["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (8, 128, 0)
    assert share["num_experts_total"] == CATALOG["n_routed_experts"] == share["chips_per_layer"] * NANO["n_routed_experts"]
    assert share["vocab_size_total"] == CATALOG["vocab_size"] and share["num_hidden_layers_total"] == 52
    assert NANO["train"]["chips"] == 1 and NANO["train"]["remat_policy"] in (None, "attn", "qkv_attn")
    assert {"blocks_that_run", "no_rotary_embedding", "mamba", "router", "experts", "bias", "dtypes", "initial_values",
            "optimizer_state_dtype", "optimizer_hyperparameters", "document_boundaries"} <= set(NANO["assumed"])
    assert NANO["deployment"]


def test_the_files_distortion_is_what_the_builder_computes():
    d = builder.distortion(NANO, 8192)
    assert (d["routed_rows_per_token"], d["routed_rows_per_token_model"]) == (0.75, 6.0)
    assert (d["rows_per_held_expert_uniform"], d["rows_per_held_expert_deployed"]) == (384.0, 3072.0)
    stated = NANO["distortion"]
    for text in ("6*16/128 = 0.75", "333.4M", f"{d['mamba_proj_pct_of_matmul']:.1f}%", f"{d['shared_expert_pct_of_matmul']:.1f}%",
                 f"{d['head_pct_of_matmul']:.1f}%", f"{d['routed_experts_pct_of_matmul']:.1f}%",
                 f"{d['attn_proj_pct_of_matmul']:.1f}%", f"{d['attention_pct_of_needed']:.1f}%", "384 rows", "3,072"):
        assert text in stated, text
    assert round(d["active_matmul_params"] / 1e6, 1) == 333.4


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_ten_readers():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "seq8k", NAME)
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (8192, 1) and config["kind"] == "nemotron_h_decoder"
    same_traffic = [w["name"] for w in bench["workloads"] if w["traffic"] == "seq8k"]
    assert same_traffic == ["granite-h-micro-1chip.seq8k", "phi4-mini-flash-1chip.seq8k", CELL]  # differ by the model alone
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN
    readers = harness.layer_metric_readers()
    assert all(readers[name].cells == [CELL] for name in own)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"] if "workloads" in m and m["name"] not in OWN)
    assert len(bench["workloads"]) == 9 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1  # 9 // 4 = 2 may
    assert len(cell["why"]) <= 200 and bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == NAME


def test_model_kwargs_describe_the_published_blocks_and_the_share():
    kw = builder.model_kwargs(NANO, 8192)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["vocab_size"]) == \
        (2688, 5, 32, 2, 128, 16384)
    assert list(zip(kw["layer_types"], kw["ffn_types"])) == PAIRS == builder.layer_pairs(NANO)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"], kw["ssm_conv"], kw["ssm_groups"]) == (64, 64, 128, 4, 8)
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"]) == (128, 16, 0, 6)
    assert (kw["moe_d_ff"], kw["shared_expert_d_ff"], kw["expert_kind"], kw["router_activation"],
            kw["routed_scaling_factor"]) == (1856, 3712, "relu2", "sigmoid", 2.5)
    assert kw["norm_topk_prob"] is True and kw["rope_theta"] is None and kw["tie_embeddings"] is False
    assert kw["routed_branch_init"] is True  # `assumed.initial_values`
    assert builder.mamba_sizes(NANO) == (4096, 6144)  # heads x head size, NOT expand x d = 5376
    for key, value in {"mlp_hidden_act": "silu", "n_group": 8, "norm_topk_prob": False, "use_conv_bias": False,
                       "tie_word_embeddings": True, "sliding_window": 4096}.items():
        with pytest.raises(ValueError):
            builder.model_kwargs(dict(NANO, **{key: value}), 8192)
    # the harness's rehearsal overrides six keys: two blocks, `ME` = ONE pair (mamba, experts); the mixer's, the
    # attention head's and the experts' own widths untouched
    toy = builder.model_kwargs(dict(NANO, **harness.REHEARSAL_CONFIG), 256)
    assert list(zip(toy["layer_types"], toy["ffn_types"])) == PAIRS[:1] and (toy["d_model"], toy["n_layers"]) == (256, 1)
    assert (toy["ssm_heads"], toy["ssm_groups"], toy["attn_head_dim"], toy["moe_d_ff"], toy["n_experts"],
            toy["n_experts_held"]) == (64, 8, 128, 1856, 128, 16)


def test_parameter_counts_by_hand():
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 4096 * d  # in_proj to z | xBC | dt = 10,304 columns; out_proj
    mamba_other = 6144 * 4 + 6144 + 3 * 64 + 4096  # the convolution and its bias; dt_bias, A_log, D; the gated norm
    attention = 2 * d * 32 * 128 + 2 * d * 2 * 128  # q, o; k, v
    router, shared, expert = d * 128, 2 * d * 3712, 2 * d * 1856
    assert (mamba + mamba_other + d, attention + d, router + 128 + shared + d, expert) == \
        (38_744_896, 23_399_040, 20_302_592, 9_977_856)  # 38.74M, 23.40M, 20.30M, 9.978M with each block's norm
    block = {"M": mamba + mamba_other + d, "*": attention + d, "E": router + 128 + shared + d}
    cut = 2 * 16384 * d + d + 4 * block["M"] + block["*"] + 4 * (block["E"] + 16 * expert)
    assert builder.total_params(NANO) == cut == 986_254_848  # 986.3M: 7.89 GB of state and gradients at 8 B
    uncut = 2 * 131072 * d + d + 23 * block["M"] + 6 * block["*"] + 23 * (block["E"] + 128 * expert)
    assert builder.total_params(NANO, uncut=True) == uncut and round(uncut / 1e9, 2) == 31.58  # the catalog's "31.6B"
    # the program counts the same, leaf for leaf
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    kw = builder.model_kwargs(NANO, 8192)
    kw.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params() == cut


def test_needed_flops_by_hand():
    assert builder.routed_rows_per_token(NANO) == 0.75  # 6 choices among 128, 16 of them held
    d = 2688
    mamba, attention = d * 10304 + 4096 * d, 2 * d * 4096 + 2 * d * 256
    expert_block = d * 128 + 2 * d * 3712 + 0.75 * 2 * d * 1856
    active = d * 16384 + 4 * mamba + attention + 4 * expert_block
    assert builder.active_matmul_params(NANO) == active == 333_398_016
    # causal attention in the ONE attention block: 6 x S x 32 heads x 128
    assert builder.attention_layers(NANO) == 1
    assert builder.attention_flops_per_token(NANO, 8192) == 6 * 8192 * 32 * 128 == 201_326_592
    # the scan at chunk 128 in FOUR blocks: C B^T once a GROUP (8), the rest a head (64)
    assert builder.ssd_flops_per_token(NANO) == 4 * 3 * (8 * 128 * 128 + 64 * (128 * 64 + 4 * 64 * 128)) == 33_030_144
    needed = builder.needed_flops_per_token(NANO, 8192)
    assert needed == 6 * 333_398_016 + 201_326_592 + 33_030_144 == 2_234_744_832  # 18.3 TFLOP a step of 8,192
    assert round(needed * 8192 / 1e12, 1) == 18.3
    # a toy by hand: 2 groups, 4 heads of 8, state 16, chunk 32, one block
    toy = dict(NANO, chunk_size=32, ssm_state_size=16, mamba_head_dim=8, n_groups=2, mamba_num_heads=4,
               num_hidden_layers=1)
    assert builder.ssd_flops_per_token(toy) == 3 * (2 * 32 * 16 + 4 * (32 * 8 + 4 * 8 * 16)) == 12_288
    # the grouped matmuls at given rows: two matrices, forward + backward
    assert builder.expert_matmul_flops(NANO, 8192) == 6 * 8192 * 2 * d * 1856 == 490_431_578_112
    assert builder.expert_matmul_flops(NANO, 0) == 0
    # the three flash readers divide by ALL nine blocks (PERF.md section 3): a ninth of this
    assert builder.attention_flops_per_token(NANO, 8192) / NANO["num_hidden_layers"] == 22_369_621 + 1 / 3


PATHS = {
    "ssm-proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/ssm/proj/"
                 "bse,ef->bsf/dot_general", "ssm/proj", ("layer/attn_proj", "fwd")),
    "grouped-scan-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/"
                              "attn_core/ssm/scan/bctgn,bcsgn->bcgts/dot_general", "ssm/scan", ("layer/attn_core", "bwd")),
    "shared-expert": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/shared/"
                      "bse,ef->bsf/dot_general", "moe/shared", ("layer/mlp", "fwd")),
    "routed-experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/experts/"
                       "moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "flash-kernel": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/flash_fwd/"
                     "pallas_call", None, ("flash_fwd", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_names_go_through_trace_moes_reduction_and_come_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe._COMPONENT
    with trace_kimi._names_of_trace_moe(trace_nemotron_h.NAMES):
        assert trace_moe.classify(path) == name
    assert (trace_moe.NAMES, trace_moe._COMPONENT) == before  # the accepted reader reads what it read
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def run_with_series(series, steps=(10, 15), newest=None):
    return {"config": NANO, "traffic": {"warmup_steps": 2}, "trace": {"steps": list(steps)},
            "run_record": {"step_counters": newest or {"moe_held_rows_mean": 128.0, "moe_load_max_over_mean": 21.3},
                           "step_counter_series": series}}


def test_the_traced_steps_rows_come_from_the_records_series():
    """Loop step i of the window is `train_step` call 3 + i (compile step, two warm-up steps): steps 10..14 of the
    window are calls 13..17.  A step's mean is over 16 held experts x 4 blocks."""
    series = [[s, {"moe_held_rows_mean": 128.0 if s < 15 else 256.0}] for s in range(40)]
    assert trace_nemotron_h.traced_held_rows(run_with_series(series)) == (2 * 128.0 + 3 * 256.0) * 64
    assert trace_nemotron_h.traced_held_rows(run_with_series([[s, {"moe_held_rows_mean": 0.0}] for s in range(40)])) == 0.0
    assert trace_nemotron_h.traced_held_rows(run_with_series(series[:16])) is None  # a traced step is missing
    assert trace_nemotron_h.traced_held_rows(run_with_series([])) is None
    assert trace_nemotron_h.traced_held_rows({"config": NANO, "trace": None, "run_record": None}) is None


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them: nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[name] for name in OWN]
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": NANO, "traffic": {"warmup_steps": 2, "seq_len": 8192}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    # a record from before the counters reads as nothing; one with them gives the newest value
    assert readers["nh_load_max_over_mean"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = run_with_series([])
    assert readers["nh_load_max_over_mean"].read(got) == 21.3 and readers["nh_held_rows_per_expert"].read(got) == 128.0
    # a recorded trace of a program without the names (a dense step): nothing
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")
    with trace_kimi._names_of_trace_moe(trace_nemotron_h.NAMES):
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got is None or not any(got["seconds"].values())


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed",
         "2147483900", "--seconds", "6", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    # the counters reach the readers through the run's record
    assert {"nh_held_rows_per_expert", "nh_load_max_over_mean"} <= set(line["metric_names"])
