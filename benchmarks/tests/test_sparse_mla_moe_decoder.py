"""kind "sparse_mla_moe_decoder": the configuration file against the catalog's
row key for key, the cuts the issue names and nothing else, the builder's
parameter and operation counts against counts worked by hand (279.55B /
16.25B from the uncut keys, 1,452.4M at ISSUE 66's 4-way heads, 1,390.8M at
the 8-way that runs), its refusals, `trace_dots3`'s names on path strings, the
readers on runs with nothing to read, and the cell's rehearsal on the CPU
(the tier-1 copy of the comparison with the reference is
tests/test_dots3_note_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import sparse_mla_moe_decoder as builder  # noqa: E402
from benchmarks.lib import trace_dots3, trace_kind, trace_moe, trace_scopes  # noqa: E402

CELL = "dots3-note-ep32-1chip.seq8k"
NAME = "dots3-note-prev-ep32-1chip"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    DOTS = json.load(f)
CUTS = {"n_routed_experts": (256, 8), "num_attention_heads": (128, 16), "num_key_value_heads": (128, 16),
        "swa_num_attention_heads": (64, 8), "swa_num_key_value_heads": (64, 8), "num_hidden_layers": (46, 5), "vocab_size": (152064, 19008)}
OWN = ["dsa_index_time_pct", "dsa_topk_time_pct", "dsa_attn_time_pct", "dsa_kl_time_pct", "dsa_attn_roofline",
       "dsa_selected_pairs_pct", "dsa_index_kl_nats"]
SHARED = ["moe_router_time_pct", "moe_dispatch_time_pct", "moe_experts_time_pct", "moe_combine_time_pct", "moe_experts_roofline",
          "mla_proj_time_pct", "moe_shared_time_pct", "moe_routed_time_pct", "moe_held_rows_per_expert",
          "attn_window_tiles_visited_pct", "moe_load_max_over_mean", "moe_rows_moved_share"]


def test_every_catalog_key_is_copied_and_the_cuts_are_the_issues():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog_file):
        pytest.skip("the catalog is not on this machine")
    with open(catalog_file) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert row["source_url"] == DOTS["source"] and set(row["config"]) <= set(DOTS)
    differ = {k for k, v in row["config"].items() if DOTS[k] != v}
    assert differ == set(CUTS) == set(DOTS["reduced"])  # every width, rank, index_* key, the window and both thetas as published
    assert DOTS["reduced"] == {k: {"from": a, "to": b} for k, (a, b) in CUTS.items()}
    assert DOTS["layer_types"] == row["config"]["layer_types"] and len(DOTS["layer_types"]) == 46  # copied whole
    entry = next(c for c in harness.load_benchmark()["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(DOTS["reduced"]) and entry["source"] == DOTS["source"]
    share = DOTS["share"]
    assert (share["chips_per_layer"], share["expert_parallel"], share["head_parallel"], share["vocab_parallel"]) == (32, 32, 8, 8)
    assert share["expert_parallel"] * DOTS["n_routed_experts"] == share["num_experts_total"] == 256
    assert share["head_parallel"] * DOTS["num_attention_heads"] == share["num_attention_heads_total"] == 128
    assert share["head_parallel"] * DOTS["swa_num_attention_heads"] == share["swa_num_attention_heads_total"] == 64
    assert share["vocab_parallel"] * DOTS["vocab_size"] == share["vocab_size_total"] == 152064
    assert {"layers_that_run", "apply_mla_qkv_lora_rescale", "mla", "rope", "sliding_window", "indexer", "sparse_core", "gate", "objective",
            "no_mtp_head", "router", "dtypes", "initial_values", "optimizer_state_dtype", "optimizer_hyperparameters"} <= set(DOTS["assumed"])
    assert "8-WAY" in share["why"] and "15.79 GiB" in share["why"]  # which head share ran and why


def test_the_totals_read_back_from_the_file():
    assert round(builder.total_params(DOTS, uncut=True) / 1e9, 2) == 279.55
    assert round(builder.total_params(DOTS, uncut=True, active=True) / 1e9, 2) == 16.25
    four_way = dict(DOTS, num_attention_heads=32, swa_num_attention_heads=16)
    assert round(builder.total_params(four_way) / 1e6, 1) == 1452.5 and round(builder.total_params(DOTS) / 1e6, 1) == 1390.8
    sizes = builder._sizes(builder.published(DOTS))
    assert round((sizes[builder.FULL] + sizes["indexer"]) / 1e6, 2) == 144.05 and round(sizes[builder.SLIDING] / 1e6, 2) == 90.83
    d = builder.distortion(four_way, 8192)  # ISSUE 66's arithmetic, at its 4-way heads
    assert round(d["forward_mflop_per_token"]) == 1473
    for part, pct in (("index_scores", 9.1), ("indexer_proj", 2.5), ("selected_core", 5.0), ("full_proj", 10.8), ("sliding_proj", 12.6),
                      ("window_core", 1.2), ("dense", 28.8), ("head", 13.2), ("routed_experts", 3.2)):
        assert round(d[part + "_pct"], 1) == pct, part
    assert round(d["dense_causal_core_pct_of_this_needed"], 1) == 11.4 and round(d["selected_pairs_pct_of_causal"], 1) == 43.7
    assert sum(v for k, v in d.items() if k.endswith("_pct") and "_of_" not in k) == pytest.approx(100.0)
    assert builder.mean_pairs(8192, 2048) * 8192 == sum(min(t + 1, 2048) for t in range(8192))
    assert builder.mean_pairs(8192, 513) * 8192 == sum(min(t + 1, 513) for t in range(8192))
    assert (builder.attention_layers(DOTS), builder.held_expert_slots(DOTS), builder.routed_rows_per_token(DOTS)) == (3, 32, 0.25)
    assert builder.needed_flops_per_token(DOTS, 8192) == pytest.approx(3 * 1304.22e6, rel=1e-5)


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "seq8k") and config["kind"] == "sparse_mla_moe_decoder"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and [c["name"] for c in bench["configs"]][-1] == NAME
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN == [m["name"] for m in bench["per_layer"]][-len(OWN):]  # appended, nothing before them moved
    shared = [m for m in bench["per_layer"] if CELL in m.get("workloads", ()) and m["name"] not in OWN]
    assert [m["name"] for m in shared] == SHARED and all(m["workloads"][-1] == CELL for m in shared)  # the cell's name appended
    assert len(bench["per_layer"]) <= 128 and len(cell["why"]) <= 200
    readers = harness.layer_metric_readers()
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            reader = readers[m["name"]]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == (reader.unit, reader.source, reader.layer, reader.moves)


def test_model_kwargs_describe_both_kinds_the_shares_and_the_indexer():
    kw = builder.model_kwargs(DOTS, 8192)
    assert kw["layer_types"] == ("mla_sparse", "mla_sparse", "mla_window", "mla_window", "mla_window")
    assert kw["ffn_types"] == ("dense", "experts", "experts", "experts", "experts")
    assert kw["layer_windows"] == (None, None, 513, 513, 513)
    assert [r["theta"] for r in kw["layer_ropes"]] == [8e7, 8e7, 5e4, 5e4, 5e4]
    assert (kw["n_heads"], kw["head_share"], kw["window_latent"]["heads"]) == (128, (0, 8), 64)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"]) == (1024, 512, 128, 64, 128)
    assert kw["window_latent"] == dict(heads=64, q_rank=1024, kv_rank=1024, nope=192, rope=64, v=128)
    assert (kw["index_heads"], kw["index_head_dim"], kw["index_topk"]) == (64, 128, 2048)
    assert (kw["n_experts"], kw["n_experts_held"], kw["experts_per_token"], kw["router_activation"]) == (256, 8, 8, "sigmoid")
    assert "router_share_init" not in kw  # K * held / E = 0.25: no whole choice a share
    cfg = builder._transformer_config(DOTS, 8192)
    assert cfg.num_params() == builder.total_params(DOTS)


@pytest.mark.parametrize("change", [{"apply_mla_qkv_lora_rescale": False}, {"attention_gate_type": "elementwise"}, {"scoring_func": "softmax"},
                                    {"rope_scaling": {"type": "yarn"}}, {"layer_types": ["full_attention", "linear_attention"]}])
def test_the_builder_refuses_what_the_programs_layers_do_not_express(change):
    with pytest.raises(ValueError, match="sparse_mla_moe_decoder expresses"):
        builder.model_kwargs(dict(DOTS, **change), 8192)


PATHS = {
    "index": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_core/dsa/index/while/body/checkpoint/bqjd,bsd->bqjs/dot_general",
              "dsa/index", ("layer/attn_core", "fwd")),
    "topk": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_core/dsa/topk/while/body/reduce_sum", "dsa/topk",
             ("layer/attn_core", "fwd")),
    "core-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_core/dsa/attn/cond/branch_0_fun/"
                      "dsa_attn_bwd_dkv/pallas_call", "dsa/attn", ("layer/attn_core", "bwd")),
    "target": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_core/dsa/kl/cond/branch_0_fun/dsa_target/pallas_call", "dsa/kl",
               ("layer/attn_core", "fwd")),
    "window": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_core/mla/window/flash_fwd/pallas_call", "mla/window",
               ("flash_fwd", "fwd")),
    "gate": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_proj/attn/gate/bshd,hde->bse/dot_general", "attn/gate",
             ("layer/attn_proj", "fwd")),
    "proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_proj/mla/proj/bsr,rhd->bshd/dot_general", "mla/proj",
             ("layer/attn_proj", "fwd")),
    "experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/mlp/moe/experts/moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_kinds_names_and_classifier_are_arguments_of_trace_moes_reduction(path, name, scope):
    assert trace_dots3.classify(path) == name and (name is None or name in trace_dots3.NAMES)
    assert trace_moe.classify(path) == (name if name in trace_moe.NAMES else None)
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 66): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": DOTS, "traffic": {"warmup_steps": 2, "seq_len": 8192}}
    for name in OWN + SHARED:
        assert readers[name].read({"trace": None, "run_record": None}) is None and readers[name].read(dict(run)) is None
    assert readers["dsa_selected_pairs_pct"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = {"run_record": {"step_counters": {"dsa_index_kl": 1.25, "dsa_selected_pairs": 14681088.0, "dsa_causal_pairs": 33558528.0}}}
    assert readers["dsa_index_kl_nats"].read(got) == 1.25
    assert readers["dsa_selected_pairs_pct"].read(got) == pytest.approx(43.7477, abs=1e-4) == pytest.approx(
        builder.distortion(DOTS, 8192)["selected_pairs_pct_of_causal"], abs=1e-4)
    assert trace_kind.counter(got, "dsa_causal_pairs") == 8192 * 8193 / 2
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")  # a dense causal step: none of the names
    got = trace_moe.reduce_moe(recorded, window_span="bench_step", names=trace_dots3.NAMES, classify=trace_dots3.classify)
    assert got is None or not any(got["seconds"].values())


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths (two full layers, every causal key selected at 256 positions): the comparison with the
    reference holds (`[bench] reference`) and the step counters reach the readers; the loss guard is NOT asked of a short
    rehearsal (a warm-up of 2,000 steps, as `mellum2`'s and `glm47`'s)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", "3", "--seconds", "6",
         "--trace", "1"], capture_output=True, text=True, timeout=600, cwd=ROOT)
    lines = out.stdout.splitlines()
    reference = json.loads(next(l for l in lines if l.startswith("[bench] reference "))[len("[bench] reference "):])
    assert reference["ok"] and max(reference["rel_rms_error"]) < reference["tolerance"], reference
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["failed"] == 0
    assert {"dsa_index_kl_nats", "dsa_selected_pairs_pct", "moe_held_rows_per_expert", "moe_rows_moved_share"} <= set(last["metric_names"])
    counters = json.loads(next(l for l in lines if l.startswith("[bench] step counters "))[len("[bench] step counters "):])
    assert counters["dsa_selected_pairs"] == counters["dsa_causal_pairs"] == 256 * 257 / 2 and counters["dsa_index_kl"] > 0
