"""kind "cca_moe_decoder": the configuration file against the catalog's row key
for key, the two cuts the issue names and nothing else, the builder's parameter
and operation counts against counts worked by hand (8.84B from the uncut keys,
1,105.1M as it runs, 490.2 MFLOP a token forward), its refusals, the
rehearsal's derived head counts, `trace_zaya`'s names on path strings, the
readers on runs with nothing to read, and the cell's rehearsal on the CPU (the
tier-1 copy of the comparison with the reference is tests/test_zaya_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import cca_moe_decoder as builder  # noqa: E402
from benchmarks.lib import trace_kind, trace_moe, trace_scopes, trace_zaya  # noqa: E402

CELL = "zaya1-vp8-1chip.seq16k"
NAME = "zaya1-8b-vp8-1chip"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    ZAYA = json.load(f)
CUTS = {"num_hidden_layers": (40, 5), "vocab_size": (262272, 32784)}
OWN = ["cca_proj_time_pct", "cca_mix_time_pct", "cca_mix_roofline", "moe_experts_in_use"]
SHARED = ["moe_router_time_pct", "moe_dispatch_time_pct", "moe_experts_time_pct", "moe_combine_time_pct", "moe_experts_roofline",
          "moe_routed_time_pct", "moe_load_max_over_mean"]
NOT_ITS = ["moe_held_rows_per_expert", "moe_rows_moved_share", "moe_shared_time_pct"]  # no held share, no shared expert


def test_every_catalog_key_is_copied_and_the_cuts_are_the_issues():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog_file):
        pytest.skip("the catalog is not on this machine")
    with open(catalog_file) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert row["source_url"] == ZAYA["source"] and set(row["config"]) <= set(ZAYA)
    differ = {k for k, v in row["config"].items() if ZAYA[k] != v}
    assert differ == set(CUTS) == set(ZAYA["reduced"])  # every width, head count, tap count, the router's width and the ropes as published
    assert ZAYA["reduced"] == {k: {"from": a, "to": b} for k, (a, b) in CUTS.items()}
    assert (ZAYA["num_experts"], ZAYA["num_experts_per_tok"], ZAYA["router_hidden_size"], ZAYA["cca_time0"], ZAYA["cca_time1"],
            ZAYA["head_dim"], ZAYA["num_attention_heads"], ZAYA["num_key_value_heads"]) == (16, 1, 256, 2, 2, 128, 8, 2)
    assert ZAYA["layer_types"] == row["config"]["layer_types"] and len(ZAYA["layer_types"]) == 40  # copied whole
    entry = next(c for c in harness.load_benchmark()["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(ZAYA["reduced"]) and entry["source"] == ZAYA["source"] and len(entry["why"]) <= 200
    share = ZAYA["share"]
    assert (share["chips_per_layer"], share["pipeline_stages"], share["vocab_parallel"], share["stage_index"]) == (1, 8, 8, 0)
    assert share["pipeline_stages"] * ZAYA["num_hidden_layers"] == share["num_hidden_layers_total"] == 40
    assert share["vocab_parallel"] * ZAYA["vocab_size"] == share["vocab_size_total"] == 262272
    assert "num_experts_total" not in share  # every expert is here: no held share (and the pin of the six share cells does not look here)
    assert {"join", "value_shift", "convolutions", "unit_norm", "rope", "router", "router_bias_update", "gate", "not_run", "dtypes", "initial_values",
            "optimizer_state_dtype", "optimizer_hyperparameters", "document_boundaries"} <= set(ZAYA["assumed"])
    assert all(isinstance(ZAYA[k], str) and ZAYA[k] for k in ("deployment", "distortion")) and "GiB" in share["why"]


def test_the_totals_read_back_from_the_file():
    assert builder.total_params(ZAYA) == 1_105_061_210
    assert abs(builder.total_params(ZAYA, uncut=True) / (40 * 207.6e6 + 537.1e6) - 1) < 0.01
    assert round(builder.total_params(ZAYA, uncut=True) / 1e9, 2) == 8.84 and round(builder.total_params(ZAYA, uncut=True, active=True) / 1e9, 2) == 1.29
    sizes = builder._sizes(ZAYA)
    assert [round(sizes[k] / 1e6, 2) for k in ("cca_proj", "cca_conv2", "router", "expert")] == [5.24, 0.33, 0.66, 12.58]
    d = builder.distortion(ZAYA, 16384)
    assert round(d["forward_mflop_per_token"], 1) == 490.2 and round(d["forward_mflop_per_token_uncut"]) == 3921
    for part, pct in (("causal_core", 34.2), ("cca_proj", 10.7), ("cca_conv2", 0.7), ("router", 1.3), ("experts", 25.7), ("head", 27.4)):
        assert round(d[part + "_pct"], 1) == pct == round(d[part + "_pct_uncut"], 1), part  # 5 of 40 layers beside 1/8 of the table
    assert sum(v for k, v in d.items() if k.endswith("_pct")) == pytest.approx(100.0)
    assert (builder.routed_rows_per_token(ZAYA), d["rows_per_expert_uniform"]) == (1.0, 1024.0)
    assert builder.expert_flops_per_token(ZAYA) == 6 * 5 * 3 * 2048 * 2048
    assert builder.attention_flops_per_token(ZAYA, 16384) == 6 * 5 * 16384 * 8 * 128
    assert builder.mix_bytes_per_layer(ZAYA) == 4 * 1280 * 2
    assert not hasattr(builder, "expert_matmul_flops")  # `trace_kind` takes its presence to mean a held share with its counters


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "seq16k") and config["kind"] == "cca_moe_decoder"
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (16384, 1)
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and [c["name"] for c in bench["configs"]][-1] == NAME
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN == [m["name"] for m in bench["per_layer"]][-len(OWN):]  # appended, nothing before them moved
    shared = [m for m in bench["per_layer"] if CELL in m.get("workloads", ()) and m["name"] not in OWN]
    assert [m["name"] for m in shared] == SHARED and all(m["workloads"][-1] == CELL for m in shared)  # the cell's name appended
    assert not any(CELL in m.get("workloads", ()) for m in bench["per_layer"] if m["name"] in NOT_ITS)
    assert len(bench["per_layer"]) <= 128 and len(bench["workloads"]) <= 24 and len(cell["why"]) <= 200
    readers = harness.layer_metric_readers()
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            reader = readers[m["name"]]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == (reader.unit, reader.source, reader.layer, reader.moves)


def test_model_kwargs_describe_the_latent_heads_the_router_and_the_joins():
    kw = builder.model_kwargs(ZAYA, 16384)
    assert kw["layer_types"] == ("cca",) * 5 and (kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["d_model"]) == (8, 2, 128, 2048)
    assert (kw["rope_theta"], kw["rotary_dim"], kw["cca_taps"]) == (5e6, 64, (2, 2))
    assert (kw["n_experts"], kw["experts_per_token"], kw["moe_d_ff"], kw["router_kind"], kw["router_hidden"]) == (16, 1, 2048, "mlp", 256)
    assert kw["norm_topk_prob"] is False and kw["residual_scaling"] is True and kw["tie_embeddings"] is True
    assert "n_experts_held" not in kw and "layer_windows" not in kw and "router_aux_loss_coef" not in kw
    # the issue's job: the rate warms up over 2,000 steps, and the stored bias follows the load so that all 16 experts stay in use
    assert (ZAYA["train"]["lr_warmup_steps"], ZAYA["train"]["router_bias_update_rate"], kw["router_bias_update_rate"]) == (2000, 0.003, 0.003)
    cfg = builder._transformer_config(ZAYA, 16384)
    assert cfg.num_params() == builder.total_params(ZAYA) and cfg.carries_router_state
    # the harness's rehearsal overrides the head counts to 2 / 1: the builder keeps the two key heads the value shift needs
    toy = dict(ZAYA, **harness.REHEARSAL_CONFIG)
    kw = builder.model_kwargs(toy, 256)
    assert (kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["d_model"], kw["router_hidden"], kw["moe_d_ff"]) == (2, 2, 128, 256, 256, 256)  # an expert as wide as the stream, as published
    assert builder._transformer_config(toy, 256).num_params() == builder.total_params(toy)


@pytest.mark.parametrize("change", [{"tie_word_embeddings": False}, {"sliding_window": 4096}, {"num_experts_per_tok": 2}, {"attention_bias": True},
                                    {"layer_types": ["hybrid", "hybrid_sliding"]}])
def test_the_builder_refuses_what_the_programs_layers_do_not_express(change):
    with pytest.raises(ValueError, match="cca_moe_decoder expresses"):
        builder.model_kwargs(dict(ZAYA, **change), 16384)


PATHS = {
    "proj": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_proj/cca/proj/bse,ehd->bshd/dot_general", "cca/proj",
             ("layer/attn_proj", "fwd")),
    "mix-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_proj/cca/mix/bsgd,gde->bsge/dot_general",
                     "cca/mix", ("layer/attn_proj", "bwd")),
    "mix-recompute": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/rematted_computation/layer/attn_proj/cca/mix/mul", "cca/mix",
                      ("layer/attn_proj", "recompute")),
    "core": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_core/flash_fwd/pallas_call", None, ("flash_fwd", "fwd")),
    "join": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/attn_proj/cca/proj/add", "cca/proj", ("layer/attn_proj", "fwd")),
    "router": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/mlp/moe/router/dot_general", "moe/router", ("layer/mlp", "fwd")),
    "experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/mlp/moe/experts/moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_kinds_names_and_classifier_are_arguments_of_trace_moes_reduction(path, name, scope):
    assert trace_zaya.classify(path) == name and (name is None or name in trace_zaya.NAMES)
    assert trace_moe.classify(path) == (name if name in trace_moe.NAMES else None)
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 68): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": ZAYA, "traffic": {"warmup_steps": 2, "seq_len": 16384}}
    for name in OWN + SHARED:
        assert readers[name].read({"trace": None, "run_record": None}) is None and readers[name].read(dict(run)) is None
    assert readers["moe_experts_in_use"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = {"run_record": {"step_counters": {"moe_experts_in_use": 15.8, "moe_gate_mean": 0.0725, "moe_load_max_over_mean": 2.5}}}
    assert readers["moe_experts_in_use"].read(got) == 15.8 and trace_kind.counter(got, "moe_load_max_over_mean") == 2.5
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")  # a dense causal step: none of the names
    got = trace_moe.reduce_moe(recorded, window_span="bench_step", names=trace_zaya.NAMES, classify=trace_zaya.classify)
    assert got is None or not any(got["seconds"].values())


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths (two layers, 2 + 2 heads of the published 128 under d 256, the published router width, an
    expert as wide as the stream): the step runs, the comparison with the reference holds the HARNESS'S tolerance, the counters
    reach the readers.  The one reason for `correct: false` that is admitted is the loss guard's, as in `test_run.py` for every
    file that states `train.lr_warmup_steps`: under the warm-up six seconds of toy steps do not bring the loss down by the margin."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", "3", "--seconds", "6",
         "--trace", "1"], capture_output=True, text=True, timeout=600, cwd=ROOT)
    lines = out.stdout.splitlines()
    reference = json.loads(next(l for l in lines if l.startswith("[bench] reference "))[len("[bench] reference "):])
    assert reference["seqs"] == 2 and reference["ok"] is True and max(reference["rel_rms_error"]) <= reference["tolerance"], reference
    reasons = [l for l in lines if l.startswith("[bench] NOT CORRECT:") and not l.startswith("[bench] NOT CORRECT: loss fell from ")]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["failed"] == 0 and not reasons, reasons
    assert {"moe_experts_in_use", "moe_load_max_over_mean"} <= set(last["metric_names"])
    counters = json.loads(next(l for l in lines if l.startswith("[bench] step counters "))[len("[bench] step counters "):])
    assert 1 / 16 < counters["moe_gate_mean"] < 0.5 and 1 <= counters["moe_experts_in_use"] <= 16 and "moe_held_rows_mean" not in counters
