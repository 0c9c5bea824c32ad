"""kind "swa_moe_decoder": the configuration file against the catalog's row key
for key, the three cuts the guide names and nothing else, the builder's
parameter and operation counts against counts worked by hand, the job's rate
warm-up as the builder passes it, `trace_mellum`'s names on path strings, its
rows from a record's series, the readers on runs with nothing to read, and the
cell's rehearsal on the CPU (the tier-1 copy of the comparison with the
reference is tests/test_mellum_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import swa_moe_decoder as builder  # noqa: E402
from benchmarks.lib import trace_mellum, trace_moe, trace_scopes  # noqa: E402

CELL = "mellum2-ep4-1chip.seq16k"
NAME = "mellum2-12b-a2.5b-ep4-1chip"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    MELLUM = json.load(f)

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# The `config` of the catalog row Mellum2-12B-A2.5B-Instruct (model-configs guide), every key.
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
}
OWN = ["m2_window_attn_time_pct", "m2_full_attn_time_pct", "m2_window_attn_roofline", "m2_full_attn_roofline",
       "m2_window_tiles_visited_pct", "m2_moe_routed_time_pct", "m2_experts_roofline", "m2_held_rows_per_expert",
       "m2_load_max_over_mean", "m2_rows_moved_share", "m2_moe_dispatch_time_pct", "m2_moe_combine_time_pct",
       "m2_moe_experts_time_pct"]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == CATALOG and row["source_url"] == MELLUM["source"]
    differ = {k for k, v in CATALOG.items() if k not in MELLUM or MELLUM[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(MELLUM["reduced"])
    assert MELLUM["reduced"] == {"num_hidden_layers": {"from": 28, "to": 8}, "num_experts": {"from": 64, "to": 16},
                                 "vocab_size": {"from": 98304, "to": 24576}}
    # the guide's floors: whole periods (two of them, 6 : 2 as the model is 21 : 7), 8 experts, an eighth of the rows
    assert builder.layer_kinds(MELLUM) == PERIOD * 2 and MELLUM["num_experts"] >= 8
    assert MELLUM["vocab_size"] * 8 >= CATALOG["vocab_size"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(MELLUM["reduced"]) and entry["source"] == MELLUM["source"]
    share = MELLUM["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (4, 64, 0)
    assert share["num_experts_total"] == CATALOG["num_experts"] == share["chips_per_layer"] * MELLUM["num_experts"]
    assert share["vocab_size_total"] == CATALOG["vocab_size"] == share["chips_per_layer"] * MELLUM["vocab_size"]
    assert share["num_hidden_layers_total"] == 28
    assert MELLUM["train"]["chips"] == 1 and MELLUM["train"]["remat_policy"] in (None, "attn", "qkv_attn")
    # every inference is listed with its reason
    assert {"layers_that_run", "qk_norm", "router_aux_loss_coef", "no_mtp_head", "rope", "attention", "router", "dtypes",
            "initial_values", "optimizer_state_dtype", "optimizer_hyperparameters", "document_boundaries"} <= set(MELLUM["assumed"])
    assert (MELLUM["qk_norm"], MELLUM["router_aux_loss_coef"]) == ("per_head", 0.001)
    assert set(MELLUM["train"]) == {"chips", "mesh", "strategy", "param_dtype", "compute_dtype", "optimizer", "lr_warmup_steps",
                                    "remat_policy"}
    assert MELLUM["deployment"]


def test_the_files_distortion_is_what_the_builder_computes():
    d = builder.distortion(MELLUM, 16384)
    assert (d["routed_rows_per_token"], d["routed_rows_per_token_model"]) == (2.0, 8.0)
    assert (d["rows_per_held_expert_uniform"], d["rows_per_held_expert_deployed"]) == (2048.0, 8192.0)
    whole = builder.distortion(dict(MELLUM, num_hidden_layers=28, num_experts=64, vocab_size=98304), 16384)
    stated = MELLUM["distortion"]
    attention = d["window_attention_pct_of_needed"] + d["full_attention_pct_of_needed"]
    for text in ("8*16/64 = 2", "3,058 MFLOP", f"{attention:.1f}%", f"{d['full_attention_pct_of_needed']:.1f}%",
                 f"{d['window_attention_pct_of_needed']:.1f}%", f"{d['attn_proj_pct_of_needed']:.1f}%",
                 f"{d['routed_experts_pct_of_needed']:.1f}%", f"{d['head_pct_of_needed']:.1f}%",
                 f"{d['router_pct_of_needed']:.1f}%", "17,117 MFLOP", f"{whole['routed_experts_pct_of_needed']:.1f}%",
                 f"{whole['window_attention_pct_of_needed'] + whole['full_attention_pct_of_needed']:.1f}%",
                 f"{whole['attn_proj_pct_of_needed']:.1f}%", f"{whole['head_pct_of_needed']:.1f}%", "5,182 MFLOP", "41%",
                 "2,048 rows", "8,192"):
        assert text in stated, text
    assert round(100 * (1 - d["needed_mflop_per_token"] / d["needed_mflop_per_token_were_every_layer_full"])) == 41


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"]) == (1, NAME) and config["kind"] == "swa_moe_decoder"
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (16384, 1)
    if cell["traffic"] == "seq16k":  # the accepted file untouched: the three cells differ by the model alone
        same_traffic = [w["name"] for w in bench["workloads"] if w["traffic"] == "seq16k"]
        assert same_traffic == ["mistral7b-1chip.seq16k", "kimi-linear-ep16-1chip.seq16k", CELL]
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN
    readers = harness.layer_metric_readers()
    assert all(readers[name].cells == [CELL] for name in own)
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            reader = readers[m["name"]]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == (reader.unit, reader.source, reader.layer, reader.moves)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"] if "workloads" in m and m["name"] not in OWN)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_model_kwargs_describe_the_published_layers_and_the_share():
    kw = builder.model_kwargs(MELLUM, 16384)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["vocab_size"]) == \
        (2304, 8, 32, 4, 128, 24576)
    assert kw["layer_windows"] == (1024, 1024, 1024, None) * 2
    default, yarn = kw["layer_ropes"][0], kw["layer_ropes"][3]
    assert kw["layer_ropes"] == (default, default, default, yarn) * 2 and kw["rope_theta"] is None
    assert default == {"theta": 500000.0}
    assert yarn == {"theta": 500000.0, "factor": 16.0, "original_max_position": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
                    "attention_factor": 1.2772588722239782}
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"], kw["moe_d_ff"]) == \
        (64, 16, 0, 8, 896)
    assert (kw["router_activation"], kw["norm_topk_prob"], kw["router_aux_loss_coef"]) == ("softmax", True, 0.001)
    assert kw["qk_norm"] == "per_head" and kw["tie_embeddings"] is False
    assert kw["routed_branch_init"] is True and kw["router_share_init"] is True  # `assumed.initial_values`
    assert builder.model_kwargs(dict(MELLUM, qk_norm=None), 16384)["qk_norm"] is False  # one line to turn off
    for key, value in {"hidden_act": "gelu", "norm_topk_prob": False, "attention_bias": True, "tie_word_embeddings": True,
                       "use_sliding_window": False, "mlp_layer_types": ["dense"] * 28, "qk_norm": "whole",
                       "layer_types": ["chunked_attention"] * 28}.items():
        with pytest.raises(ValueError):
            builder.model_kwargs(dict(MELLUM, **{key: value}), 16384)
    with pytest.raises(ValueError, match="default rope and YaRN"):
        builder.rope_kwargs({"rope_type": "llama3", "rope_theta": 1.0})
    # the harness's rehearsal overrides six keys: two layers, both window layers; the head's and the experts' own
    # widths, the router's 64 outputs and the 16 held untouched
    toy = builder.model_kwargs(dict(MELLUM, **harness.REHEARSAL_CONFIG), 256)
    assert (toy["d_model"], toy["n_layers"], toy["layer_windows"]) == (256, 2, (1024, 1024))
    assert (toy["attn_head_dim"], toy["moe_d_ff"], toy["n_experts"], toy["n_experts_held"]) == (128, 896, 64, 16)


def test_the_jobs_rate_warms_up_to_the_default_optimizers_own():
    """`train.lr_warmup_steps` (`assumed.optimizer_hyperparameters`): linear from rate / steps at the first
    step to `default_optimizer`'s 3e-4 at step 2,000 and constant from there; a file without the key is no job
    of this kind."""
    schedule = builder.learning_rate(MELLUM["train"])
    assert MELLUM["train"]["lr_warmup_steps"] == 2000
    assert [float(schedule(step)) for step in (0, 1000, 2000, 10**6)] == pytest.approx([1.5e-7, 1.500750e-4, 3e-4, 3e-4], rel=1e-4)
    assert sum(float(schedule(step)) for step in range(45)) < 5e-4 / 3  # a run's sum of rates, 1.55e-4: under a third of PR 44's rule
    with pytest.raises(KeyError):
        builder.learning_rate({k: v for k, v in MELLUM["train"].items() if k != "lr_warmup_steps"})


def test_parameter_counts_by_hand():
    d = 2304
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128  # q, o; k, v
    router, expert = d * 64, 3 * d * 896
    norms = 2 * d + 2 * 128  # ln1, ln2; q_norm, k_norm
    assert (attention, router, expert) == (21_233_664, 147_456, 6_193_152)  # 21.23M, 0.147M, 6.193M
    held_layer = attention + router + 16 * expert + norms
    cut = 2 * 24576 * d + d + 8 * held_layer
    assert builder.total_params(MELLUM) == cut == 1_077_059_840  # 1,077.1M: 8.62 GB of state and gradients at 8 B
    assert round(held_layer / 1e6, 2) == 120.48 and round(2 * 24576 * d / 1e6, 1) == 113.2
    uncut = 2 * 98304 * d + d + 28 * (attention + router + 64 * expert + norms)
    assert builder.total_params(MELLUM, uncut=True) == uncut and round(uncut / 1e9, 2) == 12.15  # the card's "12B"
    active = 28 * (attention + router + 8 * expert) + d * 98304 + d * 98304
    assert round(active / 1e9, 2) == 2.44  # its "A2.5B" (the issue's 2.43B: both tables, no norms)
    assert round((attention + router + 64 * expert + norms) / 1e6, 1) == 417.7  # a whole layer: 3.34 GB, three fit a chip
    # the program counts the same, leaf for leaf
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.ops.rotary import Rope

    kw = builder.model_kwargs(MELLUM, 16384)
    kw.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, layer_ropes=tuple(Rope(**r) for r in kw["layer_ropes"]))
    assert TransformerConfig(**kw).num_params() == cut


def test_needed_flops_by_hand():
    assert builder.routed_rows_per_token(MELLUM) == 2.0  # 8 choices among 64, 16 of them held
    d, seq = 2304, 16384
    parts = builder.matmul_params_by_part(MELLUM)
    assert parts == {"attn_proj": 8 * 21_233_664.0, "router": 8 * 147_456.0, "routed_experts": 8 * 2.0 * 6_193_152,
                     "head": float(d * 24576)}
    assert [round(6 * parts[k] / 1e6, 1) for k in ("attn_proj", "routed_experts", "head", "router")] == [1019.2, 594.5, 339.7, 7.1]
    # a window layer's query sees min(i + 1, 1024) keys: 992.03 on average over 16,384 positions; a full layer's S / 2
    keys = (1024 * 1025 / 2 + (seq - 1024) * 1024) / seq
    assert builder.mean_keys_seen(seq, 1024) == keys == 992.03125
    assert builder.window_attention_flops_per_token(MELLUM, seq) == 6 * 12 * keys * 32 * 128 == 292_561_920
    assert builder.full_attention_flops_per_token(MELLUM, seq) == 2 * 6 * seq * 32 * 128 == 805_306_368  # `flops.py`'s count
    assert builder.attention_flops_per_token(MELLUM, seq) == 292_561_920 + 805_306_368
    needed = builder.needed_flops_per_token(MELLUM, seq)
    assert needed == 6 * sum(parts.values()) + 1_097_868_288 == 3_058_443_264  # 50.1 TFLOP a step of 16,384
    assert round(needed * seq / 1e12, 1) == 50.1
    every_full = builder.needed_flops_per_token(dict(MELLUM, layer_types=["full_attention"] * 28), seq)
    assert round(every_full / 1e6) == 5182 and round(8 * 6 * seq * 32 * 128 / 1e6) == 3221
    # a window that spans the sequence is a full layer's count
    assert builder.mean_keys_seen(512, 1024) == 256 == builder.mean_keys_seen(512, None)
    # the grouped matmuls at given rows: three matrices, forward + backward
    assert builder.expert_matmul_flops(MELLUM, 16384) == 6 * 16384 * 3 * d * 896 == 608_811_614_208
    assert builder.expert_matmul_flops(MELLUM, 0) == 0
    # one held winner more in one layer: 16,384 rows x 37.2 MFLOP = 0.61 TFLOP, 1.2% of the step (ISSUE 50's hazard)
    assert round(builder.expert_matmul_flops(MELLUM, 16384) / (needed * seq) * 100, 1) == 1.2


PATHS = {
    "window-kernel": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/attn/window/flash_fwd/"
                      "pallas_call", "attn/window/kernels", ("flash_fwd", "fwd")),
    "full-kernel-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_core/"
                             "attn/full/flash_bwd_dkv/pallas_call", "attn/full/kernels", ("flash_bwd_dkv", "bwd")),
    "kv-repeat": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/attn/window/"
                  "broadcast_in_dim", "attn/window", ("layer/attn_core", "fwd")),
    "projection": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/bse,ehd->bshd/"
                   "dot_general", None, ("layer/attn_proj", "fwd")),
    "routed-experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/experts/"
                       "moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "no-window-model": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/flash_fwd/"
                        "pallas_call", None, ("flash_fwd", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_names_go_through_trace_moes_reduction_and_come_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe.classify
    with trace_mellum._lent_to_trace_moe():
        assert trace_moe.classify(path) == name and trace_moe.NAMES == trace_mellum.NAMES
    assert (trace_moe.NAMES, trace_moe.classify) == before  # the accepted reader reads what it read
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def run_with_series(series, steps=(10, 15), newest=None):
    return {"config": MELLUM, "traffic": {"warmup_steps": 2}, "trace": {"steps": list(steps)},
            "run_record": {"step_counters": newest or {"moe_held_rows_mean": 2048.0, "moe_load_max_over_mean": 3.5,
                                                       "moe_rows_moved_share": 0.5, "attn_window_tiles_visited_pct": 22.8},
                           "step_counter_series": series}}


def test_the_traced_steps_rows_come_from_the_records_series():
    """Loop step i of the window is `train_step` call 3 + i (compile step, two warm-up steps): steps 10..14 of the
    window are calls 13..17.  A step's mean is over 16 held experts x 8 layers."""
    series = [[s, {"moe_held_rows_mean": 2048.0 if s < 15 else 1024.0}] for s in range(40)]
    assert trace_mellum.traced_held_rows(run_with_series(series)) == (2 * 2048.0 + 3 * 1024.0) * 128
    assert trace_mellum.traced_held_rows(run_with_series([[s, {"moe_held_rows_mean": 0.0}] for s in range(40)])) == 0.0
    assert trace_mellum.traced_held_rows(run_with_series(series[:16])) is None  # a traced step is missing
    assert trace_mellum.traced_held_rows(run_with_series([])) is None
    assert trace_mellum.traced_held_rows({"config": MELLUM, "trace": None, "run_record": None}) is None


def test_the_routed_share_splits_into_the_parts_a_later_change_can_move_alone(monkeypatch):
    seconds = {**dict.fromkeys(trace_mellum.NAMES, 0.0), "moe/router": 1.0, "moe/dispatch": 8.0, "moe/experts": 7.0,
               "moe/combine": 4.0}
    monkeypatch.setattr(trace_mellum, "names_of", lambda run: {"seconds": seconds, "window_s": 100.0, "steps": 5})
    readers = harness.layer_metric_readers()
    assert [readers[f"m2_moe_{part}_time_pct"].read({}) for part in ("dispatch", "experts", "combine")] == [8.0, 7.0, 4.0]
    assert readers["m2_moe_routed_time_pct"].read({}) == 20.0  # the router's 1.0 is in the sum alone


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 50): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[name] for name in OWN]
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": MELLUM, "traffic": {"warmup_steps": 2, "seq_len": 16384}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    # a record from before the counters reads as nothing; one with them gives the newest value
    assert readers["m2_window_tiles_visited_pct"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = run_with_series([])
    assert [readers[n].read(got) for n in ("m2_load_max_over_mean", "m2_held_rows_per_expert", "m2_rows_moved_share",
                                           "m2_window_tiles_visited_pct")] == [3.5, 2048.0, 0.5, 22.8]
    # a recorded trace of a program without the names (a dense step): nothing
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")
    with trace_mellum._lent_to_trace_moe():
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got is None or not any(got["seconds"].values())


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths, long enough for the loss to fall by the traffic's margin UNDER THE JOB'S
    WARM-UP: the sum of the rates grows with the square of the steps (0.14 nats in 81 steps, 0.5 by ~160)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed",
         "2147483900", "--seconds", "60", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    # the counters reach the readers through the run's record (off TPU no tile is visited: no window counter)
    assert {"m2_held_rows_per_expert", "m2_load_max_over_mean", "m2_rows_moved_share"} <= set(line["metric_names"])
