"""kind "qwen3_next_decoder": the configuration file against the catalog's row
key for key, the three cuts the guide names and nothing else, the builder's
parameter and operation counts against counts worked by hand (79.67B whole,
1,173.5M here), the shares of needed FLOPs the file's `distortion` quotes,
`trace_qwen3_next`'s names on path strings, the readers on a small trace
recorded on the chip and on runs with nothing to read, and the cell's
rehearsal on the CPU (the tier-1 copy of the comparison with the reference is
tests/test_qwen3_next_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import qwen3_next_decoder as builder  # noqa: E402
from benchmarks.lib import trace_mellum, trace_moe, trace_scopes  # noqa: E402
from benchmarks.lib import trace_qwen3_next as trace_q3n  # noqa: E402

CELL = "qwen3-next-ep16-1chip.seq8k"
NAME = "qwen3-next-80b-a3b-ep16-1chip"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    Q3N = json.load(f)

# The `config` of the catalog row Qwen3-Next-80B-A3B-Instruct (model-configs guide), every key.
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
OWN = ["q3n_gdn_proj_time_pct", "q3n_gdn_conv_time_pct", "q3n_gdn_scan_time_pct", "q3n_gdn_scan_roofline",
       "q3n_gated_attn_time_pct", "q3n_gated_attn_roofline", "q3n_moe_router_time_pct", "q3n_moe_routed_time_pct",
       "q3n_moe_shared_time_pct", "q3n_experts_roofline", "q3n_held_rows_per_expert", "q3n_load_max_over_mean",
       "q3n_rows_moved_share"]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == CATALOG and row["source_url"] == Q3N["source"]
    differ = {k for k, v in CATALOG.items() if k not in Q3N or Q3N[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(Q3N["reduced"])
    assert Q3N["reduced"] == {"num_hidden_layers": {"from": 48, "to": 8}, "num_experts": {"from": 512, "to": 32},
                              "vocab_size": {"from": 151936, "to": 18992}}
    # the guide's floors: whole periods and at least four layers, 8 experts, an eighth of the rows
    kinds = builder.layer_kinds(Q3N)
    assert kinds == ["gdn", "gdn", "gdn", "attention"] * 2 and Q3N["num_experts"] >= 8
    assert Q3N["vocab_size"] * 8 >= CATALOG["vocab_size"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] and entry["source"] == Q3N["source"]
    share = Q3N["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (16, 512, 0)
    assert share["num_experts_total"] == CATALOG["num_experts"] == share["chips_per_layer"] * Q3N["num_experts"]
    assert share["vocab_size_total"] == CATALOG["vocab_size"] == 8 * Q3N["vocab_size"]
    assert share["num_hidden_layers_total"] == 48 == share["pipeline_stages"] * Q3N["num_hidden_layers"]
    assert share["chips_total"] == share["pipeline_stages"] * share["chips_per_layer"] == 96
    assert Q3N["train"]["chips"] == 1 and Q3N["train"]["remat_policy"] in (None, "attn", "qkv_attn")
    # every inference is listed with its reason
    assert {"layers_that_run", "bias", "norms", "gated_delta_rule", "gated_attention", "rope", "router", "balance_loss",
            "multi_token_prediction", "dtypes", "initial_values", "optimizer_state_dtype", "optimizer_hyperparameters",
            "document_boundaries"} <= set(Q3N["assumed"])
    assert set(Q3N["train"]) == {"chips", "mesh", "strategy", "param_dtype", "compute_dtype", "optimizer", "lr_warmup_steps",
                                 "remat_policy"}
    assert Q3N["deployment"] and Q3N["distortion"]


def test_the_published_totals_read_back_from_the_file():
    share = Q3N["share"]
    assert builder.total_params(Q3N, uncut=True) == share["params_total"] == 79_674_391_296  # the card's "80B"
    assert builder.total_params(Q3N) == share["params_here"] == 1_173_540_992  # 9.39 GB of state at 8 B
    assert round(8 * share["params_here"] / 1e9, 2) == 9.39
    for text in ("79,674,391,296", "1,173,540,992", "37,918,912", "31,463,936", "9.39 GB"):
        assert text in share["why"], text


def test_parameter_counts_by_hand():
    d = 2048
    gdn = d * (2048 + 2048 + 4096 + 4096) + d * 64 + 4096 * d  # q|k|v|z; b|a; o
    gdn_other = 8192 * 4 + 32 + 32 + 128  # the convolutions, A_log, dt_bias, the gated norm
    attn = d * 16 * 512 + 2 * d * 2 * 256 + 16 * 256 * d
    block = d * 512 + 3 * d * 512 + d  # router, shared expert, its gate
    expert = 3 * d * 512
    assert (gdn + gdn_other, attn + 512, block, expert) == (33_718_464, 27_263_488, 4_196_352, 3_145_728)
    delta_layer, attn_layer = gdn + gdn_other + block + 2 * d, attn + 512 + block + 2 * d
    assert (delta_layer, attn_layer) == (37_918_912, 31_463_936)
    tables = lambda rows: 2 * rows * d + d  # noqa: E731: embedding, head, final norm
    assert builder.total_params(Q3N) == tables(18992) + 6 * delta_layer + 2 * attn_layer + 8 * 32 * expert
    assert builder.total_params(Q3N, uncut=True) == tables(151936) + 36 * delta_layer + 12 * attn_layer + 48 * 512 * expert
    active = 36 * delta_layer + 12 * attn_layer + 48 * 10 * expert + d * 151936
    assert round(active / 1e9, 2) == 3.56  # its "A3B", with the head
    # one period with 64 held (EP8) would be 8.2 GB and sit on the 1.25x rung: the file's `share.why`
    assert round(8 * builder.total_params(dict(Q3N, num_hidden_layers=4, num_experts=64)) / 1e9, 1) == 8.2


def test_needed_flops_by_hand():
    assert builder.routed_rows_per_token(Q3N) == 0.625  # 10 choices among 512, 32 of them held
    d, seq = 2048, 8192
    gdn, attn, expert = d * 12288 + d * 64 + 4096 * d, d * 16 * 512 + 2 * d * 512 + 4096 * d, 3 * d * 512
    parts = builder.matmul_params_by_part(Q3N)
    assert parts == {"gdn_proj": 6.0 * gdn, "attn_proj": 2.0 * attn, "router": 8.0 * d * 512, "shared": 8.0 * (expert + d),
                     "routed_experts": 8 * 0.625 * expert, "head": float(d * 18992)}
    assert builder.attention_flops_per_token(Q3N, seq) == 2 * 6 * seq * 16 * 256 == 402_653_184
    # the scalar-decay rule at chunk 64: 3 * 32 heads * (64 * (3 * 128 + 2 * 128) + 6 * 128 * 128) a layer
    assert builder.gdn_scan_flops_per_token(Q3N) == 6 * 3 * 32 * (64 * 640 + 6 * 16384) == 80_216_064
    needed = builder.needed_flops_per_token(Q3N, seq)
    assert needed == 6 * sum(parts.values()) + 402_653_184 + 80_216_064 == 2_551_873_536
    assert round(needed * seq / 1e12, 1) == 20.9  # TFLOP a step
    assert builder.expert_matmul_flops(Q3N, 5120) == 6 * 5120 * 3 * d * 512 and builder.expert_matmul_flops(Q3N, 0) == 0


def test_the_files_distortion_is_what_the_builder_computes():
    d, whole = builder.distortion(Q3N, 8192), builder.distortion(builder.published(Q3N), 8192)
    assert (d["routed_rows_per_token"], d["routed_rows_per_token_model"]) == (0.625, 10.0)
    assert (d["rows_per_held_expert_uniform"], d["rows_per_held_expert_deployed"]) == (160.0, 2560.0)
    stated = Q3N["distortion"]
    for text in ("10*32/512 = 0.625", "2,552 MFLOP", "24,271 MFLOP", "160 rows", "2,560",
                 *(f"{side[key]:.1f}%" for side in (d, whole) for key in (
                     "routed_experts_pct", "mixer_proj_pct", "attention_pct", "gdn_scan_pct", "router_shared_pct", "head_pct"))):
        assert text in stated, text
    assert [round(d[k], 1) for k in ("mixer_proj_pct", "attention_pct", "gdn_scan_pct", "router_shared_pct", "routed_experts_pct",
                                     "head_pct")] == [60.3, 15.8, 3.1, 7.9, 3.7, 9.1]
    assert round(sum(d[k] for k in d if k.endswith("_pct")), 6) == 100.0


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "seq8k") and config["kind"] == "qwen3_next_decoder"
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (8192, 1)
    same_traffic = [w["name"] for w in bench["workloads"] if w["traffic"] == "seq8k"]
    assert same_traffic == ["granite-h-micro-1chip.seq8k", "phi4-mini-flash-1chip.seq8k", "nemotron3-nano-ep8-1chip.seq8k",
                            "glm47-flash-ep8-1chip.seq8k", CELL]
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN == [m["name"] for m in bench["per_layer"]][-len(OWN):]  # appended, in the issue's order
    readers = harness.layer_metric_readers()
    assert all(readers[name].cells == [CELL] for name in own)
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            reader = readers[m["name"]]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == (reader.unit, reader.source, reader.layer, reader.moves)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"] if "workloads" in m and m["name"] not in OWN)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(bench["workloads"]) >= 12 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][11]["name"] == CELL and bench["configs"][10]["name"] == NAME


def test_model_kwargs_describe_the_published_layers_and_the_share():
    kw = builder.model_kwargs(Q3N, 8192)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["vocab_size"]) == \
        (2048, 8, 16, 2, 256, 18992)
    assert kw["layer_types"] == ("gdn", "gdn", "gdn", "attention") * 2 and "ffn_types" not in kw
    assert (kw["gdn_key_heads"], kw["gdn_value_heads"], kw["gdn_key_dim"], kw["gdn_value_dim"], kw["gdn_conv"]) == (16, 32, 128, 128, 4)
    assert (kw["rope_theta"], kw["rotary_dim"], kw["qk_norm"], kw["norm_eps"]) == (1e7, 64, "per_head", 1e-6)
    assert kw["attn_output_gate"] is True and kw["norm_zero_centred"] is True and kw["shared_expert_gate"] is True
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"], kw["moe_d_ff"]) == \
        (512, 32, 0, 10, 512)
    assert (kw["router_activation"], kw["norm_topk_prob"], kw["n_shared_experts"], kw["shared_expert_d_ff"]) == ("softmax", True, 1, 512)
    assert kw["routed_branch_init"] is True and "router_share_init" not in kw and kw["tie_embeddings"] is False
    for key, value in {"hidden_act": "gelu", "decoder_sparse_step": 2, "mlp_only_layers": [0], "tie_word_embeddings": True,
                       "rope_scaling": {"type": "yarn"}, "use_sliding_window": True, "norm_topk_prob": False}.items():
        with pytest.raises(ValueError, match="qwen3_next_decoder expresses"):
            builder.model_kwargs(dict(Q3N, **{key: value}), 8192)
    # the harness's rehearsal overrides six keys: two delta layers (no attention layer); the heads' and the experts' own
    # widths, the router's 512 outputs and the 32 held untouched
    toy = builder.model_kwargs(dict(Q3N, **harness.REHEARSAL_CONFIG), 256)
    assert (toy["d_model"], toy["n_layers"], toy["layer_types"]) == (256, 2, ("gdn", "gdn"))
    assert (toy["gdn_value_heads"], toy["gdn_key_dim"], toy["moe_d_ff"], toy["n_experts"], toy["n_experts_held"]) == (32, 128, 512, 512, 32)


def test_the_jobs_rate_warms_up_to_the_default_optimizers_own():
    schedule = builder.learning_rate(Q3N["train"])
    assert Q3N["train"]["lr_warmup_steps"] == 2000
    assert [float(schedule(step)) for step in (0, 1000, 2000, 10**6)] == pytest.approx([1.5e-7, 1.500750e-4, 3e-4, 3e-4], rel=1e-4)
    assert sum(float(schedule(step)) for step in range(60)) < 3e-4  # a run's sum of rates, 2.7e-4


PATHS = {
    "delta-projection": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/gdn/proj/bse,ef->bsf/"
                         "dot_general", "gdn/proj", ("layer/attn_proj", "fwd")),
    "delta-conv-recompute": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/"
                             "layer/attn_proj/gdn/conv/ssm_conv_fwd/pallas_call", "gdn/conv", ("layer/attn_proj", "recompute")),
    "delta-scan-kernel": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/shard_map/gdn/scan/"
                          "kda_fwd/pallas_call", "gdn/scan", ("layer/attn_core", "fwd")),
    "delta-scan-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_core/gdn/scan/"
                            "kda_bwd/pallas_call", "gdn/scan", ("layer/attn_core", "bwd")),
    "attention-gate": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/attn/gate/logistic",
                       "attn/gate", ("layer/attn_proj", "fwd")),
    "attention-projection": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/bse,ehd->bshd/"
                             "dot_general", "attn/mixer", ("layer/attn_proj", "fwd")),
    "attention-kernel": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_core/"
                         "flash_bwd_dq/pallas_call", "attn/mixer", ("flash_bwd_dq", "bwd")),
    "shared-expert": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/shared/bse,ef->bsf/"
                      "dot_general", "moe/shared", ("layer/mlp", "fwd")),
    "routed-experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/jit(_rung_forward)/moe/experts/"
                       "moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "the-head": ("jit(_train_step)/jvp(lm_head)/bse,ev->bsv/dot_general", None, ("lm_head", "fwd")),
    "a-name-that-only-contains-gdn": ("jit(_train_step)/jvp(layers)/gdn/scanner/add", None, ("layers", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_names_go_through_trace_moes_reduction_and_come_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe.classify
    with trace_q3n._lent_to_trace_moe():
        assert trace_moe.classify(path) == name and trace_moe.NAMES == trace_q3n.NAMES
    assert (trace_moe.NAMES, trace_moe.classify) == before  # the accepted reader reads what it read
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def run_with_series(series, steps=(10, 15), newest=None):
    return {"config": Q3N, "traffic": {"warmup_steps": 2}, "trace": {"steps": list(steps)},
            "run_record": {"step_counters": newest or {"moe_held_rows_mean": 160.0, "moe_load_max_over_mean": 9.5,
                                                       "moe_rows_moved_share": 0.125},
                           "step_counter_series": series}}


def test_the_traced_steps_rows_come_from_the_records_series():
    """Loop step i of the window is `train_step` call 3 + i: a step's mean is over 32 held experts x 8 layers."""
    series = [[s, {"moe_held_rows_mean": 160.0 if s < 15 else 80.0}] for s in range(40)]
    assert trace_mellum.traced_held_rows(run_with_series(series)) == (2 * 160.0 + 3 * 80.0) * 256
    assert trace_mellum.traced_held_rows(run_with_series(series[:16])) is None  # a traced step is missing


def test_the_shares_and_the_rooflines_divide_what_they_say(monkeypatch):
    seconds = {**dict.fromkeys(trace_q3n.NAMES, 0.0), "gdn/proj": 12.0, "gdn/conv": 17.0, "gdn/scan": 33.0, "attn/gate": 1.0,
               "attn/mixer": 9.0, "moe/shared": 4.0, "moe/router": 2.0, "moe/dispatch": 3.0, "moe/experts": 5.0, "moe/combine": 1.0}
    monkeypatch.setattr(trace_q3n, "names_of", lambda run: {"seconds": seconds, "window_s": 100.0, "steps": 5})
    readers = harness.layer_metric_readers()
    got = {name: readers[name].read({}) for name in OWN if name.endswith("_time_pct")}
    assert got == {"q3n_gdn_proj_time_pct": 12.0, "q3n_gdn_conv_time_pct": 17.0, "q3n_gdn_scan_time_pct": 33.0,
                   "q3n_gated_attn_time_pct": 10.0, "q3n_moe_router_time_pct": 2.0, "q3n_moe_routed_time_pct": 9.0,
                   "q3n_moe_shared_time_pct": 4.0}
    run = {"config": Q3N, "device": {"kind": "TPU v5 lite"}, "traffic": {"seq_len": 8192}, "cell": {"chips": 1},
           "summary": {"tokens_per_step": 8192}}
    want = 100.0 * builder.gdn_scan_flops_per_token(Q3N) * 8192 * 5 / 197e12 / 33.0
    assert readers["q3n_gdn_scan_roofline"].read(run) == pytest.approx(want) and 0 < want < 100
    monkeypatch.setattr(trace_scopes, "scopes_of", lambda run: {"steps": 5, "kernels": {
        "flash_fwd": {"seconds": 0.1}, "flash_bwd_dq": {"seconds": 0.1}, "flash_bwd_dkv": {"seconds": 0.2}}})
    want = 100.0 * builder.attention_flops_per_token(Q3N, 8192) * 8192 * 5 / 197e12 / 0.4
    assert readers["q3n_gated_attn_roofline"].read(run) == pytest.approx(want) and 0 < want < 100
    monkeypatch.setattr(trace_mellum, "traced_held_rows", lambda run: 5 * 160.0 * 256)
    want = 100.0 * builder.expert_matmul_flops(Q3N, 5 * 160.0 * 256) / 197e12 / 5.0
    assert readers["q3n_experts_roofline"].read(run) == pytest.approx(want)


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 57): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[name] for name in OWN]
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": Q3N, "traffic": {"warmup_steps": 2, "seq_len": 8192}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    got = run_with_series([])
    assert [readers[n].read(got) for n in ("q3n_load_max_over_mean", "q3n_held_rows_per_expert", "q3n_rows_moved_share")] == \
        [9.5, 160.0, 0.125]
    # a recorded trace of a program without the names (a dense step: it has mixers, no `gdn/*`): the shares read nothing
    recorded = os.path.join(DATA, "v5e_4chip_scoped.xplane.pb.gz")
    with trace_q3n._lent_to_trace_moe():
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got["seconds"]["attn/mixer"] > 0 and not trace_q3n._named(got)


def test_the_readers_on_a_small_trace_recorded_on_the_chip(monkeypatch):
    """`benchmarks/tools/record_qwen3_next_trace.py`'s two steps of the kind at the rehearsal's width with ONE period
    (three delta layers and an attention layer) on one v5e chip: every name is there, the parts stay inside the window
    and the three rooflines between 0 and 100%."""
    path = os.path.join(DATA, "v5e_one_chip_qwen3_next.xplane.pb.gz")
    with open(os.path.join(DATA, "v5e_one_chip_qwen3_next.facts.json")) as f:
        facts = json.load(f)
    with trace_q3n._lent_to_trace_moe():
        got = trace_moe.reduce_moe(path, window_span="bench_step")
    assert got["steps"] == facts["steps"] == 2 and got["devices"] == 1
    for name in trace_q3n.NAMES:
        assert got["seconds"][name] > 0, name
    assert sum(got["seconds"].values()) < got["window_s"] and trace_q3n._named(got)
    assert {"kda_fwd", "kda_bwd"} <= {op.split(".")[0] for op in facts["kernel_ops"]}  # the recurrence ran as the cell runs it
    monkeypatch.setattr(trace_q3n, "names_of", lambda run: got)
    config = facts["config"]
    run = {"config": config, "device": {"kind": facts["device_kind"]}, "traffic": {"seq_len": facts["seq_len"], "warmup_steps": 0},
           "cell": {"chips": 1}, "summary": {"tokens_per_step": facts["tokens_per_step"], "facts": {"kernel_ops": facts["kernel_ops"]}},
           "trace": {"path": path, "steps": [0, 2]}, "plan": {"loop": "train_steps"},
           "run_record": {"step_counters": facts["step_counters"][-1],
                          "step_counter_series": [[1 + i, c] for i, c in enumerate(facts["step_counters"])]}}
    readers = harness.layer_metric_readers()
    values = {name: readers[name].read(run) for name in OWN}
    assert all(v is not None for v in values.values()), values
    for name in ("q3n_gdn_scan_roofline", "q3n_gated_attn_roofline", "q3n_experts_roofline"):
        assert 0 <= values[name] < 100, (name, values[name])
    shares = [values[n] for n in OWN if n.endswith("_time_pct")]
    assert all(0 < s < 100 for s in shares) and sum(shares) < 100


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths (d 256, two delta layers, no attention layer) the cell runs to its result line with
    the reference's comparison passed; under the job's warm-up the loss needs more steps than a rehearsal's window to
    fall 0.5 nats (as `mellum2`'s and `glm47`'s), so `correct` is not asked of it here."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed",
         "3", "--seconds", "10", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["failed"] == 0, out.stdout[-3000:] + out.stderr[-3000:]
    reference = json.loads(next(l for l in lines if l.startswith("[bench] reference ")).split(" ", 2)[2])
    assert reference["ok"] is True
    assert {"q3n_held_rows_per_expert", "q3n_load_max_over_mean", "q3n_rows_moved_share"} <= set(line["metric_names"])
