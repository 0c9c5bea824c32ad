"""kind "block_diffusion_moe_decoder": the configuration file against the
catalog's row key for key, the three cuts the guide names and nothing else,
the builder's parameter and operation counts against counts worked by hand
(30.53B / 3.35B from the uncut keys, the mask's pairs against a brute-force
count), its refusals, `trace_sdar`'s names on path strings, the readers on
runs with nothing to read and on the recorded one-chip trace, and the cell's
rehearsal on the CPU (the tier-1 copy of the two comparisons with the
reference is tests/test_sdar_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import block_diffusion_moe_decoder as builder  # noqa: E402
from benchmarks.lib import trace_moe, trace_scopes, trace_sdar  # noqa: E402

CELL = "sdar-ep8-1chip.seq8k"
NAME = "sdar-30b-a3b-chat-ep8-1chip"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    SDAR = json.load(f)
LAYERS = SDAR["num_hidden_layers"]

# The `config` of the catalog row SDAR-30B-A3B-Chat (model-configs guide), every key.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
OWN = ["sdar_attn_roofline", "sdar_attn_mask_fill_pct", "sdar_experts_roofline"]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["config"] == CATALOG and row["source_url"] == SDAR["source"]
        assert row["not_given"] == ["block length", "noise schedule"]  # both under `assumed`
    differ = {k for k, v in CATALOG.items() if k not in SDAR or SDAR[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(SDAR["reduced"])
    assert SDAR["reduced"] == {"num_hidden_layers": {"from": 48, "to": LAYERS}, "num_experts": {"from": 128, "to": 16},
                               "vocab_size": {"from": 151936, "to": 18992}}
    # the guide's floors: at least four layers (one period is one layer), 8 experts, an eighth of the rows; ISSUE 62's 6..12
    assert 6 <= LAYERS <= 12 and SDAR["num_experts"] >= 8
    assert SDAR["vocab_size"] * 8 == CATALOG["vocab_size"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(SDAR["reduced"]) and entry["source"] == SDAR["source"]
    share = SDAR["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (8, 128, 0)
    assert share["num_experts_total"] == CATALOG["num_experts"] == share["chips_per_layer"] * SDAR["num_experts"]
    assert share["vocab_size_total"] == CATALOG["vocab_size"] and share["num_hidden_layers_total"] == 48
    assert SDAR["train"]["chips"] == 1 and SDAR["train"]["remat_policy"] == "qkv_attn"
    # every inference is listed with its reason
    assert {"block_length", "noise_schedule", "mask_token_id", "no_shift", "qk_norm", "router_aux_loss_coef", "router",
            "attention", "dtypes", "initial_values", "optimizer_state_dtype", "optimizer_hyperparameters",
            "document_boundaries"} <= set(SDAR["assumed"])
    assert (SDAR["assumed"]["block_length"]["value"], SDAR["assumed"]["noise_schedule"]["kind"],
            SDAR["assumed"]["noise_schedule"]["eps"]) == (4, "linear", 0.001)
    assert (SDAR["qk_norm"], SDAR["router_aux_loss_coef"]) == ("per_head", 0.001)
    assert set(SDAR["train"]) == {"chips", "mesh", "strategy", "param_dtype", "compute_dtype", "optimizer", "lr_warmup_steps",
                                  "remat_policy"}
    assert SDAR["deployment"] and SDAR["train"]["lr_warmup_steps"] == 2000


def test_the_files_distortion_is_what_the_builder_computes():
    d = builder.distortion(SDAR, 8192)
    assert (d["rows_per_held_expert_uniform"], d["rows_per_held_expert_deployed"]) == (1024.0, 8192.0)
    stated = SDAR["distortion"]
    for text in ("8*16/128 = 1", f"{d['needed_mflop_per_token']:,.0f} MFLOP", f"{d['attention_pct']:.1f}%",
                 f"{d['attn_proj_pct']:.1f}%", f"{d['routed_experts_pct']:.1f}%", f"{d['head_pct']:.1f}%",
                 f"{d['router_pct']:.1f}%", "53,970 MFLOP", f"{d['attention_pct_model']:.1f}%",
                 f"{d['routed_experts_pct_model']:.1f}%", f"{d['attn_proj_pct_model']:.1f}%", f"{d['head_pct_model']:.1f}%",
                 "1,024 rows", "8,192", "136 of a head's 256", "80", "5,746"):
        assert text in stated, text
    assert round(d["needed_mflop_per_token_model"]) == 53970
    assert SDAR["share"]["depth"].startswith("TEN layers") and "14.32" in SDAR["share"]["depth"]  # the depth, AOT's peak beside the chip's


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "seq8k") and config["kind"] == "block_diffusion_moe_decoder"
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (8192, 1)
    same_traffic = [w["name"] for w in bench["workloads"] if w["traffic"] == "seq8k"]
    assert same_traffic[-1] == CELL and len(same_traffic) == 6  # the six differ by the model alone
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert own == OWN and [m["name"] for m in bench["per_layer"]][-3:] == OWN  # appended, nothing before them moved
    # the round's contract for BENCHMARK.json ("`per_layer`: 1 to 128 metrics"; a file outside it is refused before a run):
    # why three of ISSUE 62's eleven readers are here, each of a layer that runs and can move
    assert len(bench["per_layer"]) <= 128
    assert all((m["better"], m["moves"]) == ("higher", "tokens_per_s_per_chip") for m in bench["per_layer"] if m["name"] in OWN)
    readers = harness.layer_metric_readers()
    assert all(readers[name].cells == [CELL] for name in own)
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            reader = readers[m["name"]]
            assert (m["unit"], m["source"], m["layer"], m["moves"]) == (reader.unit, reader.source, reader.layer, reader.moves)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"] if "workloads" in m and m["name"] not in OWN)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_model_kwargs_describe_the_published_layers_the_share_and_the_objective():
    kw = builder.model_kwargs(SDAR, 8192)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["n_kv_heads"], kw["attn_head_dim"], kw["vocab_size"]) == \
        (2048, LAYERS, 32, 4, 128, 18992)
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"], kw["moe_d_ff"]) == \
        (128, 16, 0, 8, 768)
    assert (kw["router_activation"], kw["norm_topk_prob"], kw["router_aux_loss_coef"]) == ("softmax", True, 0.001)
    assert kw["qk_norm"] == "per_head" and kw["tie_embeddings"] is False and kw["rope_theta"] == 1e6
    assert kw["routed_branch_init"] is True and kw["router_share_init"] is True  # `assumed.initial_values`
    assert (kw["diffusion_block"], kw["diffusion_mask_id"], kw["diffusion_eps"]) == (4, 18991, 0.001)
    assert "layer_windows" not in kw and "layer_ropes" not in kw  # no window, one rope for the model
    # the harness's rehearsal overrides six keys: the head's and the experts' own widths, the router's 128 outputs and the 16
    # held untouched, and the table's last row is the mask id there too
    toy = builder.model_kwargs(dict(SDAR, **harness.REHEARSAL_CONFIG), 256)
    assert (toy["d_model"], toy["n_layers"], toy["attn_head_dim"], toy["moe_d_ff"], toy["n_experts"], toy["n_experts_held"],
            toy["diffusion_mask_id"]) == (256, 2, 128, 768, 128, 16, 511)


@pytest.mark.parametrize("change", [
    {"attention_bias": True}, {"sliding_window": 4096}, {"use_sliding_window": True}, {"mlp_only_layers": [0]},
    {"decoder_sparse_step": 2}, {"norm_topk_prob": False}, {"hidden_act": "gelu"}, {"tie_word_embeddings": True},
    {"rope_scaling": {"type": "yarn"}}, {"qk_norm": None},
], ids=lambda c: next(iter(c)))
def test_the_builder_refuses_what_the_programs_layers_do_not_express(change):
    with pytest.raises(ValueError):
        builder.model_kwargs(dict(SDAR, **change), 8192)


def test_the_builder_refuses_a_block_that_does_not_divide_the_sequence_and_another_schedule():
    assumed = SDAR["assumed"]
    with pytest.raises(ValueError, match="does not divide"):
        builder.model_kwargs(dict(SDAR, assumed=dict(assumed, block_length={"value": 3})), 8192)
    with pytest.raises(ValueError, match="does not divide"):
        builder.model_kwargs(SDAR, 8190)
    with pytest.raises(ValueError, match="linear schedule"):
        builder.model_kwargs(dict(SDAR, assumed=dict(assumed, noise_schedule=dict(assumed["noise_schedule"], kind="cosine"))), 8192)


def test_parameter_counts_by_hand():
    d = 2048
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128  # q, o; k, v
    router, expert = d * 128, 3 * d * 768
    norms = 2 * d + 2 * 128  # ln1, ln2; q_norm, k_norm
    assert (attention, router, expert) == (18_874_368, 262_144, 4_718_592)  # 18.874M, 0.262M, 4.719M
    held_layer = attention + router + 16 * expert
    assert round(held_layer / 1e6, 2) == 94.63 and 2 * 18992 * d == 77_791_232  # ISSUE 62's 94.64M (rounded parts), 77.79M
    assert builder.total_params(dict(SDAR, num_hidden_layers=8)) == 2 * 18992 * d + d + 8 * (held_layer + norms) == 834_899_968
    assert round(834_899_968 * 8 / 1e9, 2) == 6.68  # GB of state and gradients at 8 B a parameter
    uncut = 2 * 151936 * d + d + 48 * (attention + router + 128 * expert + norms)
    assert builder.total_params(SDAR, uncut=True) == uncut and round(uncut / 1e9, 2) == 30.53  # the card's "30B"
    active = 48 * (attention + router + 8 * expert) + 2 * 151936 * d
    assert builder.active_params(SDAR, uncut=True) == active and round(active / 1e9, 2) == 3.35  # its "A3B"
    assert round((attention + router + 128 * expert + norms) / 1e6, 1) == 623.1  # a whole layer: 4.98 GB
    # the program counts the same, leaf for leaf
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    kw = builder.model_kwargs(SDAR, 8192)
    kw.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params() == builder.total_params(SDAR)


def test_needed_flops_by_hand():
    eight = dict(SDAR, num_hidden_layers=8)
    assert builder.routed_rows_per_row(SDAR) == 1.0  # 8 choices among 128, 16 of them held
    d, seq, block = 2048, 8192, 4
    parts = builder.matmul_params_by_part(eight)
    # both copies pass every layer, the noisy one the head
    assert parts == {"attn_proj": 2 * 8 * 18_874_368.0, "router": 2 * 8 * 262_144.0, "routed_experts": 2 * 8 * 1.0 * 4_718_592,
                     "head": float(d * 18992)}
    assert builder.mask_pairs(seq, block) == seq * seq + seq * block == 67_141_632
    assert builder.attention_flops_per_token(eight, seq) == 12 * 8 * (seq + block) * 32 * 128 == 3_222_798_336
    needed = builder.needed_flops_per_token(eight, seq)
    assert needed == 6 * sum(parts.values()) + 3_222_798_336 and round(needed / 1e6) == 5746
    assert round(needed * seq / 1e12, 1) == 47.1  # TFLOP a step
    assert round(100 * builder.attention_flops_per_token(eight, seq) / needed, 1) == 56.1
    # one `flash_fwd` call is what the accepted readers credit it with: a layer's needed attention x 2 / 6 = 2 matmuls x 2 D
    # flops x heads x the mask's pairs (`trace_scopes.kernel_roofline_pct`): ONE call a layer and direction
    per_call = builder.attention_flops_per_token(SDAR, seq) / LAYERS * 2 / 6 * seq
    assert per_call == 2 * 2 * 128 * 32 * builder.mask_pairs(seq, block)
    # the grouped matmuls at given rows: three matrices, forward + backward
    assert builder.expert_matmul_flops(SDAR, 16384) == 6 * 16384 * 3 * d * 768 and builder.expert_matmul_flops(SDAR, 0) == 0


@pytest.mark.parametrize("seq, block", [(64, 4), (128, 32), (96, 8)])
def test_the_masks_pairs_against_a_brute_force_count(seq, block):
    """`mask_pairs` against the reference's explicit boolean mask, pair by pair."""
    import jax.numpy as jnp

    from benchmarks.lib import reference_sdar

    rows = jnp.arange(2 * seq)
    assert int(reference_sdar.seen(rows, rows, block=block, noisy_rows=seq).sum()) == builder.mask_pairs(seq, block)
    plain = reference_sdar.seen(jnp.arange(seq), jnp.arange(seq), block=block, noisy_rows=0)
    assert int(plain.sum()) == (seq * seq + seq * block) // 2  # block-causal over one copy


PATHS = {
    "kernel": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/attn/block_diffusion/cond/"
               "branch_0_fun/flash_fwd/pallas_call", "attn/block_diffusion/kernels", ("flash_fwd", "fwd")),
    "kernel-backward": ("jit(_train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/layer/attn_core/"
                        "attn/block_diffusion/flash_bwd_dkv/pallas_call", "attn/block_diffusion/kernels", ("flash_bwd_dkv", "bwd")),
    "kv-repeat": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/attn/block_diffusion/"
                  "broadcast_in_dim", "attn/block_diffusion", ("layer/attn_core", "fwd")),
    "noise": ("jit(_train_step)/jvp(diffusion/noise)/threefry2x32", "diffusion/noise", ("unscoped", "fwd")),
    "projection": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/bse,ehd->bshd/"
                   "dot_general", None, ("layer/attn_proj", "fwd")),
    "routed-experts": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/experts/"
                       "moe_gmm/pallas_call", "moe/experts", ("layer/mlp", "fwd")),
    "causal-model": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_core/flash_fwd/"
                     "pallas_call", None, ("flash_fwd", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_names_go_through_trace_moes_reduction_and_come_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe.classify
    with trace_sdar._lent_to_trace_moe():
        assert trace_moe.classify(path) == name and trace_moe.NAMES == trace_sdar.NAMES
    assert (trace_moe.NAMES, trace_moe.classify) == before  # the accepted reader reads what it read
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 62): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[name] for name in OWN]
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": SDAR, "traffic": {"warmup_steps": 2, "seq_len": 8192}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    # a record from before the counters reads as nothing; one with them gives the newest value
    assert readers["sdar_attn_mask_fill_pct"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = {"run_record": {"step_counters": {"diffusion_masked_share": 0.4993, "attn_diffusion_mask_fill_pct": 80.0390625}}}
    assert readers["sdar_attn_mask_fill_pct"].read(got) == 80.0390625
    assert trace_sdar.counter(got, "diffusion_masked_share") == 0.4993  # on the `[bench] step counters` line, read by no metric
    # a recorded trace of a program without the names (a dense causal step): nothing
    recorded = os.path.join(ROOT, "benchmarks", "tests", "data", "v5e_4chip_scoped.xplane.pb.gz")
    with trace_sdar._lent_to_trace_moe():
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got is None or not any(got["seconds"].values())


def test_the_readers_on_the_recorded_one_chip_trace():
    """`benchmarks/tests/data/v5e_one_chip_sdar.*` (`benchmarks/tools/record_sdar_trace.py`, PR 62): two steps of the kind at
    the rehearsal's width, two layers, 1 x 1,024 tokens, on one v5e chip.  The names are found, the kernels' seconds are
    under `attn/block_diffusion/kernels`, the roofline is the needed FLOPs over them, and the accepted kernel readers see
    ONE call a layer and direction."""
    data = os.path.join(ROOT, "benchmarks", "tests", "data")
    path = os.path.join(data, "v5e_one_chip_sdar.xplane.pb.gz")
    with open(os.path.join(data, "v5e_one_chip_sdar.facts.json")) as f:
        facts = json.load(f)
    config = facts["config"]
    run = {"config": config, "device": {"kind": facts["device_kind"]}, "traffic": {"seq_len": facts["seq_len"], "warmup_steps": 0},
           "cell": {"chips": 1}, "summary": {"tokens_per_step": facts["tokens_per_step"], "facts": {"kernel_ops": facts["kernel_ops"]}},
           "trace": {"path": path, "steps": [0, 2]}, "plan": {"loop": "train_steps"},
           "run_record": {"step_counters": facts["step_counters"][-1],
                          "step_counter_series": [[1 + i, c] for i, c in enumerate(facts["step_counters"])]}}
    trace_sdar._memo.pop(path, None)
    got = trace_sdar.names_of(run)
    assert got["steps"] == facts["steps"] == 2 and got["devices"] == 1
    for name in trace_sdar.NAMES:
        assert got["seconds"][name] > 0, name
    assert sum(got["seconds"].values()) < got["window_s"]
    readers = harness.layer_metric_readers()
    values = {name: readers[name].read(run) for name in OWN}
    layers = config["num_hidden_layers"]
    needed = builder.attention_flops_per_token(config, facts["seq_len"]) * facts["tokens_per_step"] * got["steps"]
    assert values["sdar_attn_roofline"] == pytest.approx(100 * needed / 197e12 / got["seconds"][trace_sdar.KERNELS])
    assert 0 < values["sdar_attn_roofline"] < 100
    # the grouped matmuls at the rows the two traced steps gave the held experts: 128 a held expert and layer, 16 x 2 of them
    rows = sum(c["moe_held_rows_mean"] for c in facts["step_counters"]) * config["num_experts"] * layers
    assert rows == 2 * 128 * 16 * layers
    assert values["sdar_experts_roofline"] == pytest.approx(
        100 * builder.expert_matmul_flops(config, rows) / 197e12 / got["seconds"]["moe/experts"])
    assert 0 < values["sdar_experts_roofline"] < 100
    assert values["sdar_attn_mask_fill_pct"] == pytest.approx(100 * (1024 * 1024 + 1024 * 4) / (3 * 1024 * 1024))  # tiles of 1024
    # the accepted kernel readers credit each call with ONE layer's work: one call a layer and direction
    scopes = trace_scopes.scopes_of(run)
    assert {k: round(v["calls"] / got["steps"]) for k, v in scopes["kernels"].items()} == {
        "flash_fwd": layers, "flash_bwd_dq": layers, "flash_bwd_dkv": layers}
    assert all(0 < readers[n].read(run) < 100 for n in ("flash_fwd_roofline", "flash_bwd_dq_roofline", "flash_bwd_dkv_roofline"))


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths: both comparisons with the reference hold (`[bench] diffusion reference`, `[bench]
    reference`), the step counters reach the readers; the loss guard is NOT asked of a short rehearsal (a warm-up of 2,000
    steps, and 64 blocks a sequence where the cell has 2,048: the draw's spread is a nat there)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed",
         "2147483900", "--seconds", "15", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    text = out.stdout
    assert "[bench] diffusion reference" in text and '"ok": true' in text.split("[bench] diffusion reference")[1].splitlines()[0]
    reference = json.loads(next(l for l in text.splitlines() if l.startswith("[bench] reference ")).split(" ", 2)[2])
    assert reference["ok"] is True
    line = json.loads(text.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    assert "sdar_attn_mask_fill_pct" in line["metric_names"]
