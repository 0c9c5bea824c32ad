"""trace_scopes.py and the readers on top of it: paths classified by hand, a
synthetic XSpace with exact arithmetic, and a trace recorded on four real
chips at a size where XLA emits the windowed-einsum collective-permute pairs
(`benchmarks/tools/record_scoped_trace.py`).  And what may never happen: a
reader raising."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import trace_reduce as tr  # noqa: E402
from benchmarks.lib import trace_scopes as ts  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "v5e_4chip_scoped.xplane.pb.gz")
SMALL = os.path.join(HERE, "data", "v5e_4chip_small.xplane.pb.gz")  # the parent's program: no scopes
NEW_READERS = ("mlp_time_pct", "attn_proj_time_pct", "lm_head_loss_time_pct", "optimizer_time_pct",
               "recompute_time_pct", "attn_core_time_pct", "flash_fwd_roofline", "flash_bwd_dq_roofline",
               "flash_bwd_dkv_roofline", "unscoped_time_pct", "make_batch_ms", "step_dispatch_ms",
               "layer_loop_time_pct")
SPANS = ("data_next", "make_batch+dispatch", "loss_fetch", "report")


def _reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name)


def _facts(path):
    with open(path.replace(".xplane.pb.gz", ".facts.json")) as f:
        return json.load(f)


def _run(path, facts, trace="reduce"):
    """The part of run.py's `run` the readers use, for a recorded trace."""
    if trace == "reduce":
        trace = tr.reduce(tr.load(path), window_span="bench_step", span_names=SPANS,
                          kernel_ops=facts["kernel_ops"])
        trace["path"] = path
    return {
        "plan": {"loop": "train_steps"}, "trace": trace, "cell": {"chips": facts["chips"]},
        "config": facts.get("config", {"kind": "dense_decoder"}), "traffic": {"seq_len": facts.get("seq_len", 512)},
        "device": {"kind": facts["device_kind"]},
        "summary": {"facts": {"kernel_ops": facts["kernel_ops"]},
                    "tokens_per_step": facts.get("tokens_per_step", 2048)},
    }


@pytest.fixture(autouse=True)
def _fresh_memo():
    ts._memo.clear()
    yield
    ts._memo.clear()


@pytest.mark.parametrize("path, want", [
    ("jit(_train_step)/jvp()/while/body/closed_call/layer/mlp/bse,ef->bsf/dot_general", ("layer/mlp", "fwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/layer/attn_proj/bse,ehd->bshd/dot_general",
     ("layer/attn_proj", "bwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/layer/mlp/mul",
     ("layer/mlp", "recompute")),
    ("jit(_train_step)/jvp(loss)/jit(log_softmax)/sub", ("loss", "fwd")),
    ("jit(_train_step)/transpose(jvp(lm_head))/convert_element_type", ("lm_head", "bwd")),
    ("jit(_train_step)/jvp(embed)/gather", ("embed", "fwd")),
    ("jit(_train_step)/optimizer/add", ("optimizer", "fwd")),
    # the kernel's own name is innermost, inside layer/attn_core, and repeats (platform_dependent's conds)
    ("jit(_train_step)/jvp()/while/body/closed_call/layer/attn_core/cond/jit(_train_step)/jvp()/while/body/"
     "closed_call/layer/attn_core/cond/branch_0_fun/flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call",
     ("flash_fwd", "fwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/layer/attn_core/"
     "cond/branch_0_fun/flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call", ("flash_fwd", "recompute")),
    # a forward kernel in the backward pass is a recompute with or without the remat marker
    ("jit(_train_step)/transpose(jvp())/while/body/layer/attn_core/flash_fwd/pallas_call", ("flash_fwd", "recompute")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/layer/attn_core/cond/branch_0_fun/"
     "flash_bwd_dkv/cond/branch_0_fun/flash_bwd_dkv/pallas_call", ("flash_bwd_dkv", "bwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/layer/attn_core/reduce_sum",
     ("layer/attn_core", "bwd")),
    # names count as whole path components only
    ("state['params']['lm_head']", ("unscoped", "fwd")),
    ("jit(loss_fn)/embedding/optimizer_state", ("unscoped", "fwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/dynamic_slice", ("unscoped", "bwd")),
    # the loop over the stack is a scope of its own, around the layers' regions
    ("jit(_train_step)/transpose(jvp(layers))/while/body/dynamic_update_slice", ("layers", "bwd")),
    ("jit(_train_step)/jvp(layers)/while/body/closed_call/layer/mlp/mul", ("layer/mlp", "fwd")),
    ("", ("unscoped", "fwd")),
    (None, ("unscoped", "fwd")),
])
def test_classify(path, want):
    assert ts.classify(path) == want


# -- a synthetic XSpace: exact arithmetic, both encodings of the path stat ------


def _xspace_text():
    """One device plane, microseconds.  Window 0..100 (one bench_step).
      while.1 10..90 (the layer loop: `layers` and nothing inside it) encloses:
        fusion.1 10..30  layer/mlp fwd            (path as str_value)
        flash_fwd.2 30..50 flash_fwd fwd, kernel  (path as ref_value)
        fusion.3 50..70  layer/mlp recompute
        collective-permute-start.4 70..71, collective-permute-done.4 71..75: layer/attn_proj bwd
        copy.5 75..80    no path at all
      fusion.6 92..96    optimizer
    """
    ops = [("%while.1 = (s32[]) while(%t), body=%b", 10, 80, "jit(_train_step)/jvp(layers)/while:"),
           ("%fusion.1 = bf16[8] fusion(%p), kind=kLoop", 10, 20,
            "jit(_train_step)/jvp()/while/body/closed_call/layer/mlp/dot_general:"),
           ('%flash_fwd.2 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"', 30, 20,
            "REF:jit(_train_step)/jvp()/while/body/layer/attn_core/flash_fwd/pallas_call:"),
           ("%fusion.3 = bf16[8] fusion(%p), kind=kOutput", 50, 20,
            "jit(_train_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/layer/mlp/mul:"),
           ("%collective-permute-start.4 = bf16[8] collective-permute-start(%w)", 70, 1,
            "jit(_train_step)/transpose(jvp())/while/body/checkpoint/layer/attn_proj/dot_general:"),
           ("%collective-permute-done.4 = bf16[8] collective-permute-done(%c)", 71, 4,
            "jit(_train_step)/transpose(jvp())/while/body/checkpoint/layer/attn_proj/dot_general:"),
           ("%copy.5 = bf16[8] copy(%x)", 75, 5, None),
           ("%fusion.6 = bf16[8] fusion(%g), kind=kLoop", 92, 4, "jit(_train_step)/optimizer/add:")]
    refs = [p[4:] for _, _, _, p in ops if p and p.startswith("REF:")]
    stat_meta = 'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n' + "".join(
        f'stat_metadata {{ key: {10 + i} value {{ id: {10 + i} name: "{r}" }} }}\n' for i, r in enumerate(refs))
    meta = evs = ""
    for i, (name, start, dur, path) in enumerate(ops, 1):
        stat = ""
        if path and path.startswith("REF:"):
            stat = f"stats {{ metadata_id: 1 ref_value: {10 + refs.index(path[4:])} }}"
        elif path:
            stat = f'stats {{ metadata_id: 1 str_value: "{path}" }}'
        quoted = name.replace('"', '\\"')
        meta += f'event_metadata {{ key: {i} value {{ id: {i} name: "{quoted}" {stat} }} }}\n'
        evs += f"events {{ metadata_id: {i} offset_ps: {start * 10**6} duration_ps: {dur * 10**6} }}\n"
    host = [("bench_step", 0, 100), ("train_step/make_batch", 10, 2), ("train_step/dispatch", 12, 3)]
    h_meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                     for i, (n, _, _) in enumerate(host, 1))
    h_evs = "".join(f"events {{ metadata_id: {i} offset_ps: {s * 10**6} duration_ps: {d * 10**6} }}\n"
                    for i, (_, s, d) in enumerate(host, 1))
    return (f'planes {{ id: 1 name: "/device:TPU:0"\nlines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{evs}}}\n'
            f"{meta}{stat_meta}}}\n"
            f'planes {{ id: 9 name: "/host:CPU"\nlines {{ id: 1 name: "python3" timestamp_ns: 0\n{h_evs}}}\n{h_meta}}}\n')


def _synthetic(tmp_path):
    from jax.profiler import ProfileData

    path = str(tmp_path / "synthetic.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_xspace_text()))
    return path


def test_event_paths_reads_str_and_ref_values(tmp_path):
    with open(_synthetic(tmp_path), "rb") as f:
        table = ts.event_paths(f.read())["/device:TPU:0"]
    by_name = {tr.op_name(text): path for text, path in table.items()}
    assert by_name["fusion.1"] == "jit(_train_step)/jvp()/while/body/closed_call/layer/mlp/dot_general"
    assert by_name["flash_fwd.2"].endswith("layer/attn_core/flash_fwd/pallas_call")  # through ref_value
    assert by_name["while.1"] == "jit(_train_step)/jvp(layers)/while" and "copy.5" not in by_name


def test_reduce_scopes_on_a_hand_made_trace(tmp_path):
    US = 1e-6
    got = ts.reduce_scopes(_synthetic(tmp_path), window_span="bench_step", kernel_ops=["flash_fwd.2"])
    assert got["window_s"] == pytest.approx(100 * US) and got["steps"] == 1 and got["devices"] == 1
    assert got["busy_s"] == pytest.approx(84 * US)
    sec = {(s, d): t for s, row in got["seconds"].items() for d, t in row.items()}
    assert sec == {
        ("layers", "fwd"): pytest.approx(10 * US),  # the while's own time, 80..90
        ("unscoped", "fwd"): pytest.approx(5 * US),  # the copy
        ("layer/mlp", "fwd"): pytest.approx(20 * US), ("layer/mlp", "recompute"): pytest.approx(20 * US),
        ("flash_fwd", "fwd"): pytest.approx(20 * US), ("layer/attn_proj", "bwd"): pytest.approx(5 * US),
        ("optimizer", "fwd"): pytest.approx(4 * US),
    }
    assert got["kernels"] == {"flash_fwd": {"calls": 1, "seconds": pytest.approx(20 * US)}}
    assert got["program_span_s"]["train_step/make_batch"] == [pytest.approx(2 * US)]
    run = _run(None, {"chips": 1, "kernel_ops": ["flash_fwd.2"], "device_kind": "TPU v5 lite"},
               trace={"path": str(tmp_path / "synthetic.xplane.pb")})
    assert _reader("mlp_time_pct").read(run) == pytest.approx(40.0)
    assert _reader("recompute_time_pct").read(run) == pytest.approx(20.0)
    assert _reader("attn_core_time_pct").read(run) == pytest.approx(20.0)
    assert _reader("unscoped_time_pct").read(run) == pytest.approx(5.0)
    assert _reader("layer_loop_time_pct").read(run) == pytest.approx(10.0)
    assert _reader("make_batch_ms").read(run) == pytest.approx(2e-3)
    assert _reader("step_dispatch_ms").read(run) == pytest.approx(3e-3)


# -- the trace recorded on four chips ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    facts = _facts(SCOPED)
    ts._memo.clear()
    run = _run(SCOPED, facts)
    return run, ts.reduce_scopes(SCOPED, window_span="bench_step", kernel_ops=facts["kernel_ops"]), facts


def test_the_recorded_trace_holds_what_the_four_chip_cell_holds(recorded):
    _, _, facts = recorded
    assert facts["chips"] == 4 and facts["device_kind"] == "TPU v5 lite"
    assert facts["collectives"]["collective-permute"] > 0  # the windowed-einsum pairs
    assert sorted(k.split(".")[0] for k in facts["kernel_ops"]) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert os.path.getsize(SCOPED) < 420_000
    with gzip.open(SCOPED, "rb") as f:
        tables = ts.event_paths(f.read())
    assert sorted(tables) == [f"/device:TPU:{i}" for i in range(4)]
    permutes = {tr.op_name(text): ts.classify(path)[0] for text, path in tables["/device:TPU:0"].items()
                if tr.op_name(text).startswith("collective-permute-start")}
    assert len(permutes) >= 40 and set(permutes.values()) <= {"layer/mlp", "layer/attn_proj", "lm_head"}


def test_every_scope_kernel_and_direction_is_found(recorded):
    _, got, _ = recorded
    assert got["devices"] == 4 and got["steps"] == 1 and got["scoped"]
    assert set(got["seconds"]) == set(ts.SCOPES) | set(ts.KERNELS) | {ts.UNSCOPED}
    for d in ts.DIRECTIONS:
        assert sum(row.get(d, 0.0) for row in got["seconds"].values()) > 0, d
    # two layers: forward + recomputed forward, one dq and one dkv call each, per device
    assert {k: v["calls"] for k, v in got["kernels"].items()} == {
        "flash_fwd": 4, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}
    assert got["seconds"]["flash_fwd"]["recompute"] == pytest.approx(got["seconds"]["flash_fwd"]["fwd"], rel=0.1)
    assert set(got["seconds"]["optimizer"]) == {"fwd"} and "recompute" not in got["seconds"]["loss"]
    for name in ts.PROGRAM_SPANS:
        assert len(got["program_span_s"][name]) == 1


def test_scopes_unscoped_and_mean_idle_add_up_to_the_window(recorded):
    run, got, _ = recorded
    read = {n: _reader(n).read(run) for n in NEW_READERS}
    assert all(v is not None for v in read.values()), read
    small = sum(got["seconds"][s].get(d, 0.0) for s in ("embed", "final_norm") for d in ts.DIRECTIONS)
    devices = run["trace"]["devices"]
    idle = 100.0 * sum(d["idle_s"] for d in devices) / len(devices) / run["trace"]["window_s"]
    total = (read["mlp_time_pct"] + read["attn_proj_time_pct"] + read["attn_core_time_pct"]
             + read["lm_head_loss_time_pct"] + read["optimizer_time_pct"] + read["layer_loop_time_pct"]
             + 100.0 * small / got["window_s"] + read["unscoped_time_pct"] + idle)
    assert total == pytest.approx(100.0, abs=0.01)
    assert got["window_s"] == pytest.approx(run["trace"]["window_s"])
    assert 0 < read["unscoped_time_pct"] < 5
    assert 0 < read["recompute_time_pct"] < read["mlp_time_pct"] + read["attn_proj_time_pct"] + read["attn_core_time_pct"]
    assert 0.5 < read["make_batch_ms"] < 20 and 0.5 < read["step_dispatch_ms"] < 20


def test_kernel_rooflines_weighted_by_time_reproduce_the_overall_one(recorded):
    run, got, _ = recorded
    roof = {k: _reader(k + "_roofline").read(run) for k in ts.KERNELS}
    assert all(0 < r < 100 for r in roof.values()), roof
    seconds = {k: got["kernels"][k]["seconds"] for k in ts.KERNELS}
    weighted = sum(roof[k] * seconds[k] for k in ts.KERNELS) / sum(seconds.values())
    executed = 2 * ts.KERNEL_MATMULS["flash_fwd"] + ts.KERNEL_MATMULS["flash_bwd_dq"] + ts.KERNEL_MATMULS["flash_bwd_dkv"]
    overall = _reader("attn_kernel_roofline").read(run)
    assert weighted * ts.NEEDED_MATMULS / executed == pytest.approx(overall, rel=1e-6)
    # the kernels' time is the same time the existing reduction calls kernel time
    mean_kernel_s = sum(d["kernel_s"] for d in run["trace"]["devices"]) / 4
    assert sum(seconds.values()) == pytest.approx(mean_kernel_s, rel=1e-9)


# -- nothing to read: None, never an exception ----------------------------------------


def _bad_traces(tmp_path):
    with gzip.open(SCOPED, "rb") as f:
        whole = f.read()
    truncated, empty = tmp_path / "truncated.xplane.pb", tmp_path / "empty.xplane.pb"
    truncated.write_bytes(whole[: len(whole) // 2])
    empty.write_bytes(b"")
    facts = _facts(SCOPED)
    return {
        "no trace": _run(None, facts, trace=None),
        "no path": _run(None, facts, trace={"window_s": 1.0}),
        "missing file": _run(None, facts, trace={"path": str(tmp_path / "gone.xplane.pb")}),
        "truncated": _run(None, facts, trace={"path": str(truncated)}),
        "empty": _run(None, facts, trace={"path": str(empty)}),
        "no scopes (the parent's program)": _run(SMALL, _facts(SMALL)),
        "half a run": {"trace": {"path": SCOPED}},
    }


def test_a_reader_with_nothing_to_read_returns_none_and_never_raises(tmp_path, capsys):
    for why, run in _bad_traces(tmp_path).items():
        ts._memo.clear()
        for name in NEW_READERS:
            assert _reader(name).read(run) is None, (why, name)
    out = capsys.readouterr().out
    assert "[bench] scopes FAILED:" in out  # said, on one line each, and survived
    assert all(line.startswith("[bench] scopes") for line in out.splitlines())


def test_the_trace_is_loaded_once_and_printed_on_one_line(recorded, capsys):
    run, _, _ = recorded
    ts._memo.clear()
    for name in NEW_READERS:
        _reader(name).read(run)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and lines[0].startswith("[bench] scopes {")
    table = json.loads(lines[0][len("[bench] scopes "):])
    assert set(table["s_per_step"]) == set(ts.SCOPES) | set(ts.KERNELS) | {ts.UNSCOPED}
    assert table["devices"] == 4 and table["program_span_ms"].keys() == set(ts.PROGRAM_SPANS)


def test_the_driver_side_reduction_initialises_no_backend():
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from benchmarks.tests import test_trace_scopes as t\n"
        "run = t._run(t.SCOPED, t._facts(t.SCOPED), trace={'path': t.SCOPED})\n"
        "assert t._reader('mlp_time_pct').read(run) > 0\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n" % ROOT)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
