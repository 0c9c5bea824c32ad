"""The twelve readers of a run's record (`benchmarks/lib/run_record.py`) on a
record as a chip run left it (`data/run_record_v5e.json`: the run's JSON of a
traced `internlm2-1chip.seq4k` run on a TPU v5e, PR 35, cut to what the
readers and the tool use), and on a run that has none."""

import copy
import importlib
import json
import os

import pytest

from benchmarks.lib import run_record
from benchmarks.tools import run_record as tool

HERE = os.path.dirname(os.path.abspath(__file__))
TWELVE = ("worker_spawn_s", "jax_import_s", "chip_wait_s", "device_open_s", "fit_unnamed_s",
          "runtime_shutdown_s", "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s",
          "setup_cache_misses", "train_step_stalls", "report_delivery_ms")


@pytest.fixture()
def run():
    with open(os.path.join(HERE, "data", "run_record_v5e.json")) as f:
        return json.load(f)["run"]


def _read(name, run):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(run)


@pytest.mark.parametrize("name", TWELVE)
def test_each_reader_reads_a_number_from_a_recorded_record_and_nothing_without_one(run, name):
    value = _read(name, run)
    assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert _read(name, dict(run, run_record=None)) is None
    empty = {"trace_id": "0", "spans": [], "runtime_spans": [], "stalls": [],
             "reports": {"count": 0, "polls": 0, "median_s": None, "max_s": None}}
    assert _read(name, dict(run, run_record=empty)) in (None, 0)  # only the count of no stalls is a number


def test_the_five_parts_add_up_to_fit_to_loop_and_the_unnamed_part_is_small(run):
    parts = [_read(n, run) for n in ("worker_spawn_s", "jax_import_s", "chip_wait_s", "device_open_s",
                                     "fit_unnamed_s")]
    fit_to_loop_s = run["start"]["t_loop"] - run["clocks"]["t_fit"]
    assert sum(parts) == pytest.approx(fit_to_loop_s, abs=0.1)
    assert parts[1] > 0.2 and parts[4] < 1.0
    spans = {s["name"]: s for s in run["run_record"]["spans"]}
    assert parts[3] == pytest.approx(spans["train::backend::device_open"]["end"]
                                     - spans["train::backend::device_open"]["start"])


def test_the_setups_jax_seconds_lie_inside_setup_s_and_count_only_the_setup(run):
    named = sum(_read(n, run) for n in ("setup_trace_s", "setup_lower_s", "setup_compile_or_load_s"))
    setup_s = run["setup"]["t_window"] - run["start"]["t_loop"]
    assert 0 < named < setup_s
    late = copy.deepcopy(run)
    compiles = [s for s in late["run_record"]["spans"] if s["name"] == "jax::compile"]
    extra = dict(compiles[0], span_id="late", start=late["setup"]["t_window"] + 1.0,
                 end=late["setup"]["t_window"] + 3.0, attrs={"fun_name": "late", "cache": "miss"})
    late["run_record"]["spans"].append(extra)
    assert _read("setup_compile_or_load_s", late) == _read("setup_compile_or_load_s", run)
    assert _read("setup_cache_misses", late) == _read("setup_cache_misses", run) == 0  # a warm run
    early = dict(extra, span_id="early", start=run["start"]["t_loop"] + 0.001, end=run["start"]["t_loop"] + 0.002)
    late["run_record"]["spans"].append(early)
    assert _read("setup_cache_misses", late) == 1


def test_stalls_are_counted_in_the_window_only(run):
    stalls = run["run_record"]["stalls"]
    in_window = [e for e in stalls if e["start"] >= run["setup"]["t_window"]]
    assert _read("train_step_stalls", run) == len(in_window)
    before = copy.deepcopy(run)
    before["run_record"]["stalls"] = [dict(e, start=run["setup"]["t_window"] - 5.0) for e in stalls]
    assert _read("train_step_stalls", before) == 0


def test_a_nested_trace_is_not_counted_twice():
    spans = [{"name": "jax::trace", "start": 10.0, "end": 14.0, "attrs": {}},
             {"name": "jax::trace", "start": 11.0, "end": 12.0, "attrs": {}},
             {"name": "train::worker::run_train_fn", "start": 9.0, "end": 30.0, "attrs": {}}]
    run = {"start": {"t_loop": 9.5}, "setup": {"t_window": 20.0},
           "run_record": {"spans": spans, "runtime_spans": [], "stalls": [], "reports": {}}}
    assert run_record.setup_s_under(run, "jax::trace") == pytest.approx(4.0)


def test_a_reader_never_raises_and_a_program_without_the_record_reads_as_nothing(run, monkeypatch, capsys):
    broken = dict(run, run_record={"spans": "not a list"})
    assert all(_read(n, broken) is None for n in TWELVE)
    assert "[bench] run record FAILED" in capsys.readouterr().out
    import ray_tpu.train

    monkeypatch.delattr(ray_tpu.train, "last_run_record")  # the parent commit's `ray_tpu.train`
    parent = {k: v for k, v in run.items() if k != "run_record"}
    assert all(_read(n, parent) is None for n in TWELVE)
    assert parent["run_record"] is None and capsys.readouterr().out == ""


def test_the_tool_lays_out_the_wall_clock_and_classifies_the_stalls(run):
    clock = tool.wall_clock(run)
    assert clock["fit_to_loop_s"] == pytest.approx(sum(
        clock[f"  {n}"] for n in ("worker_spawn_s", "jax_import_s", "chip_wait_s", "device_open_s",
                                  "fit_unnamed_s")), abs=0.1)
    assert clock["  neither trace, lower nor compile"] > 0
    assert clock["runtime_shutdown_s"] >= clock["  runtime::shutdown::workers_exit"]
    events = tool.compile_events(run)
    assert events and events == sorted(events, key=lambda r: -r["seconds"])
    assert {("jit(_train_step)", "jax::compile", "hit"), ("_train_step", "jax::trace", None)} <= {
        (r["fun_name"], r["kind"], r["cache"]) for r in events}
    for row in tool.stalls(run):
        assert row["where"] in ("set-up", "window") and 0.0 <= row["off_cpu_pct"] <= 100.0
