"""The seven readers of the steady step seen from inside (`benchmarks/lib/step_rows.py`,
`benchmarks/lib/trace_idle.py`): on a run as a chip run left it (`data/run_steps_v5e.json`: the run's JSON
of a traced `olmoe-1chip.seq4k` run on a TPU v5e, PR 71, cut to what the readers use), on a parent's run
(`data/run_record_v5e.json`: a record without `steps`), on a synthetic XSpace with exact arithmetic, and on
a small trace recorded on one real chip (`benchmarks/tools/record_steps_trace.py`)."""

import copy
import importlib
import json
import os
import statistics

import pytest

from benchmarks.lib import step_rows, trace_idle
from benchmarks.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_one_chip_steps.xplane.pb.gz")
FROM_THE_RECORD = ("step_period_ms", "step_mfu_pct", "step_host_busy_pct", "step_library_ms", "step_report_ms")
FROM_THE_TRACE = ("device_idle_in_library_pct", "device_idle_outside_library_pct")
SPANS = ("data_next", "make_batch+dispatch", "loss_fetch", "report")


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture()
def run():
    return _load("run_steps_v5e.json")["run"]


def _read(name, run):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(run)


def _window_rows(run):
    return [r for r in run["run_record"]["steps"]["rows"] if r["start"] >= run["setup"]["t_window"]]


@pytest.mark.parametrize("name", FROM_THE_RECORD)
def test_each_reader_reads_a_number_from_a_recorded_run_and_nothing_from_a_parents_record(run, name, capsys):
    value = _read(name, run)
    assert isinstance(value, float) and value > 0, (name, value)
    assert capsys.readouterr().out.count("[bench] steps {") == 1  # the summary's one line
    parent = _load("run_record_v5e.json")["run"]  # a record from before `steps`
    assert "steps" not in parent["run_record"] and _read(name, parent) is None
    assert _read(name, dict(parent, run_record=None)) is None  # and a program that keeps no record
    assert "FAILED" not in capsys.readouterr().out


def test_the_period_is_the_loops_median_step_and_the_share_of_peak_is_mfu_pct_with_the_programs_numbers(run):
    from benchmarks import run as harness

    loop_median = statistics.median(harness.step_seconds(run["summary"]))
    period_ms = _read("step_period_ms", run)
    assert period_ms == pytest.approx(1e3 * loop_median, rel=1e-3)  # entry to entry against fetch to fetch
    assert period_ms == 1e3 * statistics.median(r["period_s"] for r in _window_rows(run))
    assert run["run_record"]["steps"]["tokens_per_step"] == run["summary"]["tokens_per_step"]  # the program's count
    outside = harness.end_to_end(dict(run["plan"], config=run["config"], traffic=run["traffic"]), run)
    assert _read("step_mfu_pct", run) == pytest.approx(outside["mfu_pct"], rel=1e-3)
    assert 0.0 < _read("step_mfu_pct", run) < 100.0


def test_the_library_ms_is_the_slots_sum_and_only_the_windows_rows_are_read(run):
    rows = _window_rows(run)
    assert 0 < len(rows) < len(run["run_record"]["steps"]["rows"])  # the warm-up's rows are in the record too
    assert _read("step_library_ms", run) == 1e3 * statistics.median(
        r["make_batch_s"] + r["dispatch_s"] + r["report_s"] for r in rows)
    assert _read("step_report_ms", run) == 1e3 * statistics.median(r["report_s"] for r in rows)
    assert _read("step_report_ms", run) < _read("step_library_ms", run) < _read("step_period_ms", run)
    # the chip host's thread clock ticks in 10 ms: the median row reads 0, the totals of the steady rows do not
    assert sorted({round(r["thread_cpu_s"], 6) for r in rows})[:2] == [0.0, 0.01]
    steady = [r for r in rows if r["period_s"] <= 2.0 * statistics.median(r["period_s"] for r in rows)]
    assert len(steady) == len(rows) - 1  # the period `jax.profiler.start_trace` fell into is set apart
    assert _read("step_host_busy_pct", run) == pytest.approx(
        100.0 * sum(r["thread_cpu_s"] for r in steady) / sum(r["period_s"] for r in steady))
    assert 0.5 < _read("step_host_busy_pct", run) < 5.0
    late = copy.deepcopy({k: v for k, v in run.items() if k != step_rows.KEY})
    late["setup"]["t_window"] = rows[-1]["start"] + 1.0  # a window no closed period began in
    assert all(_read(n, late) is None for n in FROM_THE_RECORD)


def test_a_reader_never_raises(run, capsys):
    broken = dict({k: v for k, v in run.items() if k != step_rows.KEY}, run_record={"steps": {"rows": "not a list"}})
    assert all(_read(n, broken) is None for n in FROM_THE_RECORD)
    assert "[bench] run record FAILED" in capsys.readouterr().out
    assert all(_read(n, dict(run, trace={"path": "/nowhere/x.xplane.pb"})) is None for n in FROM_THE_TRACE)
    assert "FAILED" in capsys.readouterr().out


# -- the idle split: a synthetic XSpace with exact arithmetic ----------------------


def _xspace_text(report=True):
    """Two device planes and one host thread, microseconds.  Window 0..200 (two bench_steps).
      device 0 busy 10..90, 110..190: idle 0..10, 90..110, 190..200 = 40
      device 1 busy 20..95, 105..200: idle 0..20, 95..105 = 30
      make_batch 2..6, 100..104; dispatch 6..12, 104..108; report 92..94, 196..199
      idle under them: device 0 = 8 + 8 + 5 = 21, device 1 = 8 + 7 + 0 = 15."""
    def plane(i, name, line, events):
        meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
                       for k, (n, _, _) in enumerate(events, 1))
        evs = "".join(f"events {{ metadata_id: {k} offset_ps: {s * 10**6} duration_ps: {(e - s) * 10**6} }}\n"
                      for k, (_, s, e) in enumerate(events, 1))
        return f'planes {{ id: {i} name: "{name}"\nlines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n{meta}}}\n'

    host = [("bench_step", 0, 100), ("bench_step", 100, 200), ("train_step/make_batch", 2, 6),
            ("train_step/dispatch", 6, 12), ("train_step/make_batch", 100, 104), ("train_step/dispatch", 104, 108)]
    if report:
        host += [("train/report", 92, 94), ("train/report", 196, 199)]
    return (plane(1, "/device:TPU:0", "XLA Ops", [("%fusion.1 = bf16[8] fusion(%p)", 10, 90), ("%fusion.2 = bf16[8] fusion(%p)", 110, 190)])
            + plane(2, "/device:TPU:1", "XLA Ops", [("%fusion.1 = bf16[8] fusion(%p)", 20, 95), ("%fusion.2 = bf16[8] fusion(%p)", 105, 200)])
            + plane(9, "/host:CPU", "python3", host))


def _synthetic(tmp_path, **kw):
    from jax.profiler import ProfileData

    path = str(tmp_path / "synthetic.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_xspace_text(**kw)))
    return path


def test_the_idle_split_on_a_synthetic_trace_is_exact_and_adds_up_to_the_mean_idle_share(tmp_path, capsys):
    path = _synthetic(tmp_path)
    got = trace_idle.split(tr.load(path), window_span="bench_step")
    us = 1e-6
    assert got["devices"] == 2 and got["window_s"] == pytest.approx(200 * us)
    assert got["idle_s"] == pytest.approx(35 * us) and got["in_library_s"] == pytest.approx(18 * us)
    assert got["by_span_s"] == {"train_step/make_batch": pytest.approx(8 * us), "train_step/dispatch": pytest.approx(7.5 * us),
                                "train/report": pytest.approx(2.5 * us)}
    trace = tr.reduce(tr.load(path), window_span="bench_step", span_names=SPANS)
    run = {"plan": {"loop": "train_steps"}, "trace": dict(trace, path=path)}
    inside, outside = (_read(n, run) for n in FROM_THE_TRACE)
    assert (inside, outside) == (pytest.approx(9.0), pytest.approx(8.5))
    assert inside + outside == pytest.approx(tr.mean_share_pct(trace, "idle_s"))  # 17.5: the MEAN over the devices
    assert _read("device_idle_pct", run) == pytest.approx(20.0)  # the WORST device's
    assert capsys.readouterr().out.count("[bench] idle by program span {") == 1  # one load, one line a run


def test_a_trace_without_the_report_span_is_a_parents_and_reads_as_nothing(tmp_path, capsys):
    path = _synthetic(tmp_path, report=False)
    assert trace_idle.split(tr.load(path), window_span="bench_step") is None
    run = {"plan": {"loop": "train_steps"}, "trace": {"path": path}}
    assert [_read(n, run) for n in FROM_THE_TRACE] == [None, None]
    assert all(_read(n, {"trace": None}) is None for n in FROM_THE_TRACE)  # and an untraced run
    assert "FAILED" not in capsys.readouterr().out


# -- the idle split and the rows of a small trace recorded on one real chip ----------


def test_on_a_recorded_trace_the_two_shares_add_up_to_the_mean_idle_and_every_span_has_idle_under_it():
    facts = _load("v5e_one_chip_steps.facts.json")
    profile = tr.load(RECORDED)
    trace = tr.reduce(profile, window_span="bench_step", span_names=SPANS, kernel_ops=facts["kernel_ops"])
    assert trace["window_spans"] == facts["steps"] and len(trace["devices"]) == 1
    run = {"plan": {"loop": "train_steps"}, "trace": dict(trace, path=RECORDED)}
    inside, outside = (_read(n, run) for n in FROM_THE_TRACE)
    assert inside > 0 and outside > 0
    assert inside + outside == pytest.approx(tr.mean_share_pct(trace, "idle_s"), abs=0.01)
    by_span = run[trace_idle.KEY]["by_span_s"]
    assert all(s > 0 for s in by_span.values())  # a host-bound toy: the chip waits under each of the three
    assert sum(by_span.values()) == pytest.approx(run[trace_idle.KEY]["in_library_s"])  # one thread: they never overlap
    # the program's own rows of the same steps: each slot's seconds are its spans' in the trace, to the clocks' reach
    rows = [r for r in facts["record_steps"]["rows"] if r["start"] >= facts["t_window"]][:facts["steps"]]
    spans = tr.host_spans(profile, trace_idle.LIBRARY_SPANS)
    for slot, name in (("make_batch_s", "train_step/make_batch"), ("dispatch_s", "train_step/dispatch"),
                       ("report_s", "train/report")):
        traced = sorted(e - s for s, e in spans[name])[len(spans[name]) // 2]
        assert statistics.median(r[slot] for r in rows) == pytest.approx(traced, rel=0.5, abs=2e-5), (slot, name)
    assert facts["record_steps"]["tokens_per_step"] == facts["tokens_per_step"]
