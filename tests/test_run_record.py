"""The run record (`ray_tpu/train/run_record.py`): one per `fit()`, kept with
`RAY_TPU_TRACE` unset — lifecycle spans under one trace id across the
driver/worker hop, compile events, the steady step's rows, stalled steps,
report delivery."""

import errno
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train import run_record
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLACK_S = 0.05  # two processes' `time.time()` on one host


def _loop(config):
    import sys

    from ray_tpu import train

    train.report({"jax_imported_by_on_start": "jax" in sys.modules})
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import run_record

    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    clock = run_record.StepClock()  # the step's clock alone, as `LMTrainContext.train_step` drives it
    clock.note_batch((2, 8))
    for i in range(6):
        clock.enter()
        train.report({"i": i, "y": float(f(jnp.ones((8, 8)))[0, 0])})


@pytest.fixture(scope="module")
def fit_record():
    """One `JaxTrainer.fit` with tracing OFF: (Result, the record read after
    shutdown, the spans the flush to the head had left there)."""
    import ray_tpu
    from ray_tpu.train import JaxConfig, JaxTrainer, ScalingConfig, last_run_record
    from ray_tpu.util.state import list_spans

    assert not tracing.is_enabled()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        result = JaxTrainer(_loop, train_loop_config={}, scaling_config=ScalingConfig(num_workers=1),
                            backend_config=JaxConfig(platform="cpu")).fit()
        at_head = list_spans(limit=10000)
    finally:
        ray_tpu.shutdown()
    assert result.error is None
    return result, last_run_record(), at_head


def _by_name(record):
    out = {}
    for s in record["spans"] + record["runtime_spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


PARENTS = {
    "train::fit": None,
    "train::executor::start": "train::fit",
    "train::worker_group::spawn": "train::executor::start",
    "train::worker_group::creation_task": "train::worker_group::spawn",
    "worker::boot::connect": "train::worker_group::spawn",
    "worker::boot::runtime": "train::worker_group::spawn",
    "worker::boot::peer_server": "train::worker_group::spawn",
    "worker::boot::ready": "train::worker_group::spawn",
    "train::backend::on_start": "train::executor::start",
    "train::backend::import_jax": "train::backend::on_start",
    "train::backend::device_open": "train::backend::on_start",
    "train::executor::run_training": "train::fit",
    "train::worker::run_train_fn": "train::executor::run_training",
    "jax::compile": "train::worker::run_train_fn",
    "train::executor::shutdown": "train::fit",
    "runtime::init": None,
    "runtime::shutdown": None,
    "runtime::shutdown::workers_exit": "runtime::shutdown",
}


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_fit_with_tracing_off_records_every_lifecycle_span_under_its_parent(fit_record, name):
    """Each span of the contract that applies on the CPU with one worker is
    there, parented as the contract says (the worker's to the driver's
    across the process hop) and inside its parent's interval."""
    result, record, _ = fit_record
    spans = _by_name(record)
    assert name in spans, sorted(spans)
    by_id = {s["span_id"]: s for s in record["spans"] + record["runtime_spans"]}
    for s in spans[name]:
        parent = by_id.get(s["parent_span_id"])
        assert (parent["name"] if parent else None) == PARENTS[name]
        assert s["end"] >= s["start"]
        if parent is None:
            continue
        if name.startswith("worker::boot::") and s["end"] <= parent["start"]:
            continue  # a worker from the warm pool booted before it was asked for
        assert parent["start"] - SLACK_S <= s["start"] and s["end"] <= parent["end"] + SLACK_S, (s, parent)
        if name.startswith(("worker::", "train::backend::i", "train::backend::d", "train::worker::")):
            assert s["pid"] != parent["pid"] or parent["name"].startswith("train::worker::")


def test_the_record_has_one_trace_id_and_is_on_the_result_too(fit_record):
    result, record, _ = fit_record
    assert {s["trace_id"] for s in record["spans"]} == {record["trace_id"]}
    assert len({s["trace_id"] for s in record["runtime_spans"]}) == 1
    names = {s["name"] for s in result.run_record["spans"]}
    assert {"train::fit", "train::executor::shutdown", "train::worker::run_train_fn"} <= names
    # `runtime::shutdown` ran after fit() returned: only the later reading has it
    assert "runtime::shutdown" not in {s["name"] for s in result.run_record["runtime_spans"]}
    assert "runtime::shutdown" in {s["name"] for s in record["runtime_spans"]}


def test_tracing_off_records_no_per_task_and_no_per_step_span(fit_record):
    _, record, at_head = fit_record
    for s in record["spans"] + at_head:
        assert not s["name"].startswith(("submit::", "run::", "train_step/")), s["name"]
    # the same lifecycle spans took the flush to the head, for `ray_tpu timeline`
    assert "train::backend::import_jax" in {s["name"] for s in at_head}


def test_import_jax_times_the_import_that_really_happens(fit_record):
    result, record, _ = fit_record
    imports = _by_name(record)["train::backend::import_jax"]
    assert [s["attrs"]["already_imported"] for s in imports] == [False]
    assert imports[0]["end"] - imports[0]["start"] > 0.2
    assert result.metrics_history[0]["jax_imported_by_on_start"] is True


def test_compiles_of_the_train_function_carry_fun_name_and_parent(fit_record):
    _, record, _ = fit_record
    compiles = [s for s in record["spans"] if s["name"] == "jax::compile"]
    assert any("lambda" in s["attrs"]["fun_name"] for s in compiles)


def test_report_delivery_counts_every_report_once_across_poll_boundaries(fit_record):
    result, record, _ = fit_record
    reports = record["reports"]
    assert reports["count"] == len(result.metrics_history) == 7
    assert reports["polls"] >= 2 and 0.0 <= reports["median_s"] <= reports["max_s"] < 5.0
    assert all("t" not in m for m in result.metrics_history)  # beside the payload, not in it


def test_record_add_poll_counts_reports_split_over_two_polls():
    record = run_record.RunRecord({"trace_id": "t", "span_id": "s"})
    now = time.time()
    record.add_poll(0, {"reports": [{"metrics": {}, "t": now - 0.03}], "done": False,
                        "spans": [{"span_id": "a", "name": "x", "start": 1.0, "end": 2.0, "trace_id": "t"}]})
    record.add_poll(0, {"reports": [{"metrics": {}, "t": now - 0.01}, {"metrics": {}, "t": now - 0.02}],
                        "done": True, "spans": [{"span_id": "a", "name": "x", "start": 1.0, "end": 2.0,
                                                 "trace_id": "t"}], "stalls": [{"step": 3}]})
    got = record.to_dict()
    assert got["reports"]["count"] == 3 and got["reports"]["polls"] == 2
    assert [s["span_id"] for s in got["spans"]] == ["a"]  # the same span twice is one span
    assert got["stalls"] == [{"step": 3, "rank": 0}]


# -- chip_wait --------------------------------------------------------------------


def test_chip_wait_outwaits_a_busy_group_file(tmp_path):
    from ray_tpu.train.backend import _wait_for_chips

    group = tmp_path / "0"
    group.write_text("")
    calls = []

    def opener(path, flags):
        calls.append(path)
        if len(calls) <= 2:
            raise OSError(errno.EBUSY, "Device or resource busy")
        return os.open(path, flags)

    waited = _wait_for_chips(timeout_s=5.0, pattern=str(tmp_path / "[0-9]*"), opener=opener)
    assert calls == [str(group)] * 3 and 0.4 <= waited < 3.0
    assert _wait_for_chips(timeout_s=5.0, pattern=str(tmp_path / "[0-9]*")) < 0.2  # free: no wait


def test_backend_start_on_tpu_records_chip_wait_with_its_seconds(monkeypatch):
    from ray_tpu.train import backend

    monkeypatch.setattr(backend, "_wait_for_chips", lambda: 1.25)
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)  # keep this process on the CPU
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    before = len(tracing.lifecycle_spans())
    backend._init_jax_distributed("", 1, 0, "tpu")
    os.environ["JAX_PLATFORMS"] = "cpu"
    spans = tracing.lifecycle_spans()[before:]
    assert [s["name"] for s in spans] == ["train::backend::import_jax", "train::backend::chip_wait",
                                          "train::backend::device_open"]
    assert spans[1]["attrs"] == {"waited_s": 1.25}
    assert run_record.counters()["chip_wait"].snapshot()[()] >= 1.25


# -- compile events ------------------------------------------------------------------


def test_two_shapes_give_two_compile_events_with_miss_then_hit_and_a_repeat_gives_none(tmp_path):
    """In a process of its own: the persistent cache directory is a process-wide setting."""
    code = f"""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu.train import run_record
from ray_tpu.util import tracing
assert run_record.install_jax_listener() and run_record.install_jax_listener()

def build():
    def step_fn(x):
        return jnp.tanh(x) @ x
    return jax.jit(step_fn)

def events():
    run_record.flush_traces()
    return [(s["name"], s["attrs"]) for s in tracing.lifecycle_spans() if s["name"].startswith("jax::")]

with tracing.span("outer", lifecycle=True) as ctx:
    f = build()
    f(np.ones((8, 8), np.float32)); f(np.ones((16, 16), np.float32))
first = events()
compiles = [a for n, a in first if n == "jax::compile"]
assert [a["fun_name"] for a in compiles] == ["jit(step_fn)"] * 2, first
assert [a["cache"] for a in compiles] == ["miss", "miss"], first
assert [n for n, _ in first].count("jax::lower") == 2
assert all(s["parent_span_id"] == ctx["span_id"] for s in tracing.lifecycle_spans() if s["name"] == "jax::compile")
f(np.ones((8, 8), np.float32)); f(np.ones((16, 16), np.float32))
assert events() == first, "a repeated call compiled"
g = build()  # the same program again: the persistent cache has it
g(np.ones((8, 8), np.float32))
hit = [a for n, a in events() if n == "jax::compile"][-1]
assert hit["cache"] == "hit" and hit["retrieval_s"] > 0, hit
assert sorted(run_record.counters()["compiles"].snapshot().items()) == [((("cache", "hit"),), 1.0), ((("cache", "miss"),), 2.0)]
assert tracing.drain_spans() and not tracing.is_enabled()
"""
    # (with the metadata in the key, `f` and `g`, built on two lines, are two programs)
    env = {k: v for k, v in os.environ.items() if k not in (
        "RAY_TPU_TRACE", "JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY")}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]


def test_nested_traces_are_swallowed_by_the_trace_that_encloses_them():
    run_record.flush_traces()
    before = len(tracing.lifecycle_spans())
    for start, end, name in [(10.0, 10.2, "inner_a"), (10.3, 10.4, "inner_b"), (9.0, 11.0, "outer"),
                             (12.0, 12.0001, "too_short"), (13.0, 13.5, "next")]:
        run_record._on_time_span(run_record._TRACE, start, end, fun_name=name)
    run_record._on_time_span(run_record._LOWER, 14.0, 14.1, fun_name="jit(next)")
    got = [(s["name"], s["attrs"]["fun_name"]) for s in tracing.lifecycle_spans()[before:]]
    assert got == [("jax::trace", "outer"), ("jax::trace", "next"), ("jax::lower", "jit(next)")]


# -- stalled steps -------------------------------------------------------------------


CFG = TransformerConfig.tiny(n_heads=2, n_kv_heads=1, d_model=64, d_ff=64, max_seq_len=32, vocab_size=128)


@pytest.fixture(scope="module")
def toy():
    ctx = LMTrainContext(CFG, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = ctx.init_state(seed=0)
    toks = np.zeros((2, 32), np.int32)
    batch = ctx.make_batch({"tokens": toks, "targets": toks})
    state, _ = ctx.train_step(state, batch)  # compiled
    return ctx, state, batch


class FakeClock:
    """A clock moved by hand: `tracing._clock`, or `run_record._clock` and `_wall`."""

    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now

    def tick(self, seconds):
        self.now += seconds


@pytest.fixture
def step_time(monkeypatch):
    """`StepClock`'s period and wall clocks as one hand-moved clock; the
    process's rows, stall events and stepping clock start empty and are left so."""
    fake = FakeClock()
    monkeypatch.setattr(run_record, "_clock", fake)
    monkeypatch.setattr(run_record, "_wall", fake)
    monkeypatch.setattr(run_record, "_stepping", None)
    run_record.drain_step_rows(), run_record.drain_stalls()
    yield fake
    run_record.drain_step_rows(), run_record.drain_stalls()


def _sleep(seconds):
    time.sleep(0.1 * seconds)  # off the CPU for as long as it lasts: its length is the hand-moved clock's


def _busy(seconds):
    end = time.thread_time() + seconds  # ON a CPU for `seconds`, however long a loaded host takes to give them
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("pause, off_cpu", [(_sleep, (99.0, 100.0)), (_busy, (0.0, 50.0))],
                         ids=["sleep_is_off_cpu", "busy_loop_is_on_cpu"])
def test_a_pause_between_two_steps_gives_one_stall_event_that_says_where_the_thread_was(step_time, pause, off_cpu):
    """The periods are the hand-moved clock's, so the index, the period, the
    one event and the counter are exact on any host; where the thread was
    is the REAL `time.thread_time` around a real pause."""
    clock = run_record.StepClock()
    stalls_before = run_record.counters()["stalls"].snapshot().get((), 0.0)
    for i in range(16):
        clock.enter()
        since = step_time.now
        step_time.tick(0.002)
        clock.mark(run_record.DISPATCH, since)
        step_time.tick(0.028)  # a steady step of 30 ms
        if i == 9:
            pause(0.2)
            step_time.tick(0.2)
    events = run_record.drain_stalls()
    assert run_record.counters()["stalls"].snapshot()[()] == stalls_before + 1
    assert len(events) == 1, events
    e = events[0]
    assert e["step"] == 9 and e["period_s"] == pytest.approx(0.23) and e["median_s"] == pytest.approx(0.03)
    assert e["end"] - e["start"] == pytest.approx(e["period_s"])
    assert off_cpu[0] <= e["off_cpu_pct"] <= off_cpu[1], e
    assert e["dispatch_s"] == pytest.approx(0.002) and e["make_batch_s"] == 0.0
    assert e["process_cpu_s"] >= e["thread_cpu_s"] - 0.01 and e["gc_collections"] >= 0
    rows = run_record.drain_step_rows()
    assert [r[0] for r in rows] == list(range(15)) and rows[9][2] == e["period_s"]  # the stalled step has its row too


# -- the steady step: a row per period -------------------------------------------------


def test_n_steps_leave_n_minus_one_rows_whose_slots_add_up_under_the_period(step_time, monkeypatch):
    cpu = FakeClock(0.0)
    monkeypatch.setattr(run_record, "_thread_cpu", cpu)
    clock = run_record.StepClock()
    for i in range(5):
        clock.enter()
        for slot, seconds in ((run_record.MAKE_BATCH, 0.001 * (i + 1)), (run_record.DISPATCH, 0.002)):
            since = step_time.now
            step_time.tick(seconds)
            clock.mark(slot, since)
        run_record.add_report_seconds(0.0005)
        cpu.tick(0.004)
        step_time.tick(0.1)
    rows = [dict(zip(run_record.ROW, r)) for r in run_record.drain_step_rows()]
    assert [r["step"] for r in rows] == [0, 1, 2, 3] and clock.steps == 4  # the last step's period is still open
    for i, r in enumerate(rows):
        assert r["make_batch_s"] == pytest.approx(0.001 * (i + 1)) and r["dispatch_s"] == pytest.approx(0.002)
        assert r["report_s"] == 0.0005 and r["thread_cpu_s"] == pytest.approx(0.004)
        assert r["period_s"] == pytest.approx(0.102 + 0.001 * (i + 1)) and r["start"] == pytest.approx(
            100.0 + sum(0.102 + 0.001 * (j + 1) for j in range(i)))
        assert r["make_batch_s"] + r["dispatch_s"] + r["report_s"] < r["period_s"]
    assert run_record.drain_step_rows() == []  # drained once


def test_report_lands_in_the_step_that_was_open_and_nowhere_without_one(step_time):
    from ray_tpu.train.session import TrainSession

    session = TrainSession(rank=0, world_size=1)
    session.report({"before": "any step"})  # no period is open: nothing to mark, nothing raised
    clock = run_record.StepClock()
    for i in range(4):
        clock.enter()
        step_time.tick(0.1)
        if i == 1:
            session.report({"i": i})
            session.report({"i": i, "again": True})
    report_s = [r[run_record.ROW.index("report_s")] for r in run_record.drain_step_rows()]
    assert report_s[0] == report_s[2] == 0.0 and 0.0 < report_s[1] < 0.1  # two real calls, both in step 1
    assert len(session.drain()) == 3


def test_train_step_keeps_a_row_a_step_with_the_tokens_of_its_batch(toy):
    """Through `LMTrainContext.train_step`, on the real clocks: what must hold on any host."""
    ctx, state, _ = toy
    ctx._step_clock = run_record.StepClock()
    run_record.drain_step_rows()
    toks = np.zeros((2, 32), np.int32)
    for _ in range(4):
        state, metrics = ctx.train_step(state, {"tokens": toks, "targets": toks})  # a host batch: `make_batch` runs
    jax.block_until_ready(metrics["loss"])
    toy[1].update(state)  # the step donates its state: hand the live one on
    rows = [dict(zip(run_record.ROW, r)) for r in run_record.drain_step_rows()]
    assert [r["step"] for r in rows] == [0, 1, 2] and ctx._step_clock.tokens_per_step == 64
    for r in rows:
        assert 0.0 < r["make_batch_s"] and 0.0 < r["dispatch_s"] and r["report_s"] == 0.0
        assert r["make_batch_s"] + r["dispatch_s"] <= r["period_s"] and 0.0 <= r["thread_cpu_s"]
    assert run_record.set_step_gauges(rank=3) == 64  # what the worker's `poll` does
    gauges = run_record.counters()
    median = sorted(r["period_s"] for r in rows)[1]
    assert gauges["step_seconds"].snapshot()[(("rank", "3"),)] == median
    assert gauges["tokens_per_second"].snapshot()[(("rank", "3"),)] == pytest.approx(64 / median)
    from ray_tpu.util import metrics

    assert {"train_step_seconds", "train_tokens_per_second"} <= set(metrics.collect())  # what `ray_tpu metrics` shows


def test_rows_cross_two_polls_once_each_and_the_summary_reads_a_known_series():
    record = run_record.RunRecord({"trace_id": "t", "span_id": "s"})
    assert record.to_dict()["steps"] == {"tokens_per_step": None, "rows": [], "summary": {"count": 0}}
    periods = [0.5, 0.1, 0.2, 0.3, 0.4, 0.6, 0.7, 0.8, 0.9, 1.0, 5.0]
    rows = [[i, 10.0 + i, p, 0.001 * i, 0.002, 0.003, 0.5 * p] for i, p in enumerate(periods)]
    record.add_poll(0, {"reports": [], "step_rows": rows[:4], "tokens_per_step": 1200})
    record.add_poll(1, {"reports": [], "step_rows": rows[4:], "tokens_per_step": None})  # a poll before any step
    record.add_poll(0, {"reports": []})  # a parent's worker: no rows in its reply
    steps = record.to_dict()["steps"]
    assert [r["step"] for r in steps["rows"]] == list(range(11))
    assert [r["rank"] for r in steps["rows"]] == [0] * 4 + [1] * 7
    assert steps["rows"][5] == {"step": 5, "start": 15.0, "period_s": 0.6, "make_batch_s": 0.005,
                                "dispatch_s": 0.002, "report_s": 0.003, "thread_cpu_s": 0.3, "rank": 1}
    assert steps["tokens_per_step"] == 1200
    assert steps["summary"] == {
        "count": 11, "period_s": {"median": 0.6, "p10": 0.2, "p90": 1.0, "max": 5.0},
        "make_batch_s": 0.005, "dispatch_s": 0.002, "report_s": 0.003, "thread_cpu_s": 0.3,
        "tokens_per_s": pytest.approx(2000.0),
        "thread_cpu_share": pytest.approx(0.5)}  # of the ten rows of at most two medians: the 5 s one is set apart
    record.add_poll(0, {"reports": [], "step_rows": [[11 + i, 0.0, 0.1, 0.0, 0.0, 0.0, 0.0]
                                                      for i in range(run_record.SERIES_KEPT)]})
    assert len(record.step_rows) == run_record.SERIES_KEPT and record.step_rows[0][0] == 11  # the newest are kept


def test_fit_with_tracing_off_has_the_steps_in_its_record(fit_record):
    result, record, _ = fit_record
    assert result.run_record["steps"]["summary"]["count"] == 5  # six entries close five periods
    steps = record["steps"]
    assert [(r["step"], r["rank"]) for r in steps["rows"]] == [(i, 0) for i in range(5)]
    assert steps["tokens_per_step"] == 16
    run_fn = _by_name(record)["train::worker::run_train_fn"][0]
    for r in steps["rows"]:
        assert 0.0 < r["report_s"] < r["period_s"] and r["make_batch_s"] == r["dispatch_s"] == 0.0
        assert run_fn["start"] - SLACK_S <= r["start"] <= run_fn["end"] + SLACK_S  # on the spans' clock
    summary = steps["summary"]
    assert summary["period_s"]["p10"] <= summary["period_s"]["median"] <= summary["period_s"]["p90"]
    assert summary["tokens_per_s"] == pytest.approx(16 / summary["period_s"]["median"])


def test_five_thousand_steps_leave_the_ring_bounded_no_step_span_and_cost_microseconds(monkeypatch):
    assert not tracing.is_enabled()
    ctx = LMTrainContext.__new__(LMTrainContext)  # the step's host side alone: no program behind it
    ctx._step_clock = clock = run_record.StepClock()
    monkeypatch.setattr(clock, "RING", 1024)
    ctx.mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    ctx._train_step = lambda state, batch: (state, {})
    batch = {"tokens": jnp.zeros((1, 1), jnp.int32)}
    tracing.drain_spans()
    run_record.drain_step_rows()

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            ctx.train_step(None, batch)
        return (time.perf_counter() - t0) / n

    with_clock = min(run(500) for _ in range(10))  # the least of ten short bursts: a loaded host disturbs few of them
    assert clock.steps == 4999 and len(clock._ring) == len(clock._sorted) == 1024
    assert clock._sorted == sorted(clock._ring)
    rows = run_record.drain_step_rows()  # no poll drained them: the newest SERIES_KEPT are left
    assert len(rows) == run_record.SERIES_KEPT and [rows[0][0], rows[-1][0]] == [4999 - run_record.SERIES_KEPT, 4998]
    ctx._step_clock = type("NoClock", (), {"enter": lambda s: None, "mark": lambda s, a, b: None,
                                           "batch_shape": (1, 1)})()
    without = min(run(500) for _ in range(10))
    assert with_clock - without < 20e-6, (with_clock, without)
    assert [s for s in tracing.drain_spans() if s["name"].startswith("train_step/")] == []
    run_record.drain_stalls()


# -- scopes: the table of `tracing.scope` and the span it lands on ---------------------


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(tracing, "_clock", fake)
    monkeypatch.setattr(tracing._scopes, "state", tracing._ScopeState(), raising=False)
    run_record.flush_traces()
    yield fake
    tracing.drain_spans()  # spans at the fake clock's times: not for the next runtime's flush to its head


def _host(name, **kw):
    return tracing.scope(name, host_only=True, **kw)


def _nested(clock):
    with _host("a"):
        clock.tick(1.0)
        with _host("b"):
            clock.tick(2.0)
            with _host("c", kernel=True):
                clock.tick(4.0)
        clock.tick(8.0)
    return 15.0, {"a": [9.0, 1], "a/b": [2.0, 1], "a/b/c": [4.0, 1]}, ["c"]


def _siblings(clock):
    with _host("a"):
        with _host("b"):
            clock.tick(1.0)
        clock.tick(2.0)
        with _host("c"):
            clock.tick(4.0)
    return 7.0, {"a": [2.0, 1], "a/b": [1.0, 1], "a/c": [4.0, 1]}, []


def _re_entered(clock):
    with _host("a"):
        for seconds in (1.0, 2.0):
            with _host("layer/attn_proj"):
                clock.tick(seconds)
                with _host("k", kernel=True):
                    clock.tick(0.5)
    return 4.0, {"a": [0.0, 1], "a/layer/attn_proj": [3.0, 2], "a/layer/attn_proj/k": [1.0, 2]}, ["k"]


def _raises_inside(clock):
    with pytest.raises(ValueError):
        with _host("a"):
            clock.tick(1.0)
            with _host("b"):
                clock.tick(2.0)
                raise ValueError("inside b")
    assert tracing._scopes.state.stack == []  # the exception closed both
    return 3.0, {"a": [1.0, 1], "a/b": [2.0, 1]}, []


@pytest.mark.parametrize("blocks", [_nested, _siblings, _re_entered, _raises_inside])
def test_scope_table_self_seconds_sum_to_the_outer_block_on_a_fake_clock(clock, blocks):
    start = clock()
    total, table, kernels = blocks(clock)
    got = tracing.take_scopes(start, clock())
    assert got == (table, kernels)
    assert sum(row[0] for row in got[0].values()) == total == clock() - start
    assert tracing.take_scopes(start, clock()) is None  # taken: the table is empty


def test_scope_without_jax_accounts_and_opens_nothing():
    code = (
        "import sys, time\n"
        "from ray_tpu.util import tracing\n"
        "t0 = time.time()\n"
        "with tracing.scope('layers'):\n"
        "    with tracing.scope('moe_gmm', kernel=True):\n"
        "        time.sleep(0.01)\n"
        "table, kernels = tracing.take_scopes(t0, time.time())\n"
        "assert 'jax' not in sys.modules\n"
        "assert sorted(table) == ['layers', 'layers/moe_gmm'] and kernels == ['moe_gmm'], table\n"
        "assert table['layers/moe_gmm'][0] >= 0.01 and table['layers'][1] == 1, table\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=dict(os.environ), capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]


def _trace_spans_since(before):
    run_record.flush_traces()
    return [s for s in tracing.lifecycle_spans()[before:] if s["name"] == "jax::trace"]


def test_an_outermost_trace_span_carries_the_table_and_a_nested_trace_adds_to_it(clock):
    """jax reports the nested trace first, as it ends; it leaves no span and
    what its body spent under scopes is in the outer span's table."""
    before = len(tracing.lifecycle_spans())
    with _host("eager"):  # before any trace began: no span's
        clock.tick(0.5)
    start = clock()
    clock.tick(0.25)  # under no scope
    with _host("autodiff"):
        clock.tick(1.0)
        inner = clock()
        with _host("layers"):
            clock.tick(2.0)
        run_record._on_time_span(run_record._TRACE, inner, clock(), fun_name="_rung_forward")
        with _host("kda_fwd", kernel=True):
            clock.tick(0.5)
    with _host("optimizer"):
        clock.tick(0.125)
    run_record._on_time_span(run_record._TRACE, start, clock(), fun_name="_train_step")
    (span,) = _trace_spans_since(before)
    attrs = span["attrs"]
    assert attrs["fun_name"] == "_train_step" and attrs["kernels"] == ["kda_fwd"]
    assert attrs["scopes"] == {"autodiff": [1.0, 1], "autodiff/layers": [2.0, 1], "autodiff/kda_fwd": [0.5, 1],
                               "optimizer": [0.125, 1]}
    assert attrs["unscoped_s"] == 0.25
    assert sum(row[0] for row in attrs["scopes"].values()) + attrs["unscoped_s"] == span["end"] - span["start"]


def test_a_real_traces_span_adds_up_to_its_duration_and_leaves_other_threads_seconds_out():
    import threading

    assert run_record.install_jax_listener()
    before = len(tracing.lifecycle_spans())

    def elsewhere():
        with _host("other_thread"):
            time.sleep(0.002)

    @jax.jit
    def inner(x):
        with tracing.scope("layer/mlp"):
            time.sleep(0.004)
            return jnp.tanh(x)

    def outer(x):
        with tracing.scope("layers"):
            time.sleep(0.002)
            other = threading.Thread(target=elsewhere)
            other.start()
            other.join(timeout=10)
            x = inner(x)
        time.sleep(0.003)
        return x

    with _host("before_the_trace"):
        time.sleep(0.002)
    jax.jit(outer).trace(jnp.ones((4,)))
    (span,) = [s for s in _trace_spans_since(before) if s["attrs"]["fun_name"] == "outer"]
    attrs = span["attrs"]
    assert sorted(attrs["scopes"]) == ["layers", "layers/layer/mlp"] and attrs["kernels"] == []
    assert attrs["scopes"]["layers/layer/mlp"][0] >= 0.004 and attrs["scopes"]["layers"][0] >= 0.002
    assert attrs["unscoped_s"] >= 0.003
    total = sum(row[0] for row in attrs["scopes"].values()) + attrs["unscoped_s"]
    assert total == pytest.approx(span["end"] - span["start"], abs=1e-3)


def test_a_trace_with_an_empty_table_has_none_of_the_three_keys(clock):
    before = len(tracing.lifecycle_spans())
    run_record._on_time_span(run_record._TRACE, clock(), clock() + 0.5, fun_name="no_scopes")
    (span,) = _trace_spans_since(before)
    assert span["attrs"] == {"fun_name": "no_scopes"}
