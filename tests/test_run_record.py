"""The run record (`ray_tpu/train/run_record.py`): one per `fit()`, kept with
`RAY_TPU_TRACE` unset — lifecycle spans under one trace id across the
driver/worker hop, compile events, stalled steps, report delivery."""

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

    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    for i in range(6):
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


def _busy(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("pause, off_cpu", [(time.sleep, (90.0, 100.0)), (_busy, (0.0, 50.0))],
                         ids=["sleep_is_off_cpu", "busy_loop_is_on_cpu"])
def test_a_pause_between_two_steps_gives_one_stall_event_that_says_where_the_thread_was(toy, pause, off_cpu):
    """(Bounds with room for a loaded host: a busy loop that is descheduled
    for a part of its time is off the CPU for that part, and says so.)"""
    ctx, state, batch = toy
    ctx._step_clock = run_record.StepClock()
    run_record.drain_stalls()
    stalls_before = run_record.counters()["stalls"].snapshot().get((), 0.0)
    for i in range(16):
        time.sleep(0.03)  # a steady step of 30 ms: the host's jitter stays under twice it
        if i == 10:
            pause(0.6)
        state, metrics = ctx.train_step(state, batch)
        jax.block_until_ready(metrics["loss"])
    toy[1].update(state)  # the step donates its state: hand the live one on
    events = run_record.drain_stalls()
    assert run_record.counters()["stalls"].snapshot()[()] == stalls_before + len(events)
    events = [e for e in events if e["period_s"] >= 0.3]
    assert len(events) == 1, events
    e = events[0]
    assert e["step"] == 9 and 0.6 <= e["period_s"] < 3.0 and e["period_s"] > 2 * e["median_s"]
    assert e["end"] - e["start"] == pytest.approx(e["period_s"], abs=0.05)
    assert off_cpu[0] <= e["off_cpu_pct"] <= off_cpu[1], e
    assert 0.0 < e["dispatch_s"] < e["period_s"] and e["make_batch_s"] == 0.0
    assert e["process_cpu_s"] >= e["thread_cpu_s"] - 0.01 and e["gc_collections"] >= 0


def test_five_thousand_steps_leave_the_ring_bounded_no_step_span_and_cost_microseconds(monkeypatch):
    assert not tracing.is_enabled()
    ctx = LMTrainContext.__new__(LMTrainContext)  # the step's host side alone: no program behind it
    ctx._step_clock = clock = run_record.StepClock()
    monkeypatch.setattr(clock, "RING", 1024)
    ctx.mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    ctx._train_step = lambda state, batch: (state, {})
    batch = {"tokens": jnp.zeros((1, 1), jnp.int32)}
    tracing.drain_spans()

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            ctx.train_step(None, batch)
        return (time.perf_counter() - t0) / n

    with_clock = min(run(2500), run(2500))
    assert clock.steps == 4999 and len(clock._ring) == len(clock._sorted) == 1024
    assert clock._sorted == sorted(clock._ring)
    ctx._step_clock = type("NoClock", (), {"enter": lambda s: None, "mark": lambda s, a, b: None})()
    without = min(run(2500), run(2500))
    assert with_clock - without < 20e-6, (with_clock, without)
    assert [s for s in tracing.drain_spans() if s["name"].startswith("train_step/")] == []
    run_record.drain_stalls()


# -- scopes: the table of `tracing.scope` and the span it lands on ---------------------


class FakeClock:
    """`tracing._clock`, moved by hand."""

    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now

    def tick(self, seconds):
        self.now += seconds


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
