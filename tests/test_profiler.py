"""Profiling + task-lifecycle-attribution plane tests (ISSUE 10).

Reference intents: ray's dashboard py-spy stack sampling (`ray stack` /
CPU flame graph) and the GcsTaskManager per-task state-transition records
(test_task_events.py) — here as the in-process sampler (profiler.py), the
prof_push → ProfileSink merge, and the task_events ring upgraded into a
per-stage state machine with `task_stage_seconds` histograms.
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu._private import config as _config
from ray_tpu._private import profiler
from ray_tpu.util import state as state_api


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()
    profiler._reset_for_tests()
    _config._reset_for_tests()


# ---------------------------------------------------------------------------
# sampler core (pure / single-process)


def test_profiler_off_by_default_zero_state():
    """OFF is the default and means NO sampler thread and ENABLED False —
    the faults.ENABLED zero-overhead discipline."""
    profiler._reset_for_tests()
    assert profiler.ENABLED is False
    assert not profiler.running()
    # maybe_autostart with the default knob (0) stays off.
    profiler.maybe_autostart()
    assert not profiler.running()


def test_sampler_catches_hot_function_and_stops():
    profiler._reset_for_tests()
    eff = profiler.start(250)
    assert eff == 250 and profiler.running() and profiler.ENABLED

    def _burn_cycles_for_profile():
        t0 = time.time()
        while time.time() - t0 < 0.4:
            sum(range(500))

    _burn_cycles_for_profile()
    profiler.stop()
    assert not profiler.running() and profiler.ENABLED is False
    snap = profiler.snapshot_payload()
    assert snap["n"] >= 20, f"only {snap['n']} samples at 250Hz over 0.4s"
    assert any(
        "_burn_cycles_for_profile" in s for s in snap["samples"]
    ), list(snap["samples"])[:5]
    # Collapsed form: thread name prefix + root-first module:func frames.
    stack = next(s for s in snap["samples"] if "_burn_cycles" in s)
    assert stack.split(";")[0] == "MainThread"
    profiler._reset_for_tests()


def test_merge_and_flamegraph_render():
    a = {"main;mod:f;mod:g": 10, "main;mod:f": 5}
    b = {"main;mod:f;mod:g": 3, "main;mod:h": 2}
    merged = profiler.merge_samples([a, b])
    assert merged["main;mod:f;mod:g"] == 13
    txt = profiler.folded_text(merged)
    assert txt.splitlines()[0] == "main;mod:f;mod:g 13"
    svg = profiler.flamegraph_svg(merged)
    assert svg.startswith("<svg") and "rect" in svg and "mod:g" in svg
    # escaping: hostile frame names must not break the document
    svg2 = profiler.flamegraph_svg({'t;<mod>:"fn"': 1})
    assert "<mod>" not in svg2 and "&lt;mod&gt;" in svg2


def test_profile_sink_cumulative_latest_wins_and_filters():
    sink = profiler.ProfileSink()
    sink.ingest("w1", {"pid": 11, "n": 5, "samples": {"s;a": 5}}, node="n1")
    # Later cumulative push replaces (not adds to) the sender's table.
    sink.ingest("w1", {"pid": 11, "n": 9, "samples": {"s;a": 9}}, node="n1")
    sink.ingest("w2", {"pid": 22, "n": 4, "samples": {"s;a": 1, "s;b": 3}},
                node="n2")
    rep = sink.merged()
    assert rep["samples"] == {"s;a": 10, "s;b": 3}
    assert rep["pids"] == [11, 22]
    only_n2 = sink.merged(node="n2")
    assert only_n2["samples"] == {"s;a": 1, "s;b": 3}
    only_pid = sink.merged(pid=11)
    assert only_pid["samples"] == {"s;a": 9}
    sink.forget("w1")
    assert sink.merged()["pids"] == [22]


# ---------------------------------------------------------------------------
# stage attribution (pure)


def test_stage_durations_telescope_and_clamp():
    from ray_tpu._private.telemetry import (
        stage_durations,
        stage_wall_seconds,
    )

    stages = {
        "submit": 100.0, "queued": 100.1, "leased": 100.15,
        "pushed": 100.2, "received": 100.21, "running": 100.22,
        "exec_done": 100.72, "done": 100.75, "sealed": 100.76,
    }
    durs = stage_durations(stages)
    assert durs["pending"] == pytest.approx(0.1)
    assert durs["running"] == pytest.approx(0.5)
    # Telescoping: the durations sum to the stamped wall time exactly.
    assert sum(durs.values()) == pytest.approx(stage_wall_seconds(stages))
    # Missing stamps skip cleanly (partial records from direct tasks).
    partial = stage_durations({"received": 1.0, "running": 1.2, "exec_done": 1.5})
    assert partial == {"exec_queue": pytest.approx(0.2),
                       "running": pytest.approx(0.3)}
    # Clock-offset disorder clamps to zero instead of going negative.
    skewed = stage_durations({"pushed": 10.0, "received": 9.9, "running": 10.1})
    assert skewed["wire"] == 0.0


def test_summarize_task_events_slow_and_fraction():
    from ray_tpu._private.telemetry import summarize_task_events

    events = [
        {
            "task_id": f"t{i}", "name": "f", "state": "FINISHED",
            "stages": {"submit": 0.0, "running": 0.01, "done": 0.01 + d},
            "durations": {"pending": 0.01, "running": d},
        }
        for i, d in enumerate([0.1, 0.5, 0.2])
    ]
    out = summarize_task_events(events, slow=2)
    assert out["tasks"] == 3
    assert out["slow"][0]["wall_s"] == pytest.approx(0.51)
    assert out["slow"][0]["critical_stage"] == "running"
    assert out["accounted_fraction"] == pytest.approx(1.0, abs=0.01)
    assert out["stages"]["running"]["count"] == 3


# ---------------------------------------------------------------------------
# cluster integration


def test_task_events_carry_stage_durations(rt):
    """Every finished task's ring entry is a stage-attributed record, and
    the durations account for >=95% of its stamped wall time (the
    acceptance property, on the live runtime)."""

    @ray_tpu.remote
    def f(x):
        time.sleep(0.05)
        return x + 1

    assert ray_tpu.get([f.remote(i) for i in range(6)], timeout=60) == list(
        range(1, 7)
    )
    summary = state_api.task_summary(slow=10)
    assert summary["tasks"] >= 6
    assert summary["accounted_fraction"] is not None
    assert summary["accounted_fraction"] >= 0.95, summary
    row = summary["slow"][0]
    assert row["durations"].get("running", 0) > 0.02, row
    assert row["critical_stage"] is not None
    # The histogram family exists in this process's registry.
    from ray_tpu.util.metrics import collect

    reg = collect()
    assert "task_stage_seconds" in reg
    assert any(reg["task_stage_seconds"]["data"]), "no stage observations"


def test_cluster_profile_start_stop_merges_multiple_pids(rt):
    """profile_start broadcasts to workers; the merged report spans the
    head + worker pids with their pushed collapsed stacks."""

    @ray_tpu.remote
    def spin(sec):
        t0 = time.time()
        while time.time() - t0 < sec:
            sum(range(200))
        return 1

    # Warm the pool first: on a slow host a cold worker's boot can outlive
    # the whole profile window (nothing anywhere would sample the spin),
    # and the ticker needs a beat to subscribe to the profiler channel.
    assert ray_tpu.get(
        [spin.remote(0.1) for _ in range(3)], timeout=60
    ) == [1, 1, 1]
    time.sleep(1.2)
    state_api.profile_start(hz=120)
    refs = [spin.remote(1.5) for _ in range(3)]
    time.sleep(1.6)
    state_api.profile_stop()
    assert ray_tpu.get(refs, timeout=60) == [1, 1, 1]
    deadline = time.time() + 10
    rep = {}
    while time.time() < deadline:
        rep = state_api.profile_report()
        if len(rep.get("pids", [])) >= 2 and rep.get("total_samples", 0) > 0:
            break
        time.sleep(0.3)
    assert rep["total_samples"] > 0, rep
    assert len(rep["pids"]) >= 2, rep["pids"]
    # Worker time is attributable: some stack mentions the spin fn or the
    # executor loop.
    assert rep["samples"], "merged flamegraph is empty"
    # The local sampler is off again after the stop broadcast.
    assert not profiler.running()


def test_blocked_get_prints_critical_path(rt):
    @ray_tpu.remote
    def slow_producer():
        time.sleep(8)
        return 1

    r = slow_producer.remote()
    with pytest.raises(ray_tpu.exceptions.GetTimeoutError) as ei:
        ray_tpu.get(r, timeout=0.4)
    msg = str(ei.value)
    assert "critical path" in msg and "slow_producer" in msg, msg
    assert "stuck in stage" in msg, msg
    ray_tpu.cancel(r, force=True)


def test_prof_push_rides_ticker_when_autostarted(monkeypatch):
    """RAY_TPU_PROF_HZ>0 autostarts samplers everywhere (workers inherit
    the env at spawn); worker tables arrive via prof_push without any
    broadcast.  Env must be set BEFORE init — the prestart pool and the
    zygote capture their environment at boot."""
    monkeypatch.setenv("RAY_TPU_PROF_HZ", "100")
    _config._reset_for_tests()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def spin(sec):
            t0 = time.time()
            while time.time() - t0 < sec:
                sum(range(200))
            return 1

        assert ray_tpu.get(spin.remote(1.2), timeout=60) == 1
        deadline = time.time() + 10
        rep = {}
        while time.time() < deadline:
            rep = state_api.profile_report()
            if rep.get("total_samples", 0) > 0:
                break
            time.sleep(0.4)  # ticker beats: the prof_push lands
        # At least one process's table landed (the head autostarts too;
        # workers definitely sample the spin).
        assert rep["total_samples"] > 0, rep
        assert rep["processes"], rep
    finally:
        ray_tpu.shutdown()
        profiler._reset_for_tests()
        _config._reset_for_tests()


# ---------------------------------------------------------------------------
# timeline windowing (satellite)


def test_window_chrome_events_pure():
    from ray_tpu.util.tracing import window_chrome_events

    now = 1000.0
    ev = lambda t, dur=0: {"name": "x", "ts": int(t * 1e6), "dur": dur}
    events = [ev(100), ev(990), ev(999), {"name": "no-ts"}]
    assert window_chrome_events(events) == events  # no window = identity
    out = window_chrome_events(events, last=15, now=now)
    assert [e.get("ts") for e in out] == [int(990e6), int(999e6), None]
    out = window_chrome_events(events, since=995, now=now)
    assert [e.get("ts") for e in out] == [int(999e6), None]
    # An event STRADDLING the cutoff is kept (its tail is in-window).
    straddle = ev(100, dur=int(900e6))
    assert window_chrome_events([straddle], last=15, now=now) == [straddle]


def test_timeline_last_window_bounds_export(rt):
    from ray_tpu.dashboard import timeline

    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get(f.remote(), timeout=30) == 1
    # Other threads append to the buffer between two exports (a `jax::trace` or task event: the driver's run at
    # PR 58 read `assert 1002 == 1001`), so a later export is held to CONTAIN an earlier one, never to equal it.
    row = lambda e: (e.get("name"), e.get("ph"), e.get("ts"), e.get("pid"), e.get("tid"))
    full = timeline()
    assert full, "no timeline events at all"
    timed = {row(e) for e in full if isinstance(e.get("ts"), (int, float))}
    assert timed, "no event with a clock: nothing for a window to drop"
    # Everything just happened: a wide trailing window keeps it...
    recent = timeline(last=3600)  # wide: the module's own earlier tests may be minutes old on a loaded machine
    assert {row(e) for e in full} <= {row(e) for e in recent}
    # ...a window in the past drops the task rows.
    none = timeline(since=time.time() + 3600)
    assert not timed & {row(e) for e in none}
    assert all("ts" not in e or e["ts"] >= (time.time() + 3500) * 1e6
               for e in none)


# ---------------------------------------------------------------------------
# serve request tracing (satellite): one parented span tree per request


def test_serve_request_renders_single_span_tree(monkeypatch):
    import urllib.request

    from ray_tpu.util import tracing

    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    tracing.enable_tracing()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        from ray_tpu import serve

        serve.start(http_options={"host": "127.0.0.1", "port": 0})

        @serve.deployment
        def traced_app(body=None):
            return {"ok": True}

        serve.run(traced_app.bind(), name="traced_app")
        addr = serve.get_http_address()
        resp = urllib.request.urlopen(f"{addr}/traced_app", timeout=30)
        rid = resp.headers.get("X-Request-Id")
        assert resp.status == 200
        assert rid, "X-Request-Id header missing"

        from ray_tpu.util.state import list_spans

        # The three spans close in three processes (replica, router's
        # host, proxy), each shipped on its own telemetry tick: wait for
        # all of them, not for the first to land.
        wanted = {"serve::request", "serve::route", "serve::replica"}
        deadline = time.time() + 15
        tree, names = [], set()
        while time.time() < deadline:
            spans = list_spans(limit=5000)
            tree = [s for s in spans if s["trace_id"] == rid]
            names = {s["name"] for s in tree}
            if wanted <= names:
                break
            time.sleep(0.3)
        assert "serve::request" in names, names
        assert "serve::route" in names, names
        assert "serve::replica" in names, names
        # One PARENTED tree: walking up from the replica leaf reaches the
        # proxy's request root through the router span.
        by_id = {s["span_id"]: s for s in tree}
        cur = next(s for s in tree if s["name"] == "serve::replica")
        chain = [cur["name"]]
        while cur.get("parent_span_id") in by_id:
            cur = by_id[cur["parent_span_id"]]
            chain.append(cur["name"])
        assert chain[0] == "serve::replica" and chain[-1] == "serve::request", chain
        assert "serve::route" in chain, chain
        serve.shutdown()
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# CLI + dashboard surfaces


def test_tasks_cli_and_dashboard_endpoints(rt, capsys):
    from ray_tpu.scripts.cli import main as cli_main

    @ray_tpu.remote
    def f(x):
        return x

    ray_tpu.get([f.remote(i) for i in range(3)], timeout=30)
    assert cli_main(["tasks", "--slow", "3", "--summary"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["tasks"] >= 3 and "stages" in out

    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    import urllib.request

    dash = start_dashboard()
    try:
        body = json.loads(
            urllib.request.urlopen(
                f"{dash.url}/api/task_summary?slow=2", timeout=10
            ).read()
        )
        assert body["tasks"] >= 3
        prof = json.loads(
            urllib.request.urlopen(
                f"{dash.url}/api/profile?seconds=0.3", timeout=30
            ).read()
        )
        assert "samples" in prof and "processes" in prof
    finally:
        stop_dashboard()


def test_profile_cli_writes_flame_outputs(rt, tmp_path, capsys):
    from ray_tpu.scripts.cli import main as cli_main

    @ray_tpu.remote
    def spin(sec):
        t0 = time.time()
        while time.time() - t0 < sec:
            sum(range(100))
        return 1

    ref = spin.remote(1.2)
    out_txt = str(tmp_path / "flame.txt")
    assert cli_main(
        ["profile", "--seconds", "0.8", "--hz", "150", "--flame", out_txt]
    ) == 0
    ray_tpu.get(ref, timeout=60)
    report = json.loads(capsys.readouterr().out.split("wrote ", 1)[1].split("\n", 1)[1])
    assert report["total_samples"] > 0
    with open(out_txt) as f:
        folded = f.read()
    assert folded.strip(), "empty collapsed-stack output"
    # every line is `stack count`
    for line in folded.strip().splitlines():
        stack, n = line.rsplit(" ", 1)
        assert int(n) > 0 and ";" in stack or stack
