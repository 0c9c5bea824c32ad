"""State API + metrics + ActorPool + Queue tests (reference intents:
python/ray/tests/test_state_api.py, test_metrics_agent.py,
test_actor_pool.py, test_queue.py).
"""

import time

import pytest

import ray_tpu
from ray_tpu.util import ActorPool, Empty, Full, Queue
from ray_tpu.util import state as state_api
from ray_tpu.util.metrics import Counter, Gauge, Histogram, collect


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_list_tasks_actors_objects_nodes(rt):
    @ray_tpu.remote
    def f(x):
        return x + 1

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    refs = [f.remote(i) for i in range(5)]
    a = A.remote()
    ray_tpu.get(refs + [a.ping.remote()], timeout=60)
    big = ray_tpu.put(b"x" * 500_000)

    tasks = state_api.list_tasks()
    assert any(t["name"].startswith("f") and t["state"] == "FINISHED" for t in tasks)

    actors = state_api.list_actors()
    assert any(x["state"] == "ALIVE" for x in actors)

    objs = state_api.list_objects()
    assert any(o["object_id"] == big.id and o["location"] == "shm" for o in objs)

    nodes = state_api.list_nodes()
    assert any(n["is_head"] and n["alive"] for n in nodes)

    workers = state_api.list_workers()
    assert any(w["state"] == "actor" for w in workers)

    summary = state_api.summarize_tasks()
    assert summary.get("FINISHED", 0) >= 5


def test_cluster_metrics_counters(rt):
    @ray_tpu.remote
    def ok():
        return 1

    @ray_tpu.remote
    def boom():
        raise ValueError("x")

    before = state_api.cluster_metrics()
    ray_tpu.get([ok.remote() for _ in range(3)], timeout=60)
    with pytest.raises(Exception):
        ray_tpu.get(boom.remote(), timeout=60)
    after = state_api.cluster_metrics()
    assert after["tasks_finished"] - before["tasks_finished"] >= 3
    assert after["tasks_failed"] - before["tasks_failed"] >= 1
    assert after["tasks_submitted"] >= after["tasks_finished"]
    assert after["object_store_capacity_bytes"] > 0


def test_metric_api():
    c = Counter("test_requests", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    snap = c.snapshot()
    assert snap[(("route", "/a"),)] == 3
    assert snap[(("route", "/b"),)] == 1
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        c.inc(tags={"nope": "x"})

    g = Gauge("test_depth")
    g.set(7)
    g.set(3)
    assert g.snapshot()[()] == 3

    h = Histogram("test_latency", boundaries=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0, 0.7):
        h.observe(v)
    data = h.snapshot()[()]
    assert data["count"] == 4
    assert data["buckets"] == [1, 2, 1]

    everything = collect()
    assert {"test_requests", "test_depth", "test_latency"} <= set(everything)


def test_actor_pool_ordered_and_unordered(rt):
    @ray_tpu.remote
    class Sq:
        def compute(self, x):
            time.sleep(0.01 * (x % 3))
            return x * x

    pool = ActorPool([Sq.remote() for _ in range(3)])
    got = list(pool.map(lambda a, v: a.compute.remote(v), range(8)))
    assert got == [x * x for x in range(8)]  # submission order

    got2 = sorted(pool.map_unordered(lambda a, v: a.compute.remote(v), range(8)))
    assert got2 == sorted(x * x for x in range(8))


def test_actor_pool_queues_past_capacity(rt):
    @ray_tpu.remote
    class W:
        def go(self, v):
            return v

    pool = ActorPool([W.remote()])
    for i in range(5):
        pool.submit(lambda a, v: a.go.remote(v), i)
    out = [pool.get_next(timeout=30) for _ in range(5)]
    assert out == list(range(5))
    assert not pool.has_next()


def test_queue_fifo_and_limits(rt):
    q = Queue(maxsize=3)
    q.put(1)
    q.put(2)
    q.put(3)
    assert q.qsize() == 3 and q.full()
    with pytest.raises(Full):
        q.put_nowait(4)
    assert [q.get(timeout=10) for _ in range(3)] == [1, 2, 3]
    assert q.empty()
    with pytest.raises(Empty):
        q.get_nowait()

    q.put_nowait_batch([7, 8])
    assert q.get_nowait_batch(2) == [7, 8]
    q.shutdown()


def test_queue_cross_actor(rt):
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return n

    @ray_tpu.remote
    def consumer(q, n):
        return [q.get(timeout=30) for _ in range(n)]

    p = producer.remote(q, 5)
    c = consumer.remote(q, 5)
    assert ray_tpu.get(p, timeout=60) == 5
    assert ray_tpu.get(c, timeout=60) == [0, 1, 2, 3, 4]


def test_config_knob_table():
    """§5.6 config system: defaults, env override, _system_config override
    (ray: ray_config_def.h RAY_CONFIG table semantics)."""
    import os

    from ray_tpu._private import config

    config._reset_for_tests()
    try:
        assert config.get("scheduler_spread_threshold") == 0.5
        with pytest.raises(KeyError):
            config.get("no_such_knob")

        config._reset_for_tests()
        os.environ["RAY_TPU_SCHEDULER_SPREAD_THRESHOLD"] = "0.9"
        assert config.get("scheduler_spread_threshold") == 0.9

        # programmatic beats env
        config._reset_for_tests()
        config.set_system_config({"scheduler_spread_threshold": 0.25})
        assert config.get("scheduler_spread_threshold") == 0.25

        # malformed env falls back to default
        config._reset_for_tests()
        os.environ["RAY_TPU_SCHEDULER_SPREAD_THRESHOLD"] = "not-a-float"
        assert config.get("scheduler_spread_threshold") == 0.5

        desc = config.describe()
        assert "object_store_memory" in desc
        assert all("doc" in row for row in desc.values())
    finally:
        os.environ.pop("RAY_TPU_SCHEDULER_SPREAD_THRESHOLD", None)
        config._reset_for_tests()


@pytest.mark.parametrize(
    "key", ["bogus", "head_io_shards", "io_shard_restart_s"]
)
def test_unknown_system_config_key_is_refused(key):
    """A key that is not in the knob table is an error, never silently
    ignored: a misspelt name, or one that was retired with its code."""
    from ray_tpu._private import config

    config._reset_for_tests()
    try:
        with pytest.raises(ValueError, match="unknown config"):
            config.set_system_config({key: 1})
    finally:
        config._reset_for_tests()


def test_task_parentage_tracing(rt):
    """§5.1 tracing: tasks submitted INSIDE a task record their parent —
    the context propagation the reference injects into task specs
    (tracing_helper.py:160)."""

    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent():
        return ray_tpu.get([child.remote(i) for i in range(2)], timeout=30)

    assert ray_tpu.get(parent.remote(), timeout=60) == [1, 2]
    # Direct (peer-executed) tasks report state in BATCHES off the latency
    # path (ray: task_event_buffer.h flushes on an interval too), so the
    # state API is eventually consistent: poll briefly.
    deadline = time.time() + 5
    parents = children = []
    while time.time() < deadline:
        events = {e["task_id"]: e for e in state_api.list_tasks()}
        parents = [e for e in events.values() if e["name"] == "parent"]
        children = [e for e in events.values() if e["name"] == "child"]
        if len(parents) == 1 and len(children) == 2:
            break
        time.sleep(0.2)
    assert len(parents) == 1 and len(children) == 2
    assert parents[0].get("parent_task_id") is None  # driver submit
    for c in children:
        assert c["parent_task_id"] == parents[0]["task_id"]


def test_prometheus_endpoint(rt):
    """/metrics serves the Prometheus text exposition format with user
    metrics + runtime gauges (ray: metrics_agent.py:375 export path)."""
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    from ray_tpu.util.metrics import Counter, Gauge, Histogram

    c = Counter("prom_requests", "reqs", tag_keys=("route",))
    c.inc(3, tags={"route": "/a"})
    g = Gauge("prom_inflight", "inflight")
    g.set(7)
    h = Histogram("prom_latency", "lat", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote(), timeout=30)

    dash = start_dashboard()
    try:
        body = urllib.request.urlopen(f"{dash.url}/metrics", timeout=10).read().decode()
    finally:
        stop_dashboard()
    assert '# TYPE prom_requests_total counter' in body
    assert 'prom_requests_total{route="/a"} 3.0' in body
    assert "prom_inflight 7.0" in body
    assert 'prom_latency_bucket{le="0.1"} 1' in body
    assert 'prom_latency_bucket{le="+Inf"} 3' in body
    assert "prom_latency_count 3" in body
    # Runtime gauges ride along.
    assert "ray_tpu_tasks_finished" in body
    assert "ray_tpu_object_store_capacity_bytes" in body


def test_live_ref_table_counts_and_sites():
    """refs.py live-ref table: constructions count up, GC'd refs count
    down (drained off __del__ queues), creation sites captured under the
    knob — the worker leg of the object ledger."""
    import gc
    import os

    from ray_tpu._private import config as _config
    from ray_tpu._private import refs as refs_mod

    os.environ["RAY_TPU_REF_CALLSITE"] = "1"
    _config._reset_for_tests()
    refs_mod._reset_table_for_tests()
    try:
        r1 = refs_mod.ObjectRef("ledger-oid-1")
        r2 = refs_mod.ObjectRef("ledger-oid-1")
        r3 = refs_mod.ObjectRef("ledger-oid-2")
        snap = refs_mod.snapshot_refs()
        assert snap["refs"]["ledger-oid-1"][0] == 2
        assert snap["refs"]["ledger-oid-2"][0] == 1
        # The creation site is THIS test file, not a ray_tpu frame.
        assert "test_observability.py" in (snap["refs"]["ledger-oid-1"][1] or "")
        del r1, r2
        gc.collect()
        snap = refs_mod.snapshot_refs()
        assert "ledger-oid-1" not in snap["refs"]
        assert snap["refs"]["ledger-oid-2"][0] == 1
        del r3
    finally:
        os.environ.pop("RAY_TPU_REF_CALLSITE", None)
        _config._reset_for_tests()
        refs_mod._reset_table_for_tests()


def test_build_memory_records_leak_rules():
    """Pure-join unit test of the ledger's two leak rules (telemetry.py):
    dead-holder (crashed process's unreclaimed borrows) and
    no-live-holder (aged located bytes at refcount 0)."""
    from ray_tpu._private.telemetry import (
        build_memory_records,
        summarize_memory_records,
    )

    now = 1000.0
    records = build_memory_records(
        store_table={
            "o-live": ("shm", 100),
            "o-crashheld": ("shm", 5000),
            "o-orphan": ("shm", 900),
            "o-young": ("shm", 50),
        },
        refcounts={"o-live": 1, "o-crashheld": 1},
        ready={"o-live": True, "o-crashheld": True, "o-orphan": True, "o-young": True},
        locations={"o-remote": ["nodeB"]},
        sizes={"o-remote": 777},
        meta={
            "o-live": (now - 60, "driver"),
            "o-orphan": (now - 60, "driver"),
            "o-young": (now - 1, "driver"),
            "o-remote": (now - 60, "w-1"),
        },
        conn_refs={"head": {"o-live": 1}, "w-2": {"o-remote": 1}},
        pushed_tables={"head": {"refs": {"o-live": [1, "app.py:7"]}}},
        dead_refs={
            "w-dead": {"refs": {"o-crashheld": 1}, "node": "nodeA", "pid": 4242}
        },
        proc_info={"head": ("head", 1), "w-2": ("nodeB", 9)},
        now=now,
        leak_age_s=10.0,
    )
    by_id = {r["object_id"]: r for r in records}
    assert by_id["o-live"]["leak"] is None
    assert by_id["o-live"]["site"] == "app.py:7"
    assert by_id["o-crashheld"]["leak"] == "dead-holder"
    dead_holder = [h for h in by_id["o-crashheld"]["holders"] if h["dead"]][0]
    assert (dead_holder["node"], dead_holder["pid"]) == ("nodeA", 4242)
    assert by_id["o-orphan"]["leak"] == "no-live-holder"
    assert by_id["o-young"]["leak"] is None  # inside the seal window
    assert by_id["o-remote"]["leak"] is None  # held by live w-2
    assert by_id["o-remote"]["location"] == "remote"

    summary = summarize_memory_records(records, group_by="node", top=2)
    assert summary["leak_suspects"] == 2
    assert summary["leak_suspect_bytes"] == 5900
    assert len(summary["top"]) == 2
    assert summary["top"][0]["size_bytes"] == 5000  # sorted by size
    assert "nodeB" in summary["groups"]
    by_owner = summarize_memory_records(records, group_by="owner")
    assert by_owner["groups"]["driver"]["objects"] >= 2


def test_memory_summary_spill_restore_free(monkeypatch):
    """Ledger states across the hard transitions: shm -> spilled ->
    restored -> freed, with the lifecycle event ring recording each."""
    import numpy as np

    monkeypatch.setenv("RAY_TPU_OBJECT_STORE_MEMORY", str(3 * 1024 * 1024))
    from ray_tpu._private import config as _config

    _config._reset_for_tests()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu._private.runtime import get_runtime

        rt = get_runtime()
        a = ray_tpu.put(np.zeros(2 * 1024 * 1024, dtype=np.uint8))
        b = ray_tpu.put(np.ones(2 * 1024 * 1024, dtype=np.uint8))
        recs = {r["object_id"]: r for r in state_api.list_object_refs()}
        assert recs[a.id]["location"] == "spilled", recs[a.id]
        assert recs[b.id]["location"] == "shm"
        # Spilled size survives via the runtime's size map.
        assert recs[a.id]["size_bytes"] and recs[a.id]["size_bytes"] > 1024 * 1024
        summary = state_api.memory_summary()
        assert summary["nodes"]["head"]["spilled_bytes"] > 0

        assert int(ray_tpu.get(a, timeout=60)[0]) == 0  # transparent restore
        recs = {r["object_id"]: r for r in state_api.list_object_refs()}
        assert recs[a.id]["location"] in ("shm", "spilled")  # b may spill now

        aid = a.id
        del a
        deadline = time.time() + 10
        while time.time() < deadline:
            known = {r["object_id"] for r in state_api.list_object_refs()}
            if aid not in known:
                break
            time.sleep(0.2)
        assert aid not in known, "freed object still in the ledger"
        events = [
            (e["oid"], e["event"]) for e in rt.object_events if e["oid"] == aid
        ]
        kinds = [k for _o, k in events]
        for expected in ("create", "spill", "restore", "free"):
            assert expected in kinds, (expected, kinds)
        # create precedes spill precedes restore precedes free
        assert kinds.index("spill") < kinds.index("restore") < kinds.index("free")
        del b
    finally:
        ray_tpu.shutdown()
        from ray_tpu._private import config as _c2

        _c2._reset_for_tests()


def test_worker_crash_mid_hold_flags_leak_then_reclaims(monkeypatch):
    """A worker SIGKILLed while holding a borrowed ref leaves a DEAD-
    HOLDER leak suspect attributed to its node/pid; reclaim_dead_refs
    drops the borrow, frees the bytes, and the ledger converges to zero
    suspects (the chaos-soak standing property, in miniature)."""
    import os
    import signal

    monkeypatch.setenv("RAY_TPU_LEAK_RECLAIM_GRACE_S", "600")  # hold the flag
    from ray_tpu._private import config as _config

    _config._reset_for_tests()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu._private.runtime import get_runtime

        rt = get_runtime()

        @ray_tpu.remote
        class Holder:
            def __init__(self):
                self.kept = None

            def hold(self, box):
                self.kept = box  # deliberate leak: never released
                return "held"

            def pid(self):
                return os.getpid()

        h = Holder.remote()
        big = ray_tpu.put(b"z" * 700_000)
        # Inside a list so the actor receives the REF (a borrow), not the value.
        assert ray_tpu.get(h.hold.remote([big]), timeout=60) == "held"
        pid = ray_tpu.get(h.pid.remote(), timeout=60)
        oid = big.id
        del big  # the driver's own ref drops; the actor's borrow remains
        os.kill(pid, signal.SIGKILL)

        leak = None
        deadline = time.time() + 30
        while time.time() < deadline:
            s = state_api.memory_summary(top=0)
            match = [r for r in s["leaks"] if r["object_id"] == oid]
            if match:
                leak = match[0]
                break
            time.sleep(0.3)
        assert leak is not None, "crashed holder's object never flagged"
        assert leak["leak"] == "dead-holder"
        dead = [x for x in leak["holders"] if x["dead"]]
        assert dead and dead[0]["pid"] == pid and dead[0]["node"], (
            "leak not attributed to the dead holder's node/pid"
        )

        assert rt.reclaim_dead_refs(force=True) >= 1
        deadline = time.time() + 15
        while time.time() < deadline:
            s = state_api.memory_summary(top=0)
            known = {r["object_id"] for r in state_api.list_object_refs()}
            if s["leak_suspects"] == 0 and oid not in known:
                break
            time.sleep(0.3)
        assert s["leak_suspects"] == 0, s["leaks"]
        assert oid not in known, "reclaimed object still holds bytes"
    finally:
        ray_tpu.shutdown()
        from ray_tpu._private import config as _c2

        _c2._reset_for_tests()


def test_orphan_no_live_holder_reclaimed_by_ledger_tick(monkeypatch):
    """Bytes at refcount 0 that no live process claims (the head-bounce
    retention shape) are flagged no-live-holder, then FREED by the ledger
    tick's orphan sweep after the grace — with a WARNING event, so the
    reclaim is visible, not papered over."""
    monkeypatch.setenv("RAY_TPU_LEAK_AGE_S", "1")
    monkeypatch.setenv("RAY_TPU_LEAK_ORPHAN_RECLAIM_S", "2")
    monkeypatch.setenv("RAY_TPU_METRICS_PUSH_MS", "300")
    from ray_tpu._private import config as _config

    _config._reset_for_tests()
    ray_tpu.init(num_cpus=1)
    try:
        from ray_tpu._private.runtime import get_runtime

        rt = get_runtime()
        import pickle as _pickle

        oid = "orphan-test-oid"
        # Seal bytes straight into the store with NO ObjectRef anywhere —
        # the rc-0 orphan a lost refop add leaves behind.
        rt.store.put_serialized(oid, _pickle.dumps(b"x" * 400_000), [])
        rt._note_object(oid, "driver")
        deadline = time.time() + 5
        flagged = False
        while time.time() < deadline and not flagged:
            recs = {r["object_id"]: r for r in state_api.list_object_refs()}
            flagged = recs.get(oid, {}).get("leak") == "no-live-holder"
            time.sleep(0.2)
        assert flagged, "orphan never flagged"
        deadline = time.time() + 15
        while time.time() < deadline:
            if not rt.store.has_local(oid):
                break
            time.sleep(0.3)
        assert not rt.store.has_local(oid), "orphan never reclaimed"
        evs = state_api.list_cluster_events(limit=100, severity="WARNING")
        assert any(
            e["message"] == "orphaned object reclaimed (no live holder)"
            for e in evs
        ), "reclaim left no WARNING event"
    finally:
        ray_tpu.shutdown()
        from ray_tpu._private import config as _c2

        _c2._reset_for_tests()


def test_memory_groupby_callsite(monkeypatch):
    """RAY_TPU_REF_CALLSITE=1: ledger records carry creation sites and
    --group-by callsite buckets bytes by the user line that made them."""
    monkeypatch.setenv("RAY_TPU_REF_CALLSITE", "1")
    from ray_tpu._private import config as _config
    from ray_tpu._private import refs as refs_mod

    _config._reset_for_tests()
    refs_mod._reset_table_for_tests()
    ray_tpu.init(num_cpus=2)
    try:
        keep = [ray_tpu.put(b"c" * 300_000) for _ in range(3)]  # one callsite
        summary = state_api.memory_summary(group_by="callsite")
        sites = [s for s in summary["groups"] if "test_observability.py" in s]
        assert sites, summary["groups"]
        assert summary["groups"][sites[0]]["objects"] >= 3
        del keep
    finally:
        ray_tpu.shutdown()
        _config._reset_for_tests()
        refs_mod._reset_table_for_tests()


def test_logs_all_aggregates_with_prefixes(rt, capsys):
    """`ray_tpu logs --all`: one aggregate tail across every worker with
    node/pid line prefixes (the old verb reached exactly one worker)."""
    @ray_tpu.remote
    def shout(i):
        print(f"LOGSALL-{i}")
        return i

    assert sorted(ray_tpu.get([shout.remote(i) for i in range(2)], timeout=60)) == [0, 1]
    from ray_tpu._private.runtime import get_runtime

    rt_ = get_runtime()
    deadline = time.time() + 20
    while time.time() < deadline:
        alllogs = rt_.get_logs_all()
        lines = [l for rec in alllogs.values() for l in rec["lines"]]
        if sum(1 for l in lines if l.startswith("LOGSALL-")) >= 2:
            break
        time.sleep(0.3)
    assert sum(1 for l in lines if l.startswith("LOGSALL-")) >= 2, alllogs
    for rec in alllogs.values():
        assert "node" in rec and "pid" in rec

    from ray_tpu.scripts import cli as cli_mod

    class _Args:
        all = True
        tail = 0
        address = None
        worker = None
        actor = None

    assert cli_mod.cmd_logs(_Args()) == 0
    out = capsys.readouterr().out
    # log_to_driver echoes "(w-...) line" copies into stdout too — the
    # aggregate verb's own lines are the node/pid-prefixed ones.
    hits = [
        l for l in out.splitlines()
        if "LOGSALL-" in l and l.startswith("[") and "/" in l.split("]")[0]
    ]
    assert len(hits) >= 2, out.splitlines()[:10]


def test_dashboard_memory_endpoint(rt):
    """/api/memory serves the ledger summary; ?leaks=1 trims to suspects."""
    import json as _json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    keep = ray_tpu.put(b"d" * 400_000)
    dash = start_dashboard()
    try:
        body = _json.loads(
            urllib.request.urlopen(f"{dash.url}/api/memory", timeout=10).read()
        )
        assert body["objects"] >= 1
        assert body["nodes"]["head"]["store_bytes"] >= 400_000
        assert any(r["object_id"] == keep.id for r in body["top"])
        leaks = _json.loads(
            urllib.request.urlopen(
                f"{dash.url}/api/memory?leaks=1", timeout=10
            ).read()
        )
        assert set(leaks) == {"leak_suspects", "leak_suspect_bytes", "leaks"}
        assert leaks["leak_suspects"] == 0
    finally:
        stop_dashboard()
        del keep


def test_attached_state_verbs_and_memory_leaks_cli(tmp_path, capsys):
    """The attachable introspection plane against a REAL standalone head:
    util/state list_* verbs route through the head's state_list op (the
    old in-process-runtime requirement is gone), and `ray_tpu memory
    --leaks --address ...` flags a deliberately leaked object, attributing
    its bytes to the holding node/pid (the ISSUE 9 acceptance line)."""
    import json as _json
    import os
    import signal
    import subprocess

    from ray_tpu._private.head import launch_head_subprocess

    # The head inherits the env: hold dead-holder suspects long enough to
    # observe them over the CLI before the reclaim sweep clears them.
    os.environ["RAY_TPU_LEAK_RECLAIM_GRACE_S"] = "600"
    proc = None
    try:
        proc, head_json = launch_head_subprocess(
            str(tmp_path), num_cpus=4, session="memcli"
        )
        ray_tpu.init(address=head_json)

        @ray_tpu.remote
        class Holder:
            def __init__(self):
                self.kept = None

            def hold(self, box):
                self.kept = box
                return "held"

        h = Holder.remote()
        big = ray_tpu.put(b"L" * 800_000)
        assert ray_tpu.get(h.hold.remote([big]), timeout=90) == "held"

        # Attachable state verbs (satellite): answers come from the head.
        nodes = state_api.list_nodes()
        assert any(n["is_head"] for n in nodes)
        workers = state_api.list_workers()
        actor_workers = [w for w in workers if w["actor_id"]]
        assert actor_workers and actor_workers[0]["pid"]
        objs = state_api.list_objects()
        assert any(o["object_id"] == big.id for o in objs)
        assert state_api.summarize_tasks().get("FINISHED", 0) >= 1
        assert state_api.cluster_metrics()["object_store_capacity_bytes"] > 0

        # Deliberate leak: kill the holding worker, keep nothing else.
        pid = actor_workers[0]["pid"]
        oid = big.id
        del big
        os.kill(pid, signal.SIGKILL)

        from ray_tpu.scripts import cli as cli_mod

        class _Args:
            address = head_json
            group_by = None
            leaks = True
            top = 20
            events = False

        leak = None
        deadline = time.time() + 45
        while time.time() < deadline:
            assert cli_mod.cmd_memory(_Args()) == 0
            out = _json.loads(capsys.readouterr().out)
            match = [r for r in out["leaks"] if r["object_id"] == oid]
            if match:
                leak = match[0]
                break
            time.sleep(0.5)
        assert leak is not None, "attached --leaks never flagged the kill"
        assert leak["reason"] == "dead-holder"
        assert leak["size_bytes"] >= 800_000
        dead = [x for x in leak["holders"] if x["dead"]]
        assert dead and dead[0]["pid"] == pid and dead[0]["node"], leak

        # logs --all rides the same attachable path.
        from ray_tpu._private.worker_proc import get_worker_runtime

        wr = get_worker_runtime()
        assert wr is not None
        alllogs = wr.request("get_logs_all", None)
        assert isinstance(alllogs, dict)
    finally:
        os.environ.pop("RAY_TPU_LEAK_RECLAIM_GRACE_S", None)
        from ray_tpu._private import config as _c2

        ray_tpu.shutdown()
        _c2._reset_for_tests()
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def test_hung_daemon_declared_dead_by_heartbeat_timeout():
    """A daemon that stops heartbeating (SIGSTOP: conn open, process
    frozen) must be declared dead within the timeout so its tasks retry
    elsewhere (ray: gcs_health_check_manager.h:28-37 — EOF alone cannot
    catch a hung node)."""
    import os
    import signal
    import time

    import ray_tpu
    from ray_tpu._private.runtime import get_runtime

    os.environ["RAY_TPU_HEALTH_CHECK_TIMEOUT_MS"] = "3000"
    try:
        ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
        rt = get_runtime()
        nid = rt.add_daemon_node(num_cpus=2)
        assert nid in rt.node_daemons
        daemon_pid = rt._daemon_procs[nid].pid
        os.kill(daemon_pid, signal.SIGSTOP)  # hung, not dead: no EOF
        try:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and nid in rt.node_daemons:
                time.sleep(0.2)
            assert nid not in rt.node_daemons, (
                "hung daemon still counted alive after heartbeat timeout"
            )
        finally:
            os.kill(daemon_pid, signal.SIGCONT)
    finally:
        os.environ.pop("RAY_TPU_HEALTH_CHECK_TIMEOUT_MS", None)
        from ray_tpu._private import config as _c

        ray_tpu.shutdown()
        _c._reset_for_tests()


def test_dashboard_index_page(rt):
    """The web UI-lite page serves at / and every endpoint its script
    fetches responds with the JSON shapes the renderer consumes."""
    import re
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote(), timeout=30)
    dash = start_dashboard()
    try:
        html = urllib.request.urlopen(f"{dash.url}/", timeout=10).read().decode()
        assert "<html" in html and "ray_tpu dashboard" in html
        # Every table the script fills exists in the markup.
        for el in ("metrics", "nodes", "actors", "summary", "err", "ts"):
            assert f'id="{el}"' in html, el
        # Every endpoint the script fetches answers with parseable JSON.
        import json as _json

        for ep in re.findall(r"j\('(/api/[a-z_]+)'\)", html):
            body = urllib.request.urlopen(f"{dash.url}{ep}", timeout=10).read()
            _json.loads(body)
    finally:
        stop_dashboard()


def test_structured_cluster_events():
    """§2.1 event framework (ray: src/ray/util/event.h:102): severity +
    source structured events land in the session's events.jsonl AND the
    state API / dashboard, recording node and worker transitions."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    from ray_tpu.util.state import list_cluster_events
    from ray_tpu._private.runtime import get_runtime

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        rt_ = get_runtime()
        nid = rt_.add_daemon_node(num_cpus=1)  # crashes below -> "node died"
        nid2 = rt_.add_daemon_node(num_cpus=1)  # removed -> routine INFO

        @ray_tpu.remote
        def die():
            import os

            os._exit(1)

        with pytest.raises(Exception):
            ray_tpu.get(die.options(max_retries=0).remote(), timeout=60)
        rt_._daemon_procs[nid].kill()  # node CRASH (unplanned)
        rt_.remove_node(nid2)  # planned downscale

        deadline = time.time() + 15
        while time.time() < deadline:
            evs = list_cluster_events(limit=200)
            kinds = {(e["source"], e["message"]) for e in evs}
            if ("node", "node died") in kinds and (
                "node", "node removed"
            ) in kinds and ("worker", "worker died") in kinds:
                break
            time.sleep(0.2)
        assert ("node", "node registered") in kinds
        assert ("node", "node died") in kinds  # the kill -9'd daemon
        assert ("node", "node removed") in kinds  # planned: NOT an ERROR
        assert ("worker", "worker died") in kinds
        sev = {
            (e["source"], e["message"]): e["severity"]
            for e in list_cluster_events(limit=200)
        }
        assert sev[("node", "node died")] == "ERROR"
        assert sev[("node", "node removed")] == "INFO"
        # Severity filter: INFO-level registration drops at WARNING floor.
        warn_up = list_cluster_events(limit=200, severity="WARNING")
        assert all(e["severity"] in ("WARNING", "ERROR", "FATAL") for e in warn_up)
        # Durable file: JSONL lines parse and carry the schema.
        path = f"{rt_.log_dir}/events.jsonl"
        lines = [_json.loads(l) for l in open(path)]
        assert any(l["message"] == "node died" for l in lines)
        assert all({"timestamp", "severity", "source", "message"} <= set(l) for l in lines)
        # Dashboard endpoint with filters.
        dash = start_dashboard()
        try:
            out = _json.loads(
                urllib.request.urlopen(
                    f"{dash.url}/api/events?severity=WARNING&source=worker",
                    timeout=10,
                ).read()
            )
            assert out and all(e["source"] == "worker" for e in out)
        finally:
            stop_dashboard()
    finally:
        ray_tpu.shutdown()


def test_tracing_spans_chain_across_processes(monkeypatch):
    """OTel-style spans with context in task specs (SURVEY §5.1; ray:
    tracing_helper.py:160): a driver submit, its worker-side run, and a
    NESTED submit/run all share one trace id with parent links."""
    import time

    monkeypatch.setenv("RAY_TPU_TRACE", "1")  # workers inherit
    import ray_tpu
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def inner(x):
            return x + 1

        @ray_tpu.remote
        def outer():
            return ray_tpu.get(inner.remote(1))

        assert ray_tpu.get(outer.remote(), timeout=60) == 2
        from ray_tpu.util.state import list_spans

        deadline = time.time() + 15
        spans = []
        while time.time() < deadline:
            spans = list_spans()
            if sum(1 for s in spans if s["name"].startswith("run::")) >= 2:
                break
            time.sleep(0.3)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert "submit::outer" in by_name, sorted(by_name)
        assert "run::outer" in by_name, sorted(by_name)
        assert "run::inner" in by_name, sorted(by_name)
        sub = by_name["submit::outer"][-1]
        run = by_name["run::outer"][-1]
        assert run["trace_id"] == sub["trace_id"], "one trace across processes"
        assert run["parent_span_id"] == sub["span_id"], "run parents to submit"
        # the nested chain stays in the same trace
        assert by_name["run::inner"][-1]["trace_id"] == sub["trace_id"]
    finally:
        tracing.disable_tracing()  # module global: no leak into later tests
        ray_tpu.shutdown()


def test_spans_appear_in_chrome_timeline(monkeypatch):
    """Enabled tracing feeds the chrome-trace timeline export alongside
    task rows (the `ray_tpu timeline` surface)."""
    import time

    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    import ray_tpu
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def traced():
            return 1

        assert ray_tpu.get(traced.remote(), timeout=60) == 1
        from ray_tpu.dashboard import timeline

        deadline = time.time() + 15
        names = []
        while time.time() < deadline:
            names = [e["name"] for e in timeline()]
            if any(n.startswith("run::traced") for n in names):
                break
            time.sleep(0.3)
        assert any(n.startswith("submit::traced") for n in names), names[:20]
        assert any(n.startswith("run::traced") for n in names), names[:20]
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()
