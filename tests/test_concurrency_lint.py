"""Concurrency lint (ray_tpu/_private/analysis/ + scripts/ray_tpu_lint.py).

Tier-1 gate: the whole package must pass the analyzer with zero NEW
violations (existing reviewed sites live in the allowlist with
justifications), and each pass must detect a seeded synthetic violation
in its fixture — so a regression in the analyzer itself (a pass that
silently stops finding anything) also fails CI.
"""

import os
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "scripts"))

from ray_tpu._private.analysis import run_analysis  # noqa: E402
from ray_tpu._private.analysis import allowlist as allowlist_mod  # noqa: E402
from ray_tpu._private.analysis import (  # noqa: E402
    blocking,
    fault_registry,
    hot_send,
    lock_order,
    metric_names,
)
from ray_tpu._private.analysis.common import iter_py_files  # noqa: E402


def _write(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return str(p)


def _blocking_keys(violations):
    return [v.key for v in violations if v.pass_name == "blocking-under-lock"]


# ---------------------------------------------------------------------------
# the tier-1 gate: the real tree is clean


def test_package_has_no_new_violations():
    """The committed tree passes its own concurrency lint: every finding
    is allowlisted WITH a justification, the fault-point catalog is
    fresh, and every literal fault spec in tests/scripts names only real
    points and plausible process tags."""
    import ray_tpu_lint

    rc = ray_tpu_lint.main([])
    assert rc == 0, "concurrency lint failed on the committed tree (run scripts/ray_tpu_lint.py for details)"


def test_lint_reports_all_three_pass_types():
    result = run_analysis(
        [os.path.join(REPO, "ray_tpu")],
        spec_roots=[os.path.join(REPO, "tests"), os.path.join(REPO, "scripts")],
        allowlist_path=os.path.join(
            REPO, "ray_tpu", "_private", "analysis", "allowlist.txt"
        ),
        catalog_path=os.path.join(
            REPO, "ray_tpu", "_private", "analysis", "fault_points.txt"
        ),
    )
    # The analyzer knows all three pass types and the reviewed findings
    # (blocking-under-lock sites) are present-but-allowlisted, not absent.
    assert result.ok
    assert any(v.pass_name == "blocking-under-lock" for v in result.allowlisted)
    assert all(
        why and why != allowlist_mod.TODO_JUSTIFICATION
        for why in result.allowlist.values()
    ), "allowlist entries must carry a one-line justification"


# ---------------------------------------------------------------------------
# pass 1: blocking-under-lock


def test_blocking_detects_sleep_under_with_lock(tmp_path):
    p = _write(
        tmp_path,
        "fix1.py",
        """
        import threading, time

        class S:
            def __init__(self):
                self.lock = threading.Lock()

            def bad(self):
                with self.lock:
                    time.sleep(1)  # seeded violation
        """,
    )
    found = blocking.scan_file(p, "fix1.py")
    assert len(found) == 1
    assert "time.sleep" in found[0].key and "S.bad" in found[0].key


def test_blocking_detects_recv_between_acquire_release(tmp_path):
    p = _write(
        tmp_path,
        "fix2.py",
        """
        class S:
            def bad(self, conn):
                self._lock.acquire()
                data = conn.recv()  # seeded violation
                self._lock.release()
                return data

            def fine(self, conn):
                self._lock.acquire()
                self._lock.release()
                return conn.recv()
        """,
    )
    found = blocking.scan_file(p, "fix2.py")
    assert len(found) == 1
    assert "conn.recv" in found[0].key and "S.bad" in found[0].key


def test_blocking_catalog_covers_issue_sites(tmp_path):
    """The catalog named in the issue: time.sleep, conn/sock recv,
    .result(), wire send, subprocess, faults.point."""
    p = _write(
        tmp_path,
        "fix3.py",
        """
        import subprocess, time
        from ray_tpu._private import faults

        class S:
            def bad(self, conn, sock, fut):
                with self.lock:
                    time.sleep(0.1)
                    conn.recv()
                    sock.recv(1024)
                    fut.result()
                    conn.send(("x",))
                    subprocess.run(["true"])
                    faults.point("p.q")
        """,
    )
    found = blocking.scan_file(p, "fix3.py")
    assert len(found) == 7


def test_blocking_exempts_known_idioms(tmp_path):
    p = _write(
        tmp_path,
        "fix4.py",
        """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._ready = threading.Condition(self._lock)
                self.send_lock = threading.Lock()

            def cond_idiom(self):
                with self._lock:
                    self._ready.wait(1.0)  # releases _lock while blocked

            def send_idiom(self, msg):
                with self.send_lock:
                    self.conn.send(msg)  # the serialization lock's job

            def poll_idiom(self, refs):
                with self._lock:
                    return self.q.wait(refs, timeout=0)  # poll, not block

            def closure_idiom(self):
                with self._lock:
                    def later(conn):
                        return conn.recv()  # runs later, not under the lock
                    return later
        """,
    )
    assert blocking.scan_file(p, "fix4.py") == []


# ---------------------------------------------------------------------------
# pass 2: lock-order


def test_lock_order_detects_nested_with_inversion(tmp_path):
    p = _write(
        tmp_path,
        "ord1.py",
        """
        class S:
            def ab(self):
                with self.a_lock:
                    with self.b_lock:
                        pass

            def ba(self):
                with self.b_lock:
                    with self.a_lock:  # seeded inversion
                        pass
        """,
    )
    found = lock_order.scan_file(p, "ord1.py")
    assert len(found) == 1
    assert "S.a_lock" in found[0].key and "S.b_lock" in found[0].key


def test_lock_order_detects_cross_function_cycle(tmp_path):
    """f holds A and calls g, which takes B; h nests B->A directly: the
    call edge closes the cycle even though no single function nests both
    orders."""
    p = _write(
        tmp_path,
        "ord2.py",
        """
        class S:
            def f(self):
                with self.a_lock:
                    self.g()

            def g(self):
                with self.b_lock:
                    pass

            def h(self):
                with self.b_lock:
                    with self.a_lock:
                        pass
        """,
    )
    found = lock_order.scan_file(p, "ord2.py")
    assert len(found) == 1


def test_lock_order_consistent_order_is_clean(tmp_path):
    p = _write(
        tmp_path,
        "ord3.py",
        """
        class S:
            def f(self):
                with self.a_lock, self.b_lock:
                    pass

            def g(self):
                with self.a_lock:
                    with self.b_lock:
                        pass

            def reentrant(self):
                with self.a_lock:
                    with self.a_lock:  # RLock re-entry: never an edge
                        pass
        """,
    )
    assert lock_order.scan_file(p, "ord3.py") == []


# ---------------------------------------------------------------------------
# pass 3: fault-registry


def _fixture_points(tmp_path):
    pkg = _write(
        tmp_path,
        "pkg.py",
        """
        from ray_tpu._private import faults

        def hazard():
            if faults.ENABLED:
                faults.point("real.send", key="done")
            faults.point("real.recv")
        """,
    )
    return fault_registry.collect_points([(pkg, "pkg.py")])


def test_fault_registry_collects_points(tmp_path):
    points = _fixture_points(tmp_path)
    assert sorted(points) == ["real.recv", "real.send"]


def test_fault_registry_flags_typod_point_and_proc(tmp_path):
    points = _fixture_points(tmp_path)
    spec_file = _write(
        tmp_path,
        "spec_user.py",
        """
        import os
        from ray_tpu._private import faults

        def plan():
            faults.configure("real.sned:drop@every=3")  # seeded typo
            os.environ["RAY_TPU_FAULT_SPEC"] = "real.send:crash@proc=wrker"
            env = {"RAY_TPU_FAULT_SPEC": "real.*:delay=0.1"}  # valid
            monkey = None
        """,
    )
    found = fault_registry.validate_spec_files(
        [(spec_file, "spec_user.py")], points
    )
    msgs = " | ".join(v.message for v in found)
    assert len(found) == 2
    assert "real.sned" in msgs
    assert "proc='wrker'" in msgs


def test_fault_registry_flags_bad_grammar(tmp_path):
    points = _fixture_points(tmp_path)
    spec_file = _write(
        tmp_path,
        "spec_bad.py",
        """
        from ray_tpu._private import faults

        def plan():
            faults.configure("real.send:explode")  # unknown action
        """,
    )
    found = fault_registry.validate_spec_files(
        [(spec_file, "spec_bad.py")], points
    )
    assert len(found) == 1 and "unparseable" in found[0].message


def test_fault_registry_catalog_staleness_and_regen(tmp_path):
    points = _fixture_points(tmp_path)
    catalog = str(tmp_path / "fault_points.txt")
    # Missing catalog -> stale; regenerated -> clean; drifted -> stale.
    assert fault_registry.check_catalog(points, catalog)
    fault_registry.write_catalog(points, catalog)
    assert fault_registry.check_catalog(points, catalog) == []
    points["real.new"] = ["pkg.py:99"]
    stale = fault_registry.check_catalog(points, catalog)
    assert stale and "real.new" in stale[0].message


def test_committed_catalog_matches_tree():
    files = iter_py_files(os.path.join(REPO, "ray_tpu"))
    points = fault_registry.collect_points(files)
    committed = fault_registry.load_catalog(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "fault_points.txt")
    )
    assert sorted(points) == sorted(committed)
    # The PR 1 hazard sites are all registered.
    for expected in ("wire.send", "wire.recv", "peer.send", "gcs.save"):
        assert expected in points


# ---------------------------------------------------------------------------
# pass 4: hot-send


@pytest.mark.parametrize("rel", sorted(hot_send.HOT_MODULES))
def test_hot_send_flags_direct_conn_send_in_hot_modules(tmp_path, rel):
    """A direct conn send added to a hot streaming module is a finding
    (until reviewed into the allowlist); the same code outside the hot
    module set is not."""
    src = """
    class S:
        def stream(self, msg):
            self.conn.send(msg)  # seeded: bypasses BatchingConn review

        def not_a_conn(self, sock, msg):
            sock.send(msg)  # non-conn receiver: out of scope
    """
    import textwrap

    p = tmp_path / os.path.basename(rel)
    p.write_text(textwrap.dedent(src))
    found = hot_send.scan_file(str(p), rel)
    assert len(found) == 1
    assert found[0].key == f"hot-send:{rel}:S.stream:self.conn.send"
    assert hot_send.scan_file(str(p), "ray_tpu/rllib/policy_client.py") == []


def test_hot_send_every_committed_site_is_justified():
    """The real tree's hot-send findings are all reviewed entries with
    real justifications (the coalescing regression gate is armed)."""
    result = run_analysis(
        [os.path.join(REPO, "ray_tpu")],
        spec_roots=[],
        allowlist_path=os.path.join(
            REPO, "ray_tpu", "_private", "analysis", "allowlist.txt"
        ),
    )
    hot = [v for v in result.violations if v.pass_name == "hot-send"]
    assert hot, "hot-send pass found nothing — the pass regressed"
    for v in hot:
        why = result.allowlist.get(v.key)
        assert why and why != allowlist_mod.TODO_JUSTIFICATION, v.key


# ---------------------------------------------------------------------------
# allowlist + --fix-allowlist


def test_allowlist_roundtrip_preserves_justifications(tmp_path):
    path = str(tmp_path / "allow.txt")
    allowlist_mod.save(path, {"k1": "because reasons", "k2": ""})
    loaded = allowlist_mod.load(path)
    assert loaded["k1"] == "because reasons"
    # k2 was saved with the TODO placeholder and counts as unjustified.
    assert allowlist_mod.unjustified(loaded) == ["k2"]


def test_fix_allowlist_regenerate_semantics():
    existing = {"keep": "reviewed: fine", "stale": "old reason"}
    merged, added, dropped = allowlist_mod.regenerate(
        existing, ["keep", "fresh"]
    )
    assert merged["keep"] == "reviewed: fine"  # justification survives
    assert merged["fresh"] == allowlist_mod.TODO_JUSTIFICATION
    assert added == ["fresh"] and dropped == ["stale"]
    assert "stale" not in merged  # regeneration is deliberate removal


def test_cli_fails_on_seeded_violation(tmp_path):
    """End-to-end: a fixture tree with one seeded blocking violation makes
    the CLI exit non-zero; --fix-allowlist then makes it pass (with the
    TODO entry reported until justified)."""
    import ray_tpu_lint

    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\n"
        "def bad(lock):\n"
        "    with lock:\n"
        "        time.sleep(1)\n"
    )
    allow = str(tmp_path / "allow.txt")
    args = [
        str(pkg),
        "--spec-roots",
        "--allowlist", allow,
        "--catalog", str(tmp_path / "catalog.txt"),
        "--metric-catalog", str(tmp_path / "metric_names.txt"),
        "--span-catalog", str(tmp_path / "span_names.txt"),
        "--no-catalog-check",
    ]
    assert ray_tpu_lint.main(args) == 1
    assert ray_tpu_lint.main(args + ["--fix-allowlist"]) == 0
    # TODO-justified entries still fail the plain run: growth is deliberate
    # AND reviewed, never silent.
    assert ray_tpu_lint.main(args) == 1
    entries = allowlist_mod.load(allow)
    entries = {k: "fixture: intentional" for k in entries}
    allowlist_mod.save(allow, entries)
    assert ray_tpu_lint.main(args) == 0


# ---------------------------------------------------------------------------
# pass 6: metric-names (duplicate registrations + undeclared tags)


def test_metric_names_collects_constructions(tmp_path):
    p = _write(
        tmp_path,
        "m1.py",
        """
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        REQS = Counter("app_requests", "reqs", tag_keys=("route",))
        DEPTH = Gauge("app_depth")
        LAT = Histogram("app_latency", "lat", boundaries=[0.1, 1.0])
        """,
    )
    got = metric_names.collect_metrics([(p, "m1.py")])
    assert sorted(got) == ["app_depth", "app_latency", "app_requests"]
    assert got["app_requests"][0][1] == "Counter"


def test_metric_names_flags_duplicates_and_type_conflicts(tmp_path):
    p1 = _write(
        tmp_path, "d1.py",
        'from ray_tpu.util.metrics import Counter\nC = Counter("dup_m", "x")\n',
    )
    p2 = _write(
        tmp_path, "d2.py",
        'from ray_tpu.util.metrics import Gauge\nG = Gauge("dup_m", "x")\n',
    )
    got = metric_names.collect_metrics([(p1, "d1.py"), (p2, "d2.py")])
    found = metric_names.check_duplicates(got)
    assert len(found) == 1
    assert found[0].key == "metric-names:dup:dup_m"
    assert "CONFLICTING" in found[0].message


def test_metric_names_flags_undeclared_tags(tmp_path):
    p = _write(
        tmp_path,
        "m2.py",
        """
        from ray_tpu.util.metrics import Counter, Gauge

        class S:
            def __init__(self):
                self.c = Counter("svc_reqs", "r", tag_keys=("route",))
                self.g = Gauge("svc_depth", "d", tag_keys=("shard",)
                               ).set_default_tags({"shard": "0"})

            def good(self):
                self.c.inc(tags={"route": "/a"})
                self.g.set(1, tags={"shard": "1"})

            def bad(self):
                self.c.inc(tags={"rout": "/a"})  # seeded typo
                self.g.set(1, tags={"replica": "x"})  # seeded undeclared

        BAD_DEFAULT = Gauge("svc_other", "o", tag_keys=("a",)
                            ).set_default_tags({"b": "1"})  # seeded
        """,
    )
    found = metric_names.scan_file(p, "m2.py")
    msgs = " | ".join(v.message for v in found)
    assert len(found) == 3, [v.key for v in found]
    assert "'rout'" in msgs and "'replica'" in msgs and "'b'" in msgs


def test_metric_names_flags_undeclared_ledger_tag(tmp_path):
    """The object-ledger gauges declare ("node", "tier") / ("path",): a
    record call inventing a new tag (the easy typo when wiring a new
    ledger surface) fails tier-1 statically instead of raising on the
    telemetry tick in production."""
    p = _write(
        tmp_path,
        "ledger.py",
        """
        from ray_tpu.util.metrics import Counter, Gauge

        LEDGER_BYTES = Gauge(
            "fixture_object_ledger_node_bytes", "b", tag_keys=("node", "tier")
        )
        COPIES = Counter("fixture_object_copies", "c", tag_keys=("path",))

        def tick(node):
            LEDGER_BYTES.set(1.0, tags={"node": node, "tier": "store"})  # ok
            LEDGER_BYTES.set(1.0, tags={"node": node, "teir": "spilled"})  # seeded
            COPIES.inc(tags={"paths": "put"})  # seeded
        """,
    )
    found = metric_names.scan_file(p, "ledger.py")
    msgs = " | ".join(v.message for v in found)
    assert len(found) == 2, [v.key for v in found]
    assert "'teir'" in msgs and "'paths'" in msgs


def test_metric_names_catalog_staleness_and_regen(tmp_path):
    p = _write(
        tmp_path, "m3.py",
        'from ray_tpu.util.metrics import Counter\nC = Counter("cat_m", "x")\n',
    )
    got = metric_names.collect_metrics([(p, "m3.py")])
    catalog = str(tmp_path / "metric_names.txt")
    assert metric_names.check_catalog(got, catalog)  # missing -> stale
    metric_names.write_catalog(got, catalog)
    assert metric_names.check_catalog(got, catalog) == []
    got["cat_new"] = [("m3.py:99", "Gauge")]
    stale = metric_names.check_catalog(got, catalog)
    assert stale and "cat_new" in stale[0].message


def test_committed_metric_catalog_matches_tree():
    files = iter_py_files(os.path.join(REPO, "ray_tpu"))
    got = metric_names.collect_metrics(files)
    committed = metric_names.load_catalog(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "metric_names.txt")
    )
    actual = {
        f"{name} {'/'.join(sorted({t for _s, t in sites}))}"
        for name, sites in got.items()
    }
    assert actual == set(committed)
    # The serve replica telemetry metrics are registered.
    assert any(n.startswith("serve_replica_queue_depth") for n in committed)
    # The task-attribution histogram is registered (ISSUE 10).
    assert "task_stage_seconds Histogram" in committed


# ---------------------------------------------------------------------------
# pass 7: span-names (literal tracing.span registry + catalog)


def test_span_names_collects_literals_and_skips_dynamic(tmp_path):
    from ray_tpu._private.analysis import span_names

    p = _write(
        tmp_path,
        "sp1.py",
        """
        from ray_tpu.util import tracing
        from ray_tpu.util.tracing import span

        def a(name):
            with tracing.span("fixture::alpha", attrs={"k": 1}):
                pass
            with span("fixture::beta"):
                pass
            with tracing.span(f"run::{name}"):  # dynamic: skipped
                pass
        """,
    )
    got = span_names.collect_spans([(p, "sp1.py")])
    assert sorted(got) == ["fixture::alpha", "fixture::beta"]
    assert got["fixture::alpha"][0].startswith("sp1.py:")


def test_span_names_flags_duplicates(tmp_path):
    from ray_tpu._private.analysis import span_names

    p1 = _write(
        tmp_path, "sd1.py",
        'from ray_tpu.util.tracing import span\n'
        'def f():\n    with span("fixture::dup"):\n        pass\n',
    )
    p2 = _write(
        tmp_path, "sd2.py",
        'from ray_tpu.util import tracing\n'
        'def g():\n    with tracing.span("fixture::dup"):\n        pass\n',
    )
    got = span_names.collect_spans([(p1, "sd1.py"), (p2, "sd2.py")])
    found = span_names.check_duplicates(got)
    assert len(found) == 1
    assert found[0].key == "span-names:dup:fixture::dup"
    assert "sd1.py" in found[0].message and "sd2.py" in found[0].message


def test_span_names_catalog_staleness_and_regen(tmp_path):
    from ray_tpu._private.analysis import span_names

    p = _write(
        tmp_path, "sc.py",
        'from ray_tpu.util.tracing import span\n'
        'def f():\n    with span("fixture::cat"):\n        pass\n',
    )
    got = span_names.collect_spans([(p, "sc.py")])
    catalog = str(tmp_path / "span_names.txt")
    assert span_names.check_catalog(got, catalog)  # missing -> stale
    span_names.write_catalog(got, catalog)
    assert span_names.check_catalog(got, catalog) == []
    got["fixture::new"] = ["sc.py:99"]
    stale = span_names.check_catalog(got, catalog)
    assert stale and "fixture::new" in stale[0].message
    assert stale[0].key.startswith("span-names:catalog:")


def test_committed_span_catalog_matches_tree():
    from ray_tpu._private.analysis import span_names

    files = iter_py_files(os.path.join(REPO, "ray_tpu"))
    got = span_names.collect_spans(files)
    committed = span_names.load_catalog(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "span_names.txt")
    )
    assert set(got) == set(committed)
    # The serve request-tracing spans are cataloged (ISSUE 10 satellite).
    for name in ("serve::request", "serve::route", "serve::replica"):
        assert name in committed


# ---------------------------------------------------------------------------
# pass 5: gcs-mutation (journaled-table writes outside gcs.py)


def test_gcs_mutation_detects_direct_table_writes(tmp_path):
    from ray_tpu._private.analysis import gcs_mutation

    p = _write(
        tmp_path,
        "fix_gcs.py",
        """
        class Runtime:
            def bad_subscript(self, info):
                self.state.actors[info.actor_id] = info  # seeded violation

            def bad_pop(self, aid):
                self.state.named_actors.pop(("ns", "name"), None)  # seeded

            def bad_update(self, jobs):
                self.state.jobs.update(jobs)  # seeded violation

            def bad_del(self, aid):
                del self.state.actors[aid]  # seeded violation

            def fine_reads(self, aid):
                a = self.state.actors.get(aid)
                for x in self.state.actors.values():
                    pass
                return a, len(self.state.jobs)

            def fine_mutators(self, info):
                self.state.register_actor(info)
                self.state.set_actor_state(info.actor_id, "ALIVE")
                self.state.set_job_state("j1", "RUNNING")

            def fine_unrelated_tables(self, aid):
                # runtime-side bookkeeping dicts are NOT the GCS tables
                self.actors[aid] = object()
                self.workers.pop(aid, None)
        """,
    )
    found = gcs_mutation.scan_file(p, "fix_gcs.py")
    assert len(found) == 4, [v.key for v in found]
    tables = {v.key.split(":")[-2] for v in found}
    assert tables == {
        "self.state.actors", "self.state.named_actors", "self.state.jobs"
    }


def test_journal_coverage_flags_unjournaled_mutator(tmp_path):
    """A GlobalState mutator that writes a journaled table without ever
    calling self._journal(...) silently skips the durability journal —
    the batched path makes this invisible to manual testing (the write
    is decoupled from the mutation in time), so it fails tier-1."""
    from ray_tpu._private.analysis import journal_coverage

    p = _write(
        tmp_path,
        "gcs.py",
        """
        class GlobalState:
            def register_actor(self, info):
                self.actors[info.actor_id] = info
                self._journal(("actor_register", info.actor_id))

            def sneaky_bind(self, ns, name, aid):
                self.named_actors[(ns, name)] = aid  # seeded: no journal

            def sneaky_drop(self, aid):
                self.actors.pop(aid, None)  # seeded: no journal

            def import_functions(self, functions):
                # restore-path bulk loader: exempt by name
                self.functions.update(functions)

            def kv_put(self, key, value, namespace=""):
                # kv is snapshot-only by design: not a journaled table
                self.kv.setdefault(namespace, {})[key] = value
        """,
    )
    found = journal_coverage.scan_file(p, "ray_tpu/_private/gcs.py")
    keys = {v.key for v in found}
    assert keys == {
        "journal-coverage:ray_tpu/_private/gcs.py:sneaky_bind:named_actors",
        "journal-coverage:ray_tpu/_private/gcs.py:sneaky_drop:actors",
    }, keys
    # Outside the mutator module only the kind catalog applies.
    assert journal_coverage.scan_file(p, "fix_gcs.py") == []


def test_journal_coverage_flags_unreviewed_entry_kind(tmp_path):
    """Every literal journal entry kind must be in the reviewed catalog:
    a new kind whose restore-time handling nobody decided replays as
    silence after a head bounce."""
    from ray_tpu._private.analysis import journal_coverage

    p = _write(
        tmp_path,
        "fix_kinds.py",
        """
        class Runtime:
            def fine(self, oid, spec):
                self._journal_append(("lineage", oid, spec))

            def fine_lease(self, lease_id):
                self._journal_append(("lease", "revoke", lease_id, "idle"))

            def bad(self, x):
                self._journal_append(("brand_new_kind", x))  # seeded
        """,
    )
    found = journal_coverage.scan_file(p, "fix_kinds.py")
    assert len(found) == 1, [v.key for v in found]
    assert found[0].key == "journal-coverage:fix_kinds.py:kind:brand_new_kind"


def test_journal_coverage_committed_tree_is_clean():
    """The real gcs.py mutators all reach journal_hook and every kind the
    runtime journals is reviewed."""
    from ray_tpu._private.analysis import journal_coverage

    for rel in ("ray_tpu/_private/gcs.py", "ray_tpu/_private/runtime.py"):
        path = os.path.join(REPO, *rel.split("/"))
        assert journal_coverage.scan_file(path, rel) == [], rel


def test_copy_coverage_flags_uncounted_byte_movers(tmp_path):
    """A byte-moving function in an object-plane module (recv_into /
    os.write / buffer-fill slice assignment) that never ticks
    telemetry.count_copy would silently bypass the bytes-per-copy
    honesty counters — the one-copy broadcast proofs would keep passing
    while real copies go uncounted."""
    from ray_tpu._private.analysis import copy_coverage

    p = _write(
        tmp_path,
        "object_plane.py",
        """
        import os
        import struct

        def counted_ingest(sock, view, total):
            got = 0
            while got < total:
                got += sock.recv_into(view[got:total])
            _telemetry.count_copy("pull", total)

        def sneaky_stage(view, data):
            view[: len(data)] = data  # seeded: buffer fill, no counter

        def sneaky_send(fd, mv):
            os.write(fd, mv)  # seeded: byte mover, no counter

        def header_only(mm, wm):
            struct.pack_into("<Q", mm, 24, wm)  # metadata: exempt

        def no_bytes(a, b):
            return a + b
        """,
    )
    found = copy_coverage.scan_file(p, "ray_tpu/_private/object_plane.py")
    keys = {v.key for v in found}
    assert keys == {
        "copy-coverage:ray_tpu/_private/object_plane.py:sneaky_stage",
        "copy-coverage:ray_tpu/_private/object_plane.py:sneaky_send",
    }, keys
    # Modules outside the object plane are not scanned.
    assert copy_coverage.scan_file(p, "ray_tpu/_private/elsewhere.py") == []


def test_copy_coverage_committed_tree_is_clean():
    """Every byte-moving path in the real store/object_plane/arena
    modules either ticks count_copy or carries a reviewed justification
    in the allowlist."""
    from ray_tpu._private.analysis import copy_coverage
    from ray_tpu._private.analysis import allowlist as allowlist_mod

    allowed = allowlist_mod.load(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "allowlist.txt")
    )
    for rel in sorted(copy_coverage.COPY_MODULES):
        path = os.path.join(REPO, *rel.split("/"))
        new = [
            v.key
            for v in copy_coverage.scan_file(path, rel)
            if v.key not in allowed
        ]
        assert new == [], new


def test_gcs_mutation_exempts_the_mutator_module(tmp_path):
    from ray_tpu._private.analysis import gcs_mutation

    p = _write(
        tmp_path,
        "gcs.py",
        """
        class GlobalState:
            def register_actor(self, info):
                self.actors[info.actor_id] = info
        """,
    )
    # Only the real module path is exempt — a stray gcs.py elsewhere is not.
    assert gcs_mutation.scan_file(p, "ray_tpu/_private/gcs.py") == []
    # self.actors on a non-state receiver is out of scope anyway, so seed a
    # state-shaped write to prove the non-exempt path fires.
    p2 = _write(
        tmp_path,
        "other.py",
        """
        def bad(rt, info):
            rt.state.actors[info.actor_id] = info  # seeded violation
        """,
    )
    assert len(gcs_mutation.scan_file(p2, "other.py")) == 1


# ---------------------------------------------------------------------------
# pass 10: wire-schema


def test_wire_schema_flags_unregistered_kind_send(tmp_path):
    """The PR-7 bug class: a send site invents a frame kind ('refs_pushh')
    that wire.SCHEMAS never registered — the peer's _validate kills the
    conn on the first push, and nothing static said so."""
    from ray_tpu._private.analysis import wire_schema

    p = _write(
        tmp_path,
        "fixture_send.py",
        """
        class Pusher:
            def push(self, conn, refs):
                conn.send(("refs_pushh", refs))  # seeded typo'd kind
                conn.send(("refs_push", refs))   # real kind, fine
        """,
    )
    found = wire_schema.scan_file(p, "fixture_send.py")
    keys = [v.key for v in found]
    assert keys == ["wire-schema:send-kind:fixture_send.py:Pusher.push:refs_pushh"]
    assert "refs_pushh" in found[0].message


def test_wire_schema_flags_send_arity_and_leading_type(tmp_path):
    from ray_tpu._private.analysis import wire_schema

    p = _write(
        tmp_path,
        "fixture_arity.py",
        """
        def announce(conn, wid):
            conn.send(("spawn_worker", wid))            # 1 extra, schema wants 2
            conn.send(("worker_exited", 3, 0))          # field0 int, schema wants str
            conn.send(("worker_exited", wid, 0))        # unknowable wid: fine
        """,
    )
    keys = sorted(v.key for v in wire_schema.scan_file(p, "fixture_arity.py"))
    assert keys == [
        "wire-schema:send-arity:fixture_arity.py:announce:spawn_worker",
        "wire-schema:send-type:fixture_arity.py:announce:worker_exited:field0",
    ]


def test_wire_schema_flags_recv_overread(tmp_path):
    """The PR-4 bug class: a recv handler indexes past the schema MINIMUM
    without a len() guard.  'ready' guarantees 3 extras (min) but carries
    up to 7 — msg[4] works against new senders and IndexErrors against
    old ones, exactly the skew that shipped."""
    from ray_tpu._private.analysis import wire_schema

    p = _write(
        tmp_path,
        "fixture_recv.py",
        """
        def loop(conn):
            msg = conn.recv()
            kind = msg[0]
            if kind == "ready":
                oid = msg[1]
                size = msg[2]
                announce = msg[4]          # seeded: beyond min, unguarded
                if len(msg) > 5:
                    tstamp = msg[5]        # guarded: fine
        """,
    )
    keys = [v.key for v in wire_schema.scan_file(p, "fixture_recv.py")]
    assert keys == ["wire-schema:recv-arity:fixture_recv.py:loop:ready:field4"]


def test_wire_schema_flags_exact_unpack_of_variable_arity(tmp_path):
    """Exact tuple unpack of a kind whose schema allows MORE fields than
    unpacked: 'worker_exited' is (2, 3) — `_, wid, rc = msg` raises
    ValueError the day a sender uses the third extra (the oom flag)."""
    from ray_tpu._private.analysis import wire_schema

    p = _write(
        tmp_path,
        "fixture_unpack.py",
        """
        def drain(conn):
            msg = conn.recv()
            if msg[0] == "worker_exited":
                _, wid, rc = msg           # seeded: schema max is 3 extras
        """,
    )
    found = wire_schema.scan_file(p, "fixture_unpack.py")
    assert [v.key for v in found] == [
        "wire-schema:recv-unpack:fixture_unpack.py:drain:worker_exited"
    ]
    assert "worker_exited" in found[0].message


def test_wire_schema_clean_fixture_has_no_findings(tmp_path):
    """Schema-conformant send + guarded recv produce zero findings — the
    pass has no background noise to drown real drift in."""
    from ray_tpu._private.analysis import wire_schema

    p = _write(
        tmp_path,
        "fixture_clean.py",
        """
        def pump(conn):
            conn.send(("heartbeat", 3))
            msg = conn.recv()
            if msg[0] == "worker_exited":
                wid, rc = msg[1], msg[2]
                oom = msg[3] if len(msg) > 3 else False
        """,
    )
    assert wire_schema.scan_file(p, "fixture_clean.py") == []


def test_wire_schema_native_tables_are_consistent():
    """wire_native.KIND_IDS ⊆ wire.SCHEMAS with in-range ids and arities
    — drift here means a frame encodes natively and fails validation on
    arrival."""
    from ray_tpu._private.analysis import wire_schema

    assert wire_schema.check_native() == []


def test_wire_schema_committed_wire_modules_are_clean():
    """Every send/recv site in the real wire-speaking modules conforms to
    wire.SCHEMAS or carries a reviewed allowlist justification."""
    from ray_tpu._private.analysis import wire_schema
    from ray_tpu._private.analysis import allowlist as allowlist_mod

    allowed = allowlist_mod.load(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "allowlist.txt")
    )
    for rel in sorted(wire_schema.WIRE_MODULES):
        path = os.path.join(REPO, *rel.split("/"))
        if not os.path.exists(path):
            continue
        new = [
            v.key for v in wire_schema.scan_file(path, rel)
            if v.key not in allowed
        ]
        assert new == [], new


# ---------------------------------------------------------------------------
# pass 11: knob-registry


def test_knob_registry_flags_unknown_env_name(tmp_path):
    """A typo'd knob env name silently no-ops — the exact failure mode
    the fault-registry pass already kills for fault specs."""
    from ray_tpu._private.analysis import knob_registry

    p = _write(
        tmp_path,
        "uses_env.py",
        """
        import os

        def boot():
            os.environ.get("RAY_TPU_WIRE_BATCH_BYTE")   # seeded typo
            os.environ.get("RAY_TPU_WIRE_BATCH_BYTES")  # declared: bypass, not unknown
        """,
    )
    keys = sorted(v.key for v in knob_registry.scan_file(p, "uses_env.py"))
    assert keys == [
        "knob-registry:bypass:uses_env.py:RAY_TPU_WIRE_BATCH_BYTES",
        "knob-registry:unknown:uses_env.py:RAY_TPU_WIRE_BATCH_BYTE",
    ]


def test_knob_registry_flags_bypass_read_but_not_wiring(tmp_path):
    """Reading a KNOB's env form outside config.py skips resolution order
    and type coercion; reading declared process WIRING (authkey, host)
    is what wiring is for and stays silent."""
    from ray_tpu._private.analysis import knob_registry

    p = _write(
        tmp_path,
        "reader.py",
        """
        import os

        def connect():
            native = os.environ.get("RAY_TPU_WIRE_NATIVE")  # seeded bypass
            host = os.environ.get("RAY_TPU_DRIVER_HOST")    # wiring: fine
            os.environ["RAY_TPU_SESSION"] = "s"             # wiring write: fine
        """,
    )
    keys = [v.key for v in knob_registry.scan_file(p, "reader.py")]
    assert keys == ["knob-registry:bypass:reader.py:RAY_TPU_WIRE_NATIVE"]


def test_knob_registry_flags_config_get_of_undeclared_knob(tmp_path):
    from ray_tpu._private.analysis import knob_registry

    p = _write(
        tmp_path,
        "getter.py",
        """
        from ray_tpu._private import config

        def tune():
            config.get("wire_nativ")   # seeded typo: KeyError at runtime
            config.get("wire_native")  # declared: fine
        """,
    )
    keys = [v.key for v in knob_registry.scan_file(p, "getter.py")]
    assert keys == ["knob-registry:get-unknown:getter.py:wire_nativ"]


def test_knob_registry_ignores_non_config_receivers(tmp_path):
    """`config` as a plain function parameter (tune trial dicts) must not
    be mistaken for the config module — receiver names come from the
    file's imports, not the identifier."""
    from ray_tpu._private.analysis import knob_registry

    p = _write(
        tmp_path,
        "tuner_like.py",
        """
        def train_fn(config):
            lr = config.get("train_loop_config")
        """,
    )
    assert knob_registry.scan_file(p, "tuner_like.py") == []


def test_knob_registry_spec_files_flag_unknown_only(tmp_path):
    from ray_tpu._private.analysis import knob_registry

    p = _write(
        tmp_path,
        "test_spec.py",
        """
        def test_knob(monkeypatch):
            monkeypatch.setenv("RAY_TPU_NO_SUCH_KNOB", "1")   # seeded
            monkeypatch.setenv("RAY_TPU_WIRE_NATIVE", "0")    # declared: fine
        """,
    )
    keys = [v.key for v in knob_registry.scan_spec_file(p, "test_spec.py")]
    assert keys == ["knob-registry:unknown:test_spec.py:RAY_TPU_NO_SUCH_KNOB"]


def test_knob_registry_catalog_staleness_and_regen(tmp_path):
    from ray_tpu._private.analysis import knob_registry

    catalog = str(tmp_path / "knob_names.txt")
    assert knob_registry.check_catalog(catalog)          # missing -> stale
    knob_registry.write_catalog(catalog)
    assert knob_registry.check_catalog(catalog) == []    # regenerated -> clean
    with open(catalog, "a", encoding="utf-8") as f:
        f.write("RAY_TPU_GHOST_KNOB knob\n")
    stale = knob_registry.check_catalog(catalog)
    assert stale and "RAY_TPU_GHOST_KNOB" in stale[0].message


def test_committed_knob_catalog_matches_tree():
    from ray_tpu._private.analysis import knob_registry

    committed = os.path.join(
        REPO, "ray_tpu", "_private", "analysis", "knob_names.txt"
    )
    assert knob_registry.check_catalog(committed) == []
    lines = knob_registry.load_catalog(committed)
    kinds = {ln.split()[1] for ln in lines}
    assert kinds == {"knob", "alias", "wiring"}


def test_knob_registry_no_dead_knobs_unallowlisted():
    """Every knob in config._DEFS is read by a config.get literal
    somewhere in the package, or carries a reviewed justification."""
    from ray_tpu._private.analysis import knob_registry
    from ray_tpu._private.analysis import allowlist as allowlist_mod

    allowed = allowlist_mod.load(
        os.path.join(REPO, "ray_tpu", "_private", "analysis", "allowlist.txt")
    )
    files = iter_py_files(os.path.join(REPO, "ray_tpu"))
    dead = [
        v.key for v in knob_registry.check_dead_knobs(files)
        if v.key not in allowed
    ]
    assert dead == [], dead
