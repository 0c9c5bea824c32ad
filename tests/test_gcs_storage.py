"""Pluggable snapshot storage tests (gcs_storage.py) —
ray: src/ray/gcs/store_client/ (in-memory vs redis backends)."""

import pickle

import pytest

from ray_tpu._private.gcs_storage import (
    FileSnapshotStorage,
    SqliteSnapshotStorage,
    make_snapshot_storage,
)


@pytest.mark.parametrize("backend", ["file", "sqlite"])
def test_roundtrip_and_session_scoping(tmp_path, backend):
    path = str(tmp_path / ("snap.db" if backend == "sqlite" else "snap"))
    st = (SqliteSnapshotStorage if backend == "sqlite" else FileSnapshotStorage)(path)
    snap = {"session": "s1", "kv": {"": {"k": b"v"}}, "actors": []}
    st.save("s1", snap)
    assert st.load("s1") == snap
    assert st.load("other-session") is None  # never replay foreign state
    st.save("s1", {**snap, "kv": {}})
    assert st.load("s1")["kv"] == {}
    st.close()


def test_sqlite_many_sessions_one_db(tmp_path):
    st = SqliteSnapshotStorage(str(tmp_path / "multi.db"))
    st.save("a", {"session": "a", "n": 1})
    st.save("b", {"session": "b", "n": 2})
    assert st.load("a")["n"] == 1
    assert st.load("b")["n"] == 2
    st.close()


def test_sqlite_survives_corrupt_blob(tmp_path):
    st = SqliteSnapshotStorage(str(tmp_path / "c.db"))
    st._conn.execute(
        "INSERT INTO snapshots (session, snap, updated) VALUES (?, ?, 0)",
        ("bad", b"not-a-pickle"),
    )
    st._conn.commit()
    assert st.load("bad") is None
    st.close()


def test_make_storage_respects_knob(tmp_path, monkeypatch):
    from ray_tpu._private import config

    monkeypatch.setenv("RAY_TPU_GCS_STORAGE_BACKEND", "sqlite")
    config._values.pop("gcs_storage_backend", None)
    st = make_snapshot_storage(str(tmp_path / "s"))
    assert isinstance(st, SqliteSnapshotStorage)
    st.close()
    monkeypatch.setenv("RAY_TPU_GCS_STORAGE_BACKEND", "file")
    config._values.pop("gcs_storage_backend", None)
    st = make_snapshot_storage(str(tmp_path / "s2"))
    assert isinstance(st, FileSnapshotStorage)
    config._values.pop("gcs_storage_backend", None)


def test_head_restart_replays_via_sqlite(tmp_path, monkeypatch):
    """End-to-end: a head using the sqlite backend persists and replays
    KV across restart (the same property test_head_split proves for the
    file backend)."""
    monkeypatch.setenv("RAY_TPU_GCS_STORAGE_BACKEND", "sqlite")
    from ray_tpu._private import config
    from ray_tpu._private.runtime import Runtime

    config._values.pop("gcs_storage_backend", None)
    snap_path = str(tmp_path / "head-snap")
    rt = Runtime(num_cpus=1, session_name="sqlsnap", snapshot_path=snap_path)
    rt.state.kv_put("persist-me", b"42", "")
    rt._write_snapshot()
    rt.shutdown()

    rt2 = Runtime(num_cpus=1, session_name="sqlsnap", snapshot_path=snap_path)
    try:
        assert rt2.state.kv_get("persist-me", "") == b"42"
    finally:
        rt2.shutdown()
    config._values.pop("gcs_storage_backend", None)


def test_snapshot_version_mismatch_refuses_restore(tmp_path, capsys):
    """A version-bumped document must refuse LOUDLY, not silently clean-
    boot (the wire got versioning in r4; the snapshot document now too)."""
    from ray_tpu._private import gcs_storage as gs

    path = str(tmp_path / "snap.pkl")
    st = gs.FileSnapshotStorage(path)
    st.save("s1", {"session": "s1", "kv": {}})
    snap = st.load("s1")
    assert snap is not None and snap["snapshot_version"] == gs.SNAPSHOT_VERSION

    # Forge a future-version document.
    import pickle

    with open(path, "wb") as f:
        pickle.dump({"session": "s1", "snapshot_version": 999}, f)
    assert st.load("s1") is None
    err = capsys.readouterr().err
    assert "REFUSING snapshot restore" in err
    import os
    assert os.path.exists(path + ".refused"), "refused doc must be kept aside"


def test_snapshot_corrupt_file_set_aside(tmp_path, capsys):
    from ray_tpu._private import gcs_storage as gs
    import os

    path = str(tmp_path / "snap.pkl")
    with open(path, "wb") as f:
        f.write(b"not a pickle at all")
    st = gs.FileSnapshotStorage(path)
    assert st.load("s1") is None
    err = capsys.readouterr().err
    assert "unreadable" in err
    assert os.path.exists(path + ".corrupt"), "evidence must be kept aside"


def test_sqlite_version_stamp(tmp_path):
    from ray_tpu._private import gcs_storage as gs

    st = gs.SqliteSnapshotStorage(str(tmp_path / "snaps.db"))
    st.save("s2", {"session": "s2"})
    snap = st.load("s2")
    assert snap is not None and snap["snapshot_version"] == gs.SNAPSHOT_VERSION
    st.close()


# ---------------------------------------------------------------------------
# mutation journal (append-only log between snapshot ticks)


def _journal(tmp_path, session="s1"):
    from ray_tpu._private.gcs_storage import make_mutation_journal

    return make_mutation_journal(str(tmp_path / "snap.pkl"), session)


def test_journal_append_replay_roundtrip(tmp_path):
    j = _journal(tmp_path)
    entries = [
        ("actor_register", {"actor_id": "a1", "state": "PENDING_CREATION"}),
        ("actor_state", "a1", "ALIVE", {"worker_id": "w1"}),
        ("job_state", "drv-1", "RUNNING", {}),
        ("lineage", "o:t1:0", {"spec": b"blob"}),
    ]
    for e in entries:
        j.append(e)
    j.close()
    assert _journal(tmp_path).replay() == entries


def test_journal_torn_tail_truncated_and_recovered(tmp_path, capsys):
    j = _journal(tmp_path)
    j.append(("actor_register", {"actor_id": "a1"}))
    j.append(("actor_state", "a1", "ALIVE", {}))
    j.close()
    # Simulate a head SIGKILLed mid-append: a length header with a
    # truncated body lands after the last complete record.
    import struct

    with open(j.path, "ab") as f:
        f.write(struct.pack("<II", 500, 12345) + b"only-part-of-the-body")
    size_torn = (tmp_path / "snap.pkl.journal").stat().st_size
    replayed = _journal(tmp_path).replay()
    assert replayed == [
        ("actor_register", {"actor_id": "a1"}),
        ("actor_state", "a1", "ALIVE", {}),
    ]
    assert "torn tail" in capsys.readouterr().err
    # The tear was truncated so later appends don't land after garbage.
    assert (tmp_path / "snap.pkl.journal").stat().st_size < size_torn
    j2 = _journal(tmp_path)
    j2.append(("actor_state", "a1", "DEAD", {}))
    j2.close()
    assert len(_journal(tmp_path).replay()) == 3


def test_journal_foreign_session_refused(tmp_path):
    j = _journal(tmp_path, "mine")
    j.append(("actor_register", {"actor_id": "a1"}))
    j.close()
    assert _journal(tmp_path, "theirs").replay() == []
    # ... but the rightful owner still replays it.
    assert len(_journal(tmp_path, "mine").replay()) == 1


def test_journal_version_mismatch_refused_loudly(tmp_path, capsys):
    import pickle
    import struct
    import zlib

    hdr = pickle.dumps({"session": "s1", "journal_version": 999})
    rec = pickle.dumps(("actor_register", {"actor_id": "a1"}))
    with open(str(tmp_path / "snap.pkl.journal"), "wb") as f:
        for blob in (hdr, rec):
            f.write(struct.pack("<II", len(blob), zlib.crc32(blob)) + blob)
    assert _journal(tmp_path).replay() == []
    assert "REFUSING journal replay" in capsys.readouterr().err
    import os

    assert os.path.exists(str(tmp_path / "snap.pkl.journal") + ".refused")


def test_journal_fsync_policy(tmp_path, monkeypatch):
    from ray_tpu._private import config

    monkeypatch.setenv("RAY_TPU_GCS_JOURNAL_FSYNC", "2")
    # Per-append visibility requires sync mode (flush_us=0): the policy
    # counts ENTRIES either way, but group commit applies it at flush
    # boundaries (see test_journal_group_commit_fsync_policy).
    monkeypatch.setenv("RAY_TPU_GCS_JOURNAL_FLUSH_US", "0")
    config._values.pop("gcs_journal_fsync", None)
    config._values.pop("gcs_journal_flush_us", None)
    j = _journal(tmp_path)
    try:
        # fsync every 2nd append: False, True, False, True...
        assert j.append(("a", 1)) is False
        assert j.append(("a", 2)) is True
        assert j.append(("a", 3)) is False
        assert j.append(("a", 4)) is True
    finally:
        j.close()
        config._values.pop("gcs_journal_fsync", None)
        config._values.pop("gcs_journal_flush_us", None)


def test_journal_group_commit_batches_writes_preserving_order(tmp_path, monkeypatch):
    """Entries staged within the flush window land as ONE physical write,
    in append order, with EVERY kind present — the 'batched path silently
    drops an entry kind' hazard the journal-coverage lint guards
    statically, proven dynamically here."""
    from ray_tpu._private import config

    monkeypatch.setenv("RAY_TPU_JOURNAL_FLUSH_US", "50000")
    config._values.pop("gcs_journal_flush_us", None)
    j = _journal(tmp_path)
    try:
        entries = [
            ("actor_register", {"actor_id": "a1"}),
            ("lineage", "o:1", "spec"),
            ("lease", "grant", "tl-1", "key", "w1", "n1", {"CPU": 1.0}),
            ("job_state", "j1", "RUNNING", {}),
            ("lease", "revoke", "tl-1", "idle-timeout"),
            ("function", "fn-1", b"blob"),
        ]
        for e in entries:
            j.append(e)
        assert j.entries == len(entries)
        assert j.writes == 0  # staged, not yet flushed
        j.flush()
        assert j.writes == 1, "group commit did not coalesce the batch"
        assert j.replay() == entries  # order + every kind intact
    finally:
        j.close()
        config._values.pop("gcs_journal_flush_us", None)


def test_journal_group_commit_linger_flushes_without_explicit_flush(
    tmp_path, monkeypatch
):
    from ray_tpu._private import config

    monkeypatch.setenv("RAY_TPU_JOURNAL_FLUSH_US", "2000")
    config._values.pop("gcs_journal_flush_us", None)
    import time

    j = _journal(tmp_path)
    try:
        j.append(("actor_register", {"actor_id": "a1"}))
        deadline = time.monotonic() + 5
        while j.writes == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert j.writes == 1, "linger sweep never flushed the batch"
        # A fresh journal object (restart shape) sees the entry on disk.
        assert _journal(tmp_path).replay() == [
            ("actor_register", {"actor_id": "a1"})
        ]
    finally:
        j.close()
        config._values.pop("gcs_journal_flush_us", None)


def test_journal_group_commit_fsync_policy(tmp_path, monkeypatch):
    """Under group commit the fsync policy counts ENTRIES but applies at
    flush boundaries: a batch crossing the threshold syncs once."""
    from ray_tpu._private import config

    monkeypatch.setenv("RAY_TPU_GCS_JOURNAL_FSYNC", "2")
    monkeypatch.setenv("RAY_TPU_JOURNAL_FLUSH_US", "50000")
    config._values.pop("gcs_journal_fsync", None)
    config._values.pop("gcs_journal_flush_us", None)
    j = _journal(tmp_path)
    try:
        for i in range(4):
            j.append(("a", i))
        assert j.flush() is True  # 4 entries >= 2: the flush synced
        assert j.fsyncs == 1
    finally:
        j.close()
        config._values.pop("gcs_journal_fsync", None)
        config._values.pop("gcs_journal_flush_us", None)


def test_journal_reset_compacts(tmp_path):
    j = _journal(tmp_path)
    j.append(("actor_register", {"actor_id": "a1"}))
    assert j.size_bytes() > 0
    j.reset()
    assert j.size_bytes() == 0
    assert _journal(tmp_path).replay() == []
    # A fresh journal after reset stamps a new header and keeps working.
    j.append(("actor_register", {"actor_id": "a2"}))
    j.close()
    assert _journal(tmp_path).replay() == [("actor_register", {"actor_id": "a2"})]


def test_journal_compacted_into_next_snapshot(tmp_path):
    """Runtime-level compaction: a journaled mutation is folded into the
    next snapshot tick and the journal resets — restore then sees it in
    the SNAPSHOT (and a replayed empty journal), not the journal."""
    from ray_tpu._private.gcs import ActorInfo
    from ray_tpu._private.runtime import Runtime
    from ray_tpu._private.task_spec import TaskSpec

    snap_path = str(tmp_path / "head-snap")
    rt = Runtime(num_cpus=1, session_name="jcompact", snapshot_path=snap_path)
    try:
        spec = TaskSpec(
            task_id="t1", name="mk", fn_id="f", args_blob=b"",
            actor_id="act1", is_actor_creation=True,
        )
        rt.state.register_actor(
            ActorInfo(actor_id="act1", name=None, max_restarts=1, creation_spec=spec)
        )
        assert rt._journal.size_bytes() > 0, "mutation must hit the journal"
        rt._write_snapshot()
        assert rt._journal.size_bytes() == 0, "snapshot must compact the journal"
        snap = rt._snapshot_storage.load("jcompact")
        assert any(a["actor_id"] == "act1" for a in snap["actors"])
    finally:
        rt.shutdown()


def test_function_exports_survive_head_death_via_journal_only(tmp_path):
    """PR-4 residual closed: a function exported AFTER the last snapshot
    tick survives a hard head death via the journal, so a lineage
    re-execution right after restart can resolve the fn blob instead of
    failing "unknown function"."""
    from ray_tpu._private.runtime import Runtime

    snap_path = str(tmp_path / "head-snap")
    rt = Runtime(num_cpus=1, session_name="jfnexp", snapshot_path=snap_path)
    # Freeze the snapshot document: only the journal may carry the export.
    rt._write_snapshot = lambda: None
    rt.state.export_function("fn-under-test", b"the-blob")
    # Same-blob re-export must not re-journal (size bound on hot paths).
    size_after_first = rt._journal.size_bytes()
    rt.state.export_function("fn-under-test", b"the-blob")
    assert rt._journal.size_bytes() == size_after_first
    # The kill lands after the group-commit linger (a kill inside it
    # loses that window by contract; on a loaded box the flusher thread
    # may not have run before rt2 replays).
    rt._journal.flush()
    # Hard death: no shutdown, no final snapshot.
    rt._shutdown = True
    rt.listener.close()

    rt2 = Runtime(num_cpus=1, session_name="jfnexp", snapshot_path=snap_path)
    try:
        assert rt2.state.get_function("fn-under-test") == b"the-blob"
    finally:
        rt2.shutdown()


def test_runtime_restores_anonymous_actor_from_journal_only(tmp_path):
    """An ANONYMOUS actor registered+ALIVE'd after the last snapshot tick
    survives a hard head death purely via the journal (the PR-1 gap:
    these records used to die with the head)."""
    from ray_tpu._private.gcs import ALIVE, RESTARTING, ActorInfo
    from ray_tpu._private.runtime import Runtime
    from ray_tpu._private.task_spec import TaskSpec

    snap_path = str(tmp_path / "head-snap")
    rt = Runtime(num_cpus=1, session_name="jrestore", snapshot_path=snap_path)
    # Freeze the snapshot document: from here on ONLY the journal records
    # mutations (pins that the restore below is journal-driven, not a
    # lucky snapshot tick).
    rt._write_snapshot = lambda: None
    spec = TaskSpec(
        task_id="t1", name="mk", fn_id="f", args_blob=b"",
        actor_id="anon1", is_actor_creation=True,
    )
    rt.state.register_actor(
        ActorInfo(actor_id="anon1", name=None, max_restarts=3, creation_spec=spec)
    )
    rt.state.set_actor_state("anon1", ALIVE, worker_id="w9", node_id="n1")
    rt._journal.flush()  # past the linger window, as above
    # Hard death: no shutdown, no final snapshot — only the journal knows.
    rt._shutdown = True
    rt.listener.close()

    rt2 = Runtime(num_cpus=1, session_name="jrestore", snapshot_path=snap_path)
    try:
        info = rt2.state.get_actor("anon1")
        assert info is not None
        assert info.state == RESTARTING
        assert info.worker_id == "w9"  # adoption binding preserved
        assert info.max_restarts == 3
        assert "anon1" in rt2._restored_actors
    finally:
        rt2.shutdown()
