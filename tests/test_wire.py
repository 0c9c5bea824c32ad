"""Control-plane framing tests (wire.py).

ray: src/ray/protobuf/ — the reference's control plane is typed and
versioned; these tests prove ours rejects wrong-version peers at the
handshake with a clean error (VERDICT item-9 'done' gate), validates
message schemas at the boundary, and — since protocol v2 — coalesces
frames correctly: batch round-trips in order, whole-batch rejection of a
malformed sub-frame, truncated-batch detection, per-sub-frame fault
drops, and the sender-side serialization idiom under concurrency.
"""

import pickle
import struct
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import faults, wire


@pytest.fixture
def pipe_pair():
    from multiprocessing.connection import Pipe

    a, b = Pipe()
    sender, receiver = wire.BatchingConn(a), wire.wrap(b)
    yield sender, receiver
    sender.close()
    receiver.close()


def test_encode_decode_roundtrip():
    for msg in [
        ("refop", "add", "o-1"),
        ("reply", 7, True, {"x": 1}),
        ("heartbeat",),
        b"raw-kv-bytes",
        None,
    ]:
        assert wire.decode(wire.encode(msg)) == msg


def test_unknown_kind_rejected():
    bad = wire.encode(("totally_bogus_kind", 1))
    with pytest.raises(wire.ProtocolError, match="unknown control message"):
        wire.decode(bad)


def test_arity_and_type_validation():
    with pytest.raises(wire.ProtocolError, match="fields"):
        wire.decode(wire.encode(("refop", "add")))  # missing oid
    with pytest.raises(wire.ProtocolError, match="expected str"):
        wire.decode(wire.encode(("refop", 123, "o-1")))


def test_version_mismatch_clean_error():
    frame = bytearray(wire.encode(("heartbeat",)))
    struct.pack_into("<H", frame, 2, wire.PROTOCOL_VERSION + 1)
    with pytest.raises(wire.ProtocolError, match="version mismatch"):
        wire.decode(bytes(frame))
    with pytest.raises(wire.ProtocolError, match="bad magic"):
        wire.decode(b"ZZ\x01\x00" + b"x")


def test_version_mismatch_names_both_versions():
    """A v1 peer against this v3 process: the error names BOTH versions so
    the operator knows which side to upgrade."""
    frame = bytearray(wire.encode(("heartbeat",)))
    struct.pack_into("<H", frame, 2, 1)
    with pytest.raises(wire.ProtocolError, match=r"peer speaks v1.*speaks v3"):
        wire.decode(bytes(frame))
    # Batch frames carry the same version fence.
    batch = bytearray(wire.encode_batch([pickle.dumps(("heartbeat",))]))
    struct.pack_into("<H", batch, 2, 1)
    with pytest.raises(wire.ProtocolError, match=r"peer speaks v1.*speaks v3"):
        wire.decode_frames(bytes(batch))


# ---------------------------------------------------------------------------
# v2 batch frames + BatchingConn


def test_batch_roundtrip_in_order(pipe_pair):
    sender, receiver = pipe_pair
    msgs = [("refop", "add", f"o-{i}") for i in range(17)] + [
        ("done", "t-1", [], None),
        ("heartbeat",),
    ]
    for m in msgs:
        sender.send(m)
    sender.flush()
    got = [receiver.recv() for _ in range(len(msgs))]
    assert got == msgs  # in-order dispatch through the existing recv path
    assert receiver.pending_frames() == 0
    # One pending message flushes as a plain frame (no batch envelope).
    sender.send(("heartbeat",))
    sender.flush()
    assert receiver.recv() == ("heartbeat",)


def test_batch_poll_reports_buffered_subframes(pipe_pair):
    sender, receiver = pipe_pair
    for i in range(3):
        sender.send(("refop", "add", f"o-{i}"))
    sender.flush()
    assert receiver.recv() == ("refop", "add", "o-0")
    # The socket is drained but two sub-frames are buffered: poll must
    # report them or drain loops would strand the tail behind epoll.
    assert receiver.pending_frames() == 2
    assert receiver.poll(0)
    assert receiver.recv()[2] == "o-1"
    assert receiver.recv()[2] == "o-2"


def test_batch_size_threshold_flushes_without_explicit_flush():
    from multiprocessing.connection import Pipe

    a, b = Pipe()
    sender, receiver = wire.BatchingConn(a, batch_bytes=256), wire.wrap(b)
    try:
        n = 0
        while not receiver.poll(0):  # size trigger fires on its own
            sender.send(("refop", "add", f"object-{n:06d}"))
            n += 1
            assert n < 100, "size threshold never flushed"
        got = [receiver.recv()]
        while receiver.poll(0) or receiver.pending_frames():
            got.append(receiver.recv())
        assert [g[2] for g in got] == [f"object-{i:06d}" for i in range(len(got))]
        assert sender.flush_reasons.get("size", 0) >= 1
    finally:
        sender.close()
        receiver.close()


def test_linger_flush_delivers_without_explicit_flush(pipe_pair):
    sender, receiver = pipe_pair
    sender.send(("heartbeat",))
    # No explicit flush: the background linger sweep (RAY_TPU_WIRE_FLUSH_US
    # default ~200µs) must deliver it within a beat.
    deadline = time.monotonic() + 5.0
    while not receiver.poll(0.05):
        assert time.monotonic() < deadline, "linger flusher never fired"
    assert receiver.recv() == ("heartbeat",)


def test_batch_malformed_subframe_rejects_whole_batch(pipe_pair):
    """One bad sub-frame rejects the WHOLE batch at the boundary: no
    prefix of it is dispatched (validate-all-then-deliver)."""
    sender, receiver = pipe_pair
    bodies = [
        pickle.dumps(("refop", "add", "o-1"), protocol=5),
        pickle.dumps(("totally_bogus_kind", 1), protocol=5),
        pickle.dumps(("refop", "add", "o-2"), protocol=5),
    ]
    sender.send_bytes(wire.encode_batch(bodies))
    with pytest.raises(wire.ProtocolError, match="unknown control message"):
        receiver.recv()
    assert receiver.pending_frames() == 0  # nothing partially dispatched

    bad_arity = [pickle.dumps(("refop", "add"), protocol=5)]
    sender.send_bytes(wire.encode_batch(bad_arity))
    with pytest.raises(wire.ProtocolError, match="fields"):
        receiver.recv()


def test_truncated_batch_is_clean_protocol_error():
    """The torn-stream shape a mid-flush sender crash leaves behind: the
    receiver must fail with ProtocolError, never dispatch a prefix."""
    bodies = [pickle.dumps(("refop", "add", f"o-{i}"), protocol=5) for i in range(4)]
    buf = wire.encode_batch(bodies)
    for cut in (len(buf) - 1, len(buf) // 2, 9):
        with pytest.raises(wire.ProtocolError, match="truncated batch"):
            wire.decode_frames(buf[:cut])
    # Trailing garbage is just as torn as a short body.
    with pytest.raises(wire.ProtocolError, match="trailing bytes"):
        wire.decode_frames(buf + b"xx")


def test_recv_fault_drop_hits_individual_subframes(pipe_pair):
    """A wire.recv drop clause drops ONE sub-frame of a batch, not the
    whole batch — the pre-batching per-frame semantics."""
    sender, receiver = pipe_pair
    for m in [("refop", "add", "o-1"), ("done", "t-1", [], None),
              ("refop", "add", "o-2")]:
        sender.send(m)
    sender.flush()
    faults.configure("wire.recv:drop@match=^done")
    try:
        got = [receiver.recv(), receiver.recv()]
    finally:
        faults._reset_for_tests()
    assert got == [("refop", "add", "o-1"), ("refop", "add", "o-2")]


def test_send_fault_drop_hits_individual_messages(pipe_pair):
    sender, receiver = pipe_pair
    faults.configure("wire.send:drop@match=^done")
    try:
        for m in [("refop", "add", "o-1"), ("done", "t-1", [], None),
                  ("refop", "add", "o-2")]:
            sender.send(m)
        sender.flush()
    finally:
        faults._reset_for_tests()
    assert receiver.recv() == ("refop", "add", "o-1")
    assert receiver.recv() == ("refop", "add", "o-2")
    assert receiver.pending_frames() == 0


def test_flush_fault_drop_loses_whole_batch(pipe_pair, monkeypatch):
    """wire.flush is the physical-write hazard: a drop there loses the
    whole coalesced run (one physical message now), and the sender moves
    on cleanly."""
    sender, receiver = pipe_pair
    # Explicit flushes only: if this thread loses the CPU for longer than
    # the 200 us linger between the two sends, the background sweep
    # flushes "lost-1" alone, takes the nth=1 drop, and "lost-2" arrives.
    monkeypatch.setattr(wire, "_note_dirty", lambda bc: None)
    faults.configure("wire.flush:drop@nth=1")
    try:
        sender.send(("refop", "add", "lost-1"))
        sender.send(("refop", "add", "lost-2"))
        sender.flush()  # dropped whole
        sender.send(("refop", "add", "kept"))
        sender.flush()
    finally:
        faults._reset_for_tests()
    assert receiver.recv() == ("refop", "add", "kept")


def test_batching_disabled_is_passthrough():
    from multiprocessing.connection import Pipe

    a, b = Pipe()
    sender, receiver = wire.BatchingConn(a, batch_bytes=0), wire.wrap(b)
    try:
        sender.send(("heartbeat",))  # no flush needed: direct write
        assert receiver.poll(1.0)
        assert receiver.recv() == ("heartbeat",)
    finally:
        sender.close()
        receiver.close()


def test_broken_flush_marks_conn_and_drain_pending_recovers():
    from multiprocessing.connection import Pipe

    a, b = Pipe()
    sender = wire.BatchingConn(a)
    sender.send(("refop", "add", "o-stranded"))
    b.close()
    a.close()
    with pytest.raises((OSError, ValueError)):
        sender.flush()
    # Once a flush failed, sends fail AT THE CALL (the pre-batching
    # contract oneway backlogs rely on) ...
    with pytest.raises(OSError):
        sender.send(("heartbeat",))
    # ... and the stranded tail is recoverable for replay on a new conn.
    assert sender.drain_pending() == [("refop", "add", "o-stranded")]


def test_concurrent_senders_and_flusher_serialize_on_send_lock(pipe_pair):
    """The flusher + N sender threads share one BatchingConn: frames must
    never interleave or tear on the wire (the TypedConn send-lock
    serialization idiom), and per-sender order must hold."""
    sender, receiver = pipe_pair
    n_threads, n_msgs = 4, 200
    errors = []

    def pump(tid):
        try:
            for i in range(n_msgs):
                sender.send(("refop", "add", f"t{tid}-{i}"))
                if i % 17 == 0:
                    sender.flush()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=pump, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    got = []
    while len(got) < n_threads * n_msgs:
        if not receiver.poll(5.0):
            break
        got.append(receiver.recv())
    for t in threads:
        t.join()
    sender.flush()
    while (receiver.pending_frames() or receiver.poll(0.2)) and len(got) < n_threads * n_msgs:
        got.append(receiver.recv())
    assert not errors
    assert len(got) == n_threads * n_msgs
    per_thread = {t: [] for t in range(n_threads)}
    for msg in got:
        assert msg[0] == "refop" and msg[1] == "add"  # intact, validated
        tid, i = msg[2][1:].split("-")
        per_thread[int(tid)].append(int(i))
    for t in range(n_threads):
        assert per_thread[t] == list(range(n_msgs))  # per-sender FIFO


@ray_tpu.remote
def _noop_task():
    return None


@ray_tpu.remote(num_cpus=0.05)
class _SubmitClient:
    """Worker-side client, the multi_client_tasks_async shape: its tasks
    ride head-granted leases + direct peer push, so the hot frames are
    its own pcall stream and the executors' pdone streams."""

    def run_tasks(self, n, window):
        refs = []
        for _ in range(n):
            refs.append(_noop_task.remote())
            if len(refs) >= window:
                ray_tpu.get(refs, timeout=120)
                refs = []
        if refs:
            ray_tpu.get(refs, timeout=120)
        return n

    def wire_stats(self):
        from ray_tpu._private import wire as w

        return w.stats()


def _cluster_writes_for_shape(batch_bytes: int):
    """Run the multi-client shape on a fresh session and return
    (cluster_physical_writes, cluster_logical_frames, n_tasks, metrics)
    — wire counters summed over the head and every worker process (the
    deterministic measurement: counters, not wall-clock, so host noise
    is irrelevant)."""
    import time as _time

    ray_tpu.init(
        num_cpus=4,
        _system_config={"wire_batch_bytes": batch_bytes, "wire_stats": 1},
    )
    try:
        from ray_tpu._private import wire as w
        from ray_tpu.util import state as state_api

        # The driver/head process's counters are cumulative across the
        # whole pytest process: delta them from here so only THIS
        # session's writes count (worker processes are fresh per session).
        head0 = w.stats()
        clients = [_SubmitClient.remote() for _ in range(2)]
        ray_tpu.get([c.run_tasks.remote(1, 1) for c in clients], timeout=120)
        n_tasks = sum(
            ray_tpu.get(
                [c.run_tasks.remote(150, 50) for c in clients], timeout=300
            )
        )
        # Worker snapshots ride the 0.5s events ticker: give every process
        # two beats to report its final (now-stable) counters.
        _time.sleep(1.4)
        metrics = state_api.cluster_metrics()
        for c in clients:
            ray_tpu.kill(c)
    finally:
        ray_tpu.shutdown()
    return (
        metrics["wire_physical_writes"] - head0["physical_writes"],
        metrics["wire_logical_frames"] - head0["logical_frames"],
        n_tasks,
        metrics,
    )


def test_batching_halves_physical_writes_per_task():
    """The acceptance bar, measured deterministically by the wire-stats
    counters: on the multi_client_tasks_async shape the batched control
    plane must do >=2x fewer physical writes per task than the unbatched
    baseline (RAY_TPU_WIRE_BATCH_BYTES=0) while carrying at least as
    many logical frames."""
    from ray_tpu._private import config as _cfg

    try:
        ub_writes, ub_frames, n, _ = _cluster_writes_for_shape(batch_bytes=0)
        b_writes, b_frames, n2, metrics = _cluster_writes_for_shape(
            batch_bytes=64 * 1024
        )
    finally:
        # Frozen _system_config overrides outlive the session: restore the
        # defaults explicitly so later tests see stock knobs.
        _cfg.set_system_config({"wire_batch_bytes": 64 * 1024, "wire_stats": 0})
    assert n == n2 == 300
    # Per-task cost: subtract nothing — boot frames dilute BOTH sides, so
    # the ratio bar is conservative.
    assert b_frames >= 0.8 * ub_frames  # same logical work (± telemetry noise)
    assert ub_writes >= 2.0 * b_writes, (
        f"batching saved too little: {ub_writes / n:.2f} -> "
        f"{b_writes / n2:.2f} cluster physical writes/task"
    )
    # Exposure plumbing: per-conn flush reasons aggregate too.
    assert metrics["wire_head_physical_writes"] > 0
    assert metrics.get("wire_flush_explicit", 0) > 0


def test_wire_stats_hidden_without_knob(ray_start_regular):
    from ray_tpu.util import state as state_api

    assert "wire_physical_writes" not in state_api.cluster_metrics()


def test_head_rejects_wrong_version_peer(ray_start_regular):
    """A peer that authenticates but speaks a different protocol version
    gets a clean ('protocol_error', head_version, why) reply and a closed
    connection — not an unpickling traceback mid-handler."""
    from multiprocessing import connection as mpc

    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()
    host, port = rt.address
    raw = mpc.Client((host, port), authkey=rt._authkey)
    try:
        frame = bytearray(wire.encode(("ready", "w-fake", 1, None, None)))
        struct.pack_into("<H", frame, 2, wire.PROTOCOL_VERSION + 9)
        raw.send_bytes(bytes(frame))
        reply = wire.decode(raw.recv_bytes())
        assert reply[0] == "protocol_error"
        assert reply[1] == wire.PROTOCOL_VERSION
        assert "version mismatch" in reply[2]
        # The head closes the conn after the rejection.
        with pytest.raises((EOFError, OSError)):
            raw.recv_bytes()
    finally:
        raw.close()
