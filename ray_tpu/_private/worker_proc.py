"""Worker process: task execution loop.

Analogue of the reference's worker main
(ray: python/ray/_private/workers/default_worker.py entering
CoreWorkerProcess::RunTaskExecutionLoop, python/ray/_raylet.pyx:1600) and the
executor-side scheduling queues
(ray: src/ray/core_worker/transport/actor_scheduling_queue.h et al.):

  * a recv thread demultiplexes driver messages (tasks, replies, kill);
  * an executor runs tasks -- single-threaded FIFO for plain tasks and
    default actors (ordered, like ActorSchedulingQueue), a thread pool for
    max_concurrency>1 (OutOfOrderActorSchedulingQueue), and a persistent
    asyncio loop for async actors (ray: concurrency_group_manager.h/fiber.h);
  * large results are written straight into the host shm store (zero-copy
    hand-off to the owner, like plasma Seal) -- only metadata rides the
    control connection.
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time

from ray_tpu._private import lock_watchdog
import traceback
from collections import OrderedDict
from typing import Any, Dict, Optional

from ray_tpu._private import serialization as ser
from ray_tpu._private.store import ShmStore, inline_threshold
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu.exceptions import TaskError


class WorkerRuntime:
    """The in-worker runtime: proxies API calls to the owner/driver.

    Plays the role of the reference's CoreWorker as linked into a worker
    process (ray: src/ray/core_worker/core_worker.h:284) -- get/put/submit
    flow back to the owner over the control connection, except shm reads
    which go straight to tmpfs.
    """

    def __init__(self, conn, conn_lock, session_name: str, worker_id: str,
                 authkey: bytes = b"", store_dir: Optional[str] = None):
        self.conn = conn
        self.conn_lock = conn_lock
        self.worker_id = worker_id
        self.authkey = authkey
        # Direct worker<->worker transport (peer.py): installed by
        # worker_main after the peer server binds.  None only in tests
        # that construct a bare WorkerRuntime.
        self.direct = None
        self._puts_unacked = 0
        self._puts_lock = lock_watchdog.make_lock("WorkerRuntime._puts_lock")  # max_concurrency>1 puts race
        # RAY_TPU_STORE_DIR scopes the store to THIS worker's node (set by
        # its node daemon); without it (head-node workers) the session
        # default resolves to the head store.  Objects on other nodes are
        # never path-reachable — they arrive via the transfer plane.
        self.shm = ShmStore(
            session_name,
            dir_path=store_dir or os.environ.get("RAY_TPU_STORE_DIR"),
        )
        self.session_name = session_name
        # Guards _pulls_inflight only (held for dict ops, never across
        # the wire): oid -> Event of the in-flight leader pull.
        self._pull_lock = lock_watchdog.make_lock("WorkerRuntime._pull_lock")
        self._pulls_inflight: Dict[str, Any] = {}
        # Remote (non-co-located) drivers cannot seal into any node store
        # the cluster can read: their puts always ride the control conn.
        self.force_inline_puts = False
        self._req_counter = 0
        self._req_lock = lock_watchdog.make_lock("WorkerRuntime._req_lock")
        self._pending: Dict[int, queue.Queue] = {}
        self._fn_cache: Dict[str, Any] = {}
        self.current_actor = None  # instance, when this worker hosts an actor
        self.current_actor_id: Optional[str] = None
        # Creation TaskSpec of the hosted actor: re-announced with the
        # reconnect hello so a restarted head can rebuild the actor record
        # even when its journal was lost (reconciliation handshake).
        self.current_actor_spec = None
        # Batched task-event reporter (installed by worker_main): the
        # direct transport records lease-dispatch RUNNING events here.
        self.task_event_sink = None
        # worker_main's boot phases [(span name, start, end), ...], until
        # the first traced task turns them into spans (_execute).
        self.boot_spans = None
        # Relayed tasks received but not yet replied (queued + executing):
        # the reconnect hello announces these so the head can re-drive
        # exactly what the dead conn lost — a task push that never
        # arrived, or a done frame that died in the socket.  Dict ops
        # are GIL-atomic; insertion order mirrors arrival order.
        self.relayed_pending: Dict[str, None] = {}
        # Oneways that failed during a head bounce, flushed on reconnect.
        self._oneway_backlog: list = []
        self._backlog_lock = lock_watchdog.make_lock("WorkerRuntime._backlog_lock")
        self._backlog_dropped = 0
        # Bumped by every SUCCESSFUL reconnect_recover: request() retries
        # use it to tell a healed-then-rebroken conn (fresh incident,
        # fresh window) from one continuous outage (budget runs out).
        self._conn_generation = 0
        # Attached drivers adopt the head's window (their own env may not
        # carry the knob); None = read the local config.
        self.reconnect_window_override: Optional[float] = None
        # Cross-process pubsub subscriptions: (channel, key) -> [cb].
        self._subs: Dict[tuple, list] = {}
        self._subs_lock = lock_watchdog.make_lock("WorkerRuntime._subs_lock")
        # Objects THIS process has seen materialized (resolved a value /
        # pulled a copy): a dep in this set is provably produced, so a
        # lease-dispatched task carrying it can be pushed — the executor
        # stages the bytes via the transfer plane without any deadlock
        # risk (the producer is done; nothing is starved).  Bounded LRU.
        self._known_ready: "OrderedDict[str, bool]" = OrderedDict()
        self._known_ready_lock = lock_watchdog.make_lock("WorkerRuntime._known_ready_lock")
        self.async_loop = None
        self._async_loop_lock = lock_watchdog.make_lock("WorkerRuntime._async_loop_lock")

    # -- request/reply to driver --------------------------------------------

    def _reconnect_window(self) -> float:
        if self.reconnect_window_override is not None:
            return self.reconnect_window_override
        from ray_tpu._private import config as _config

        return _config.get("reconnect_window_s")

    def request(self, op: str, payload: Any, timeout: Optional[float] = None) -> Any:
        """Request/reply to the owner.  In head-split mode a request that
        dies with the head conn is RE-SENT on the reconnected one (the
        restarted head's ops are idempotent by task/actor id), so a get()
        blocked across a head bounce resolves instead of erroring —
        ray: gcs_failover_worker_reconnect_timeout semantics."""
        import time as _time

        deadline = None
        last_err = None
        gen_at_err = None
        while True:
            try:
                return self._request_once(op, payload, timeout)
            except ConnectionError as e:
                window = self._reconnect_window()
                if window <= 0:
                    raise  # classic mode: conn loss is final
                now = _time.monotonic()
                # A fresh INCIDENT gets a fresh budget.  Two signals mark
                # one: a successful reconnect happened since the last
                # failure (the conn GENERATION moved — each head bounce
                # that heals must not eat into the next bounce's window;
                # a long-lived parked get that rides bounce after bounce
                # spaced under the window would otherwise accumulate into
                # a spurious give-up), or the last failure is simply old.
                gen = getattr(self, "_conn_generation", 0)
                if (
                    last_err is None
                    or gen != gen_at_err
                    or now - last_err > window + 10.0
                ):
                    deadline = now + window + 10.0
                gen_at_err = gen
                last_err = now
                if now > deadline:
                    # Say WHICH budget lapsed — "connection reset" alone
                    # reads like a missing retry, not an exhausted one.
                    raise ConnectionError(
                        f"request {op!r} still failing after riding the "
                        f"{window:.0f}s reconnect window: {e}"
                    ) from e
                _time.sleep(0.2)  # recv thread is swapping the conn

    def _request_once(self, op: str, payload: Any, timeout: Optional[float]) -> Any:
        from ray_tpu._private import wire as _wire

        with self._req_lock:
            self._req_counter += 1
            req_id = self._req_counter
            q: queue.Queue = queue.Queue(1)
            self._pending[req_id] = q
        try:
            with self.conn_lock:
                self.conn.send(("req", req_id, op, payload))
            # Flush-before-blocking-wait: the req (and every oneway
            # coalesced ahead of it — refops, seals) goes out as one
            # physical write before this thread parks on the reply.
            _wire.flush_conn(self.conn)
        except OSError as e:
            self._pending.pop(req_id, None)
            raise ConnectionError("head connection lost mid-send") from e
        ok, value = q.get(timeout=timeout)
        if not ok:
            raise value
        return value

    def oneway(self, msg: tuple, droppable: bool = False) -> None:
        """droppable=True marks telemetry (spans, task events): dropped on
        a dead conn instead of competing with seals/refops for the
        bounded ownership backlog."""
        with self.conn_lock:
            try:
                self.conn.send(msg)
            except OSError:
                if droppable:
                    return
                # Head away (restart window): hold the message — seals,
                # refops, and promotions carry ownership state the
                # restarted head must still learn.  Appended INSIDE the
                # conn_lock hold: the reconnect flush (also under
                # conn_lock) can't interleave, so a failed send can never
                # strand its message behind an already-finished flush.
                if self._reconnect_window() > 0:
                    with self._backlog_lock:
                        if len(self._oneway_backlog) < 4096:
                            self._oneway_backlog.append(msg)
                        else:
                            # Overflow is ownership-state LOSS: say so
                            # (once per burst) instead of silently eating
                            # seals/refops the restarted head needed.
                            self._backlog_dropped += 1
                            if self._backlog_dropped == 1:
                                print(
                                    "[ray_tpu] head-bounce backlog full: "
                                    "dropping control messages (seals/"
                                    "refops) — objects produced during "
                                    "this outage may be unresolvable",
                                    file=sys.stderr,
                                    flush=True,
                                )

    def _on_reply(self, req_id: int, ok: bool, value: Any) -> None:
        q = self._pending.pop(req_id, None)
        if q is not None:
            q.put((ok, value))

    # -- cross-process pubsub (pubsub.py remote delivery) --------------------

    def subscribe(self, channel: str, key, cb, once: bool = False) -> None:
        """Receive pushes for (channel, key) from the head's Publisher —
        key "*" = every key on the channel.  One head message per
        subscription, then events arrive push-style on this conn (no
        round trip per event; ray: subscriber.h:70).  once=True drops the
        subscription — on BOTH sides — after the first event (per-object
        channels like object_ready would otherwise accumulate forever)."""
        with self._subs_lock:
            self._subs.setdefault((channel, key), []).append((cb, once))
        self.oneway(("subscribe", channel, key, once))

    def unsubscribe(self, channel: str, key, cb=None) -> None:
        with self._subs_lock:
            lst = self._subs.get((channel, key))
            if lst is not None:
                if cb is None:
                    lst.clear()
                else:
                    lst[:] = [e for e in lst if e[0] is not cb]
                if not lst:
                    self._subs.pop((channel, key), None)
        self.oneway(("unsubscribe", channel, key))

    def _on_pub(self, channel: str, key, args: tuple) -> None:
        with self._subs_lock:
            exact = self._subs.get((channel, key), [])
            # key == "*" would alias `wild` to `exact` (double-fire +
            # double-consume); pub frames carry concrete keys, but guard.
            wild = self._subs.get((channel, "*"), []) if key != "*" else []
            fired = list(exact) + list(wild)
            # Consume once-subs from BOTH registries: a once+wildcard sub
            # fired here and must not fire on every later key forever.
            exact[:] = [e for e in exact if not e[1]]
            if not exact:
                self._subs.pop((channel, key), None)
            if key != "*":
                wild[:] = [e for e in wild if not e[1]]
                if not wild:
                    self._subs.pop((channel, "*"), None)
        for cb, _once in fired:
            try:
                cb(key, *args)
            except Exception:
                import traceback

                traceback.print_exc()

    def reconnect_recover(self, newconn, send_hello) -> bool:
        """ONE implementation of post-bounce session recovery (worker AND
        attached-driver reconnects): swap to the freshly-connected conn,
        send the re-registration hello, flush the oneway backlog (unsent
        tail restored on a second bounce), fail in-flight requests with
        the retriable ConnectionError, replay promotions + subscriptions.
        Returns False when the head bounced again mid-recovery (caller
        retries within its window)."""
        from ray_tpu._private import wire as _wire

        with self.conn_lock:
            # Frames the dead conn queued but never flushed (a batch flush
            # failing marks the conn broken and strands its pending run)
            # carry the same ownership state the backlog does — and they
            # are OLDER, so they replay first.  Replayed as RAW bodies:
            # unpickling here would run ObjectRef refcount hooks (transport
            # lock) under this conn lock — the watchdog-caught ABBA shape.
            stranded = getattr(self.conn, "drain_pending_bodies", lambda: [])()
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = newconn
            try:
                send_hello(newconn)
                _wire.flush_conn(newconn)
            except OSError:
                return False
            with self._backlog_lock:
                backlog, self._oneway_backlog = self._oneway_backlog, []
            try:
                while stranded:
                    newconn.send_body(stranded[0])
                    stranded.pop(0)
                while backlog:
                    newconn.send(backlog[0])
                    backlog.pop(0)
                _wire.flush_conn(newconn)
            except OSError:
                # Unsent tail goes back: ownership state must survive
                # repeated bounces.
                with self._backlog_lock:
                    self._oneway_backlog[:0] = backlog
                return False
            self._backlog_dropped = 0  # fresh overflow warning per burst
            # The swap succeeded: failures after this are a NEW incident.
            self._conn_generation = getattr(self, "_conn_generation", 0) + 1
        err = ConnectionError("head connection was reset (head restart)")
        for req_id in list(self._pending):
            q = self._pending.pop(req_id, None)
            if q is not None:
                q.put((False, err))
        if self.direct is not None:
            self.direct.replay_promotions()
            # Reconciliation handshake, caller leg: re-announce the direct
            # actor routes this process holds so the restarted head can
            # cross-check its rebuilt actor table (the hosting worker's
            # own hello carries the authoritative record).
            self.direct.announce_routes()
        self._replay_subscriptions()
        return True

    def actor_announcement(self):
        """Reconciliation payload for the reconnect hello: the live actor
        this worker hosts, creation spec included, so a restarted head can
        rebuild the record even when its journal was lost (None for
        stateless workers)."""
        if self.current_actor_id is None:
            return None
        return {
            "actor_id": self.current_actor_id,
            "creation_spec": self.current_actor_spec,
        }

    def _replay_subscriptions(self) -> None:
        """After a head bounce: the restarted head's registry is empty."""
        with self._subs_lock:
            entries = [
                (ck, all(once for _cb, once in lst))
                for ck, lst in self._subs.items()
                if lst
            ]
        for (channel, key), once in entries:
            self.oneway(("subscribe", channel, key, once))

    # -- object plane --------------------------------------------------------

    def ref_factory(self, id: str, owner: str | None):
        from ray_tpu._private.refs import ObjectRef

        return ObjectRef(id, owner)  # hooks installed in worker_main count it

    def borrow_ref(self, oid: str) -> None:
        """Add one reference on behalf of an in-flight direct call's args
        (released by unborrow_ref when the call completes)."""
        if self.direct is not None and self.direct.addref(oid):
            return
        self.oneway(("refop", "add", oid))

    def unborrow_ref(self, oid: str) -> None:
        if self.direct is not None and self.direct.decref(oid):
            return
        self.oneway(("refop", "del", oid))

    def ref_table_snapshot(self) -> dict:
        """This process's live-ref table (refs.py) with direct-transport
        ownership folded in — the refs_push payload (the worker leg of the
        cluster object ledger, telemetry.py ObjectLedger)."""
        import time as _time

        from ray_tpu._private import refs as refs_mod

        snap = refs_mod.snapshot_refs()
        owned: set = set()
        pinned: set = set()
        if self.direct is not None:
            with self.direct.lock:
                owned = set(self.direct.counts)
                pinned = {
                    oid
                    for oid, dr in self.direct.results.items()
                    if dr.event.is_set()
                }
        refs = {}
        for oid, rec in snap["refs"].items():
            refs[oid] = [rec[0], rec[1], oid in owned, oid in pinned]
        for oid in owned - set(refs):
            # Owned results whose caller-side ObjectRef is pre-counted
            # (constructed with _count=False before the table existed, or
            # held only by the transport cache) still belong in the table.
            refs[oid] = [1, None, True, oid in pinned]
        snap["refs"] = refs
        snap["pid"] = os.getpid()
        snap["t"] = _time.time()
        return snap

    def note_escaped(self, contained) -> None:
        """Serialize-time hook: any locally-owned direct result leaving this
        process must become visible to the head (promotion) so remote
        consumers can resolve it."""
        if self.direct is None or not contained:
            return
        for oid in contained:
            self.direct.mark_escaped(oid)

    def mark_known_ready(self, oid: str) -> None:
        with self._known_ready_lock:
            self._known_ready[oid] = True
            self._known_ready.move_to_end(oid)
            while len(self._known_ready) > 8192:
                self._known_ready.popitem(last=False)

    def known_materialized(self, oid: str) -> bool:
        """This process has direct evidence the object was produced (seen
        its value, or it sits in this node's store)."""
        with self._known_ready_lock:
            if oid in self._known_ready:
                return True
        return self.shm.contains(oid)

    def get_value(self, object_id: str, timeout: Optional[float] = None) -> Any:
        from ray_tpu.exceptions import ObjectLostError

        try:
            value = self._get_value(object_id, timeout)
        except ObjectLostError:
            # Invalidate: a stale known-ready entry would keep steering
            # lease-path submits at a dep whose bytes are gone (the
            # deadlock guard must see the loss, not the old success).
            with self._known_ready_lock:
                self._known_ready.pop(object_id, None)
            raise
        self.mark_known_ready(object_id)  # reached only on success
        return value

    def _get_value(self, object_id: str, timeout: Optional[float] = None) -> Any:
        # Fastest path: a result of one of OUR direct calls, cached locally.
        if self.direct is not None:
            if self.direct.ready_local(object_id) is not None:
                found, val = self.direct.get_local(object_id, timeout)
                if found:
                    return val
                # shm result on a remote node: resolve via the owner below.
        # Fast path: sealed segment already in this NODE's store.
        obj = self.shm.get(object_id)
        if obj is not None:
            return obj.deserialize(self.ref_factory)
        # The owner may spill the segment between its ("shm", None) reply
        # and our mmap; re-requesting makes the owner restore it from the
        # spill file (or reconstruct via lineage) — so a miss here is a
        # retry, not a loss.  One deadline covers all retries.
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        for _ in range(3):
            remaining = (
                None if deadline is None else max(deadline - _time.monotonic(), 0.0)
            )
            import queue as _q

            try:
                kind, data = self.request("get_object", object_id, timeout=remaining)
            except _q.Empty:
                from ray_tpu.exceptions import GetTimeoutError

                raise GetTimeoutError(f"get({object_id}) timed out")
            if kind == "inline":
                payload, bufs = ser.unpack(memoryview(data))
                return ser.deserialize(payload, bufs, self.ref_factory)
            if kind == "pull":
                remaining = (
                    None
                    if deadline is None
                    else max(deadline - _time.monotonic(), 0.01)
                )
                obj = self._pull(object_id, data, remaining)
                if obj is not None:
                    return obj.deserialize(self.ref_factory)
                continue  # every endpoint failed: re-ask the owner
            # kind == "shm": on this node's store
            obj = self.shm.get(object_id)
            if obj is not None:
                return obj.deserialize(self.ref_factory)
        from ray_tpu.exceptions import ObjectLostError

        raise ObjectLostError(object_id)

    def _pull(self, object_id: str, endpoints, timeout: Optional[float] = None):
        """Fetch a remote copy into this node's store via the transfer
        plane; one pull at a time per worker (pull-manager-style admission
        — concurrent arg resolutions of the same object would race the
        allocate anyway).  The endpoint list is the owner's TRANSFER PLAN:
        assigned feed first (possibly a mid-flight relay), sealed sources
        as fallback.  This pull's own board makes the node a relay feed
        the moment bytes start landing.  `timeout` carries the caller's
        remaining get() budget so a user timeout is honored over the
        transfer default."""
        from ray_tpu._private import config as _cfg
        from ray_tpu._private.object_plane import pull_from_any

        import threading as _threading

        cap = _cfg.get("object_transfer_timeout_s")
        timeout = cap if timeout is None else min(timeout, cap)
        # Per-OBJECT dedup instead of one worker-wide pull lock: pulls of
        # DIFFERENT objects run concurrently (multi-arg resolution
        # overlaps its transfers), while a second thread wanting the SAME
        # object parks on the leader's event — and no lock is ever held
        # across the wire (the old whole-pull lock showed up as multi-
        # second watchdog holds once relays made long transfers common).
        with self._pull_lock:
            evt = self._pulls_inflight.get(object_id)
            leader = evt is None
            if leader:
                evt = _threading.Event()
                self._pulls_inflight[object_id] = evt
        if not leader:
            evt.wait(timeout)
            return self.shm.get(object_id)
        try:
            obj = self.shm.get(object_id)  # a sibling pull may have landed it
            if obj is not None:
                return obj
            r = pull_from_any(
                endpoints, self.authkey, object_id,
                self.shm.start_pull,
                timeout=timeout,
            )
            if r is None:
                return None
            n, via = r
            # Report the new copy (with its packed size + transfer path)
            # so the directory serves this node locally from now on,
            # releases the plan slot, deletes the copy when the object is
            # freed, and — for head-node workers — enters it in the owner
            # store's capacity accounting.  A "local" landing (sibling
            # sealed it under us) moved no bytes and reports nothing.
            if via != "local":
                self.oneway(("object_copied", object_id, n, via))
            return self.shm.get(object_id)
        finally:
            with self._pull_lock:
                self._pulls_inflight.pop(object_id, None)
            evt.set()

    def put_value(self, value: Any) -> str:
        """Store a value under a locally-minted id with fire-and-forget
        sealing (the owner learns of it via a oneway riding the same FIFO
        conn as every later message naming the id — so a submit carrying
        the ref always lands after the seal).  A sync request every 64
        unacked puts bounds the backlog a put-loop can build up (the
        backpressure the old request-per-put path provided implicitly)."""
        from ray_tpu._private import ids as _ids

        payload, buffers, contained = ser.serialize(value)
        self.note_escaped(contained)
        size = len(payload) + sum(len(b.raw()) for b in buffers)
        oid = _ids.object_id()
        if size >= inline_threshold() and not self.force_inline_puts:
            packed = self.shm.create(oid, payload, buffers)
            from ray_tpu._private import telemetry as _telemetry

            _telemetry.count_copy("seal", packed)
            self.oneway(("seal_ow", oid, packed, contained))
        else:
            self.oneway(("put_ow", oid, bytes(ser.pack(payload, buffers)), contained))
        with self._puts_lock:
            self._puts_unacked += 1
            flush = self._puts_unacked >= 64
            if flush:
                self._puts_unacked = 0
        if flush:
            self.request("sync", None)
        return oid

    # -- function resolution -------------------------------------------------

    def resolve_function(self, fn_id: str, blob: Optional[bytes]):
        fn = self._fn_cache.get(fn_id)
        if fn is None:
            if blob is None:
                blob = self.request("get_function", fn_id)
            import cloudpickle

            fn = cloudpickle.loads(blob)
            self._fn_cache[fn_id] = fn
        return fn


_runtime: Optional[WorkerRuntime] = None

# Currently-executing task id, tracked with a ContextVar: isolated per
# thread (FIFO / pool executors) AND per asyncio task (async actors run
# interleaved coroutines on one loop thread, where a thread-local would
# bleed between concurrent requests).  Submissions made INSIDE a task read
# this to stamp their parent for trace trees.
import contextvars

_current_task: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "raytpu_current_task", default=None
)


def current_task_id() -> Optional[str]:
    return _current_task.get()


def get_worker_runtime() -> Optional[WorkerRuntime]:
    return _runtime


def _resolve_args(rt: WorkerRuntime, args_blob: bytes):
    from ray_tpu._private.refs import ObjectRef

    payload, bufs = ser.unpack(memoryview(args_blob))
    args, kwargs = ser.deserialize(payload, bufs, rt.ref_factory)
    args = tuple(rt.get_value(a.id) if isinstance(a, ObjectRef) else a for a in args)
    kwargs = {
        k: rt.get_value(v.id) if isinstance(v, ObjectRef) else v for k, v in kwargs.items()
    }
    return args, kwargs


def _store_results(rt: WorkerRuntime, spec: TaskSpec, out) -> list:
    if spec.num_returns == 1:
        out = [out]
    elif spec.num_returns == 0:
        out = []
    else:
        out = list(out)
        if len(out) != spec.num_returns:
            raise ValueError(
                f"task {spec.name} declared num_returns={spec.num_returns} "
                f"but returned {len(out)} values"
            )
    # Serialize EVERY result before sending any bookkeeping: a failure on
    # result k after result 0's guards went out would leak those borrows
    # (the task then reports an error and no release path runs).
    serialized = []
    for i, value in enumerate(out):
        oid = f"o:{spec.task_id}:{i}"
        serialized.append((oid, ser.serialize(value)))
    results = []
    guarded: list = []
    try:
        for oid, (payload, buffers, contained) in serialized:
            rt.note_escaped(contained)  # refs we own, leaving via our result
            # Guard borrows, sent WHILE the contained refs are still alive
            # in this frame: the executor's own ObjectRefs die at frame
            # teardown (their refop dels hit the conn before the done/seal
            # messages), so without a preceding add the owner could free a
            # contained child in the del→done window.  The owner releases
            # the guard once its own stored-object borrow is in place
            # (_on_task_done / direct_seal); for caller-owned inline direct
            # results the guard IS the caller-cache borrow, released when
            # the cache entry drops.
            for c in contained:
                rt.oneway(("refop", "add", c))
                guarded.append(c)
            size = len(payload) + sum(len(b.raw()) for b in buffers)
            if size >= inline_threshold():
                packed = rt.shm.create(oid, payload, buffers)
                from ray_tpu._private import telemetry as _telemetry

                _telemetry.count_copy("seal", packed)
                results.append((oid, "shm", packed, contained))
            else:
                results.append(
                    (oid, "inline", bytes(ser.pack(payload, buffers)), contained)
                )
    except BaseException:
        for c in guarded:  # storage failed: balance the sent guards
            rt.oneway(("refop", "del", c))
        raise
    return results


def _execute(rt: WorkerRuntime, spec: TaskSpec, blob: Optional[bytes]):
    """Run one task/actor-method/creation; returns ("done", ...) message."""
    import contextlib

    from ray_tpu.util import tracing

    _ctx_token = _current_task.set(spec.task_id)
    stack = contextlib.ExitStack()
    trace_ctx = getattr(spec, "trace_ctx", None)
    if trace_ctx is not None:
        if rt.boot_spans:
            # The first traced work this worker gets (an actor's creation,
            # inside a fit()): its boot phases join that trace.  A worker
            # taken from the warm pool booted BEFORE the parent span began.
            booted, rt.boot_spans = rt.boot_spans, None
            for name, start, end in booted:
                tracing.record_span(
                    name, start, end, parent=trace_ctx,
                    attrs={"worker_id": rt.worker_id}, lifecycle=True,
                )
        # Adopt the submitter's context: this run span parents to its
        # submit span, and anything WE submit parents to this run
        # (ray: tracing_helper.py execute-side wrapper).  With tracing off
        # the context is adopted and no span recorded.
        stack.enter_context(
            tracing.span(
                f"run::{spec.name}",
                parent=trace_ctx,
                attrs={"task_id": spec.task_id, "worker_id": rt.worker_id},
            )
            if tracing.is_enabled()
            else tracing.adopt(trace_ctx)
        )
    try:
        if spec.is_actor_creation:
            cls = rt.resolve_function(spec.fn_id, blob)
            from ray_tpu._private import faults

            if faults.ENABLED:
                # Scope chaos clauses by the hosted actor class
                # (proc=actor:<Class>) — set BEFORE __init__ so creation
                # is inside the scope too.
                faults.set_process_tag(
                    f"worker:{rt.worker_id}:actor:{cls.__name__}"
                )
            args, kwargs = _resolve_args(rt, spec.args_blob)
            rt.current_actor = cls(*args, **kwargs)
            rt.current_actor_id = spec.actor_id
            rt.current_actor_spec = spec
            results = _store_results(rt, spec, None)
        elif spec.actor_id is not None:
            method = getattr(rt.current_actor, spec.method_name)
            args, kwargs = _resolve_args(rt, spec.args_blob)
            out = method(*args, **kwargs)
            if _is_coroutine(out):
                out = _run_on_actor_loop(rt, out)
            results = _store_results(rt, spec, out)
        else:
            fn = rt.resolve_function(spec.fn_id, blob)
            args, kwargs = _resolve_args(rt, spec.args_blob)
            out = fn(*args, **kwargs)
            if _is_coroutine(out):
                import asyncio

                out = asyncio.run(out)
            results = _store_results(rt, spec, out)
        return ("done", spec.task_id, results, None)
    except BaseException as e:  # noqa: BLE001 -- remote errors must be reported
        if isinstance(e, SystemExit):
            raise
        err = TaskError.from_exception(spec.name, e)
        import cloudpickle

        return ("done", spec.task_id, [], cloudpickle.dumps(err))
    finally:
        stack.close()  # end the run span (records it for the next flush)
        _current_task.reset(_ctx_token)


def _is_coroutine(x) -> bool:
    import inspect

    return inspect.iscoroutine(x)


def _run_on_actor_loop(rt: WorkerRuntime, coro):
    """Run a coroutine on the actor's persistent event loop (async actors).

    The task-id ContextVar is re-set INSIDE the wrapping coroutine: the
    loop thread has its own context, and each asyncio Task gets an isolated
    copy, so concurrent async methods keep distinct parents."""
    import asyncio

    if rt.async_loop is None:
        # Locked double-check: concurrent FIRST async calls (threaded
        # max_concurrency pool) racing this create would split the actor's
        # coroutines across two loops — asyncio primitives (Event, Lock)
        # created on one loop then awaited on the other raise
        # "bound to a different event loop".
        with rt._async_loop_lock:
            if rt.async_loop is None:
                loop = asyncio.new_event_loop()
                t = threading.Thread(
                    target=loop.run_forever, daemon=True, name="actor-asyncio"
                )
                t.start()
                rt.async_loop = loop
    task_id = current_task_id()

    async def _with_context():
        token = _current_task.set(task_id)
        try:
            return await coro
        finally:
            _current_task.reset(token)

    fut = asyncio.run_coroutine_threadsafe(_with_context(), rt.async_loop)
    return fut.result()


def worker_main(address, authkey: bytes, worker_id: str, session_name: str, env_vars):
    # Apply runtime-env vars FIRST, before any heavy import (so e.g.
    # JAX_PLATFORMS / XLA_FLAGS take effect in this process).
    if env_vars:
        os.environ.update(env_vars)
    # Before anything here can import jax (see compile_cache.py).
    from ray_tpu._private import compile_cache

    compile_cache.apply_default()
    # The worker's boot as (span name, start, end), recorded as lifecycle
    # spans once a traced task says whose they are (_execute).
    boot_spans: list = []
    boot_t = [time.time()]

    def boot_phase(name: str) -> None:
        now = time.time()
        boot_spans.append((name, boot_t[0], now))
        boot_t[0] = now

    if os.environ.get("RAY_TPU_FAULTHANDLER"):
        import faulthandler
        import signal as _sig

        faulthandler.register(_sig.SIGUSR1, all_threads=True)
    if os.environ.get("RAY_TPU_PDEATHSIG"):
        # Daemon-owned worker: die when the node daemon dies, even on
        # SIGKILL of the daemon (node-failure semantics — a raylet's
        # workers don't outlive it).  Linux prctl(PR_SET_PDEATHSIG); where
        # unavailable, a watchdog thread polls for reparenting instead so
        # the invariant holds on every platform.
        armed = False
        try:
            import ctypes
            import signal as _signal

            ctypes.CDLL(None).prctl(1, _signal.SIGTERM)  # PR_SET_PDEATHSIG=1
            armed = True
        except Exception:
            pass
        if not armed:
            import time as _time

            parent = os.getppid()

            def _orphan_watch():
                while True:
                    _time.sleep(2.0)
                    if os.getppid() != parent:
                        os._exit(0)

            threading.Thread(target=_orphan_watch, daemon=True).start()
    global _runtime
    from ray_tpu._private import telemetry, wire

    # Flight recorder armed before anything can crash: a fault-plane kill
    # or uncaught exception in this worker dumps its recent-event ring.
    telemetry.install(f"worker:{worker_id}")

    # Watchdog: if the connect/auth handshake wedges (e.g. the driver
    # vanished between spawn and connect), die instead of lingering — the
    # driver's reaper then reschedules anything leased to this worker.
    from ray_tpu._private import config as _cfg

    watchdog = threading.Timer(
        _cfg.get("worker_handshake_timeout_s"), lambda: os._exit(17)
    )
    watchdog.daemon = True
    watchdog.start()
    conn = wire.batching(wire.connect(address, authkey))
    watchdog.cancel()
    boot_phase("worker::boot::connect")
    from ray_tpu._private.netutil import set_nodelay

    set_nodelay(conn)
    conn_lock = lock_watchdog.make_lock("worker_main.conn_lock")
    rt = WorkerRuntime(conn, conn_lock, session_name, worker_id, authkey=authkey)
    _runtime = rt
    boot_phase("worker::boot::runtime")

    # Install ObjectRef refcount hooks: proxy to owner (oneway, FIFO with the
    # task's own completion message so no use-after-free races).
    from ray_tpu._private import refs as refs_mod

    # Locally-owned direct-call results are counted in-process; everything
    # else proxies to the owner as before.
    refs_mod.set_ref_hooks(rt.borrow_ref, rt.unborrow_ref)
    # Mark this process as a worker for ray_tpu API routing.
    from ray_tpu._private import runtime as runtime_mod

    runtime_mod._worker_mode = True



    task_q: "queue.Queue[tuple]" = queue.Queue()
    pool = None  # ThreadPoolExecutor for max_concurrency > 1
    pool_lock = threading.Lock()

    node_id = os.environ.get("RAY_TPU_NODE_ID")

    # -- direct peer transport (ray: direct_actor_task_submitter.h:67) -----
    # The peer server's endpoint rides the "ready" handshake; peer-pushed
    # tasks execute on the SAME queues as head-pushed ones (per-caller
    # order = the pushing connection's FIFO), replying on the peer socket.
    from ray_tpu._private.peer import DirectTransport, PeerServer

    def route_task(msg: tuple, reply) -> None:
        """Route one executable task to the right executor (shared by the
        head recv loop and every peer connection)."""
        nonlocal pool
        spec: TaskSpec = msg[1]
        # Lifecycle stamp: when this executor dequeued the frame — the
        # "received" stage of the task state machine (one attribute set;
        # TaskSpec is a plain dataclass, the rider never hits the wire
        # twice because the spec is executed, not forwarded).
        spec._recv_t = time.time()
        if spec.max_concurrency > 1 and not spec.is_actor_creation:
            from concurrent.futures import ThreadPoolExecutor

            with pool_lock:
                if pool is None:
                    pool = ThreadPoolExecutor(max_workers=spec.max_concurrency)
            pool.submit(_run_and_reply, msg, reply)
        else:
            task_q.put((msg, reply))

    peer_cancelled: set = set()

    # Task events for peer-executed tasks, reported to the head in BATCHES
    # off the latency path (ray: task_event_buffer.h:147 — the reference
    # buffers and flushes task state transitions on an interval too; the
    # state API is eventually consistent in both systems).
    events_buf: list = []
    events_lock = threading.Lock()

    def flush_task_events() -> None:
        from ray_tpu._private import telemetry as _telemetry
        from ray_tpu.util import tracing as _tracing

        spans = _tracing.drain_spans()
        if spans:
            rt.oneway(("spans", spans), droppable=True)
        with events_lock:
            if not events_buf:
                return
            batch = events_buf[:]
            events_buf.clear()
        _telemetry.note("task_events_flush", n=len(batch))
        rt.oneway(("task_events", batch), droppable=True)

    def record_peer_task_event(spec, err_blob, t0: float, t1: float) -> None:
        recv_t = getattr(spec, "_recv_t", None) or t0
        with events_lock:
            events_buf.append(
                {
                    "task_id": spec.task_id,
                    "name": spec.name,
                    "state": "FINISHED" if err_blob is None else "FAILED",
                    "node_id": node_id,
                    "worker_id": worker_id,
                    "actor_id": spec.actor_id,
                    "parent_task_id": spec.parent_task_id,
                    "attempt": spec.attempt,
                    "end_time": t1,
                    "duration": t1 - t0,
                    "direct": True,
                    # Executor-side stage attribution for direct tasks
                    # (the head sees no dispatch for these, so the
                    # exec-queue + run split is all it can know).
                    "stages": {
                        "received": recv_t, "running": t0, "exec_done": t1,
                    },
                    "durations": {
                        "exec_queue": round(max(t0 - recv_t, 0.0), 6),
                        "running": round(max(t1 - t0, 0.0), 6),
                    },
                }
            )
            full = len(events_buf) >= 64
        if full:
            flush_task_events()

    def _sink_event(e: dict) -> None:
        with events_lock:
            events_buf.append(e)
            full = len(events_buf) >= 64
        if full:
            flush_task_events()

    rt.task_event_sink = _sink_event
    ready_sent = threading.Event()

    def _on_prof_ctl(_key, action, *args) -> None:
        """Cluster profiler broadcast handler ("profiler"/"ctl" pubsub):
        start/stop the local sampler; a stop pushes the final table
        immediately so the head's report window closes tight."""
        from ray_tpu._private import profiler as _profiler

        if action == "start":
            _profiler.start(args[0] if args else None)
        elif action == "stop":
            _profiler.stop()
            rt.oneway(
                ("prof_push", _profiler.snapshot_payload()), droppable=True
            )

    def _events_ticker() -> None:
        import time as _time

        from ray_tpu._private import config as _cfg2
        from ray_tpu._private import profiler as _profiler
        from ray_tpu._private import telemetry as _telemetry

        report_wire = bool(_cfg2.get("wire_stats"))
        push_s = max(_cfg2.get("metrics_push_ms"), 0) / 1000.0
        push_refs = bool(_cfg2.get("refs_push"))
        last_push = 0.0
        prof_subscribed = False
        while True:
            _time.sleep(0.5)
            if not ready_sent.is_set():
                # NOTHING may precede the ready hello on this conn: the
                # head's handshake dispatcher closes a conn whose first
                # message is not a recognized hello — a push racing a
                # slow runtime-env setup would sever the very conn the
                # env_failed report needs.
                continue
            if not prof_subscribed:
                # One subscription per worker, armed only after the ready
                # hello: profiler start/stop broadcasts now reach this
                # process for its whole life.
                prof_subscribed = True
                try:
                    rt.subscribe("profiler", "ctl", _on_prof_ctl)
                except OSError:
                    prof_subscribed = False  # head away: retry next beat
                else:
                    try:
                        # Catch up: a cluster-wide profile started before
                        # this worker existed never reached it (pubsub is
                        # live-only) — poll the head's sampler state once.
                        st = rt.request("profile", ("status",), timeout=5)
                        if st and st.get("running"):
                            _on_prof_ctl(None, "start", st.get("hz"))
                    except Exception:
                        pass  # the next start broadcast still reaches us
            flush_task_events()
            if report_wire:
                rt.oneway(("wire_stats", wire.stats()), droppable=True)
            if push_s > 0 and _time.monotonic() - last_push >= push_s:
                # Metric push (telemetry.py): this process's util/metrics
                # registry + wire counters, droppable by contract — a head
                # bounce loses a tick, never wedges the backlog.
                last_push = _time.monotonic()
                rt.oneway(
                    ("metrics_push", _telemetry.snapshot_process()),
                    droppable=True,
                )
                if push_refs:
                    # Live-ref table push (the worker leg of the object
                    # ledger): same tick, same droppable contract — it
                    # never competes with seals/refops for the backlog.
                    rt.oneway(
                        ("refs_push", rt.ref_table_snapshot()),
                        droppable=True,
                    )
                if _profiler.ENABLED and _profiler.running():
                    # Collapsed-stack push (the worker leg of the cluster
                    # flamegraph): cumulative table, so a dropped push
                    # costs freshness only.  Gated on the module bool —
                    # profiler off costs exactly this one check.
                    rt.oneway(
                        ("prof_push", _profiler.snapshot_payload()),
                        droppable=True,
                    )
            # Telemetry rides the next linger/idle flush; nudge it here so
            # a fully-busy executor still reports within a beat.
            wire.flush_dirty()

    threading.Thread(
        target=_events_ticker, daemon=True, name="raytpu-task-events"
    ).start()

    def peer_handler(msg: tuple, reply) -> None:
        if msg[0] == "pcall":
            spec = msg[1]
            if (
                spec.actor_id is None
                and not spec.is_actor_creation
                and spec.max_concurrency <= 1
            ):
                # Leased plain task: execute INLINE on this conn's recv
                # thread.  A leased worker serves exactly ONE caller and
                # the conn is its FIFO, so ordering and serialization
                # are identical to the task_q route — what disappears is
                # the queue handoff (two futex waits + a context switch
                # per task, a measured slice of per-task wall on a
                # contended host).  Actor calls keep the queue: their
                # cross-conn ordering and max_concurrency semantics live
                # there.
                spec._recv_t = time.time()
                _run_and_reply(("task", spec, None), reply)
                return
            route_task(("task", msg[1], None), reply)
        elif msg[0] == "pcancel":
            # Best-effort: queued (not yet started) calls are dropped at
            # execution time; a running method is never interrupted.
            # Bounded — a cancel for a running/finished task would
            # otherwise park in the set forever (evicting an arbitrary
            # stale entry only downgrades that cancel to a no-op).
            if len(peer_cancelled) >= 4096:
                peer_cancelled.pop()
            peer_cancelled.add(msg[1])

    advertise = os.environ.get("RAY_TPU_PEER_HOST") or (
        address[0] if isinstance(address, tuple) else "127.0.0.1"
    )
    if advertise in ("0.0.0.0", "::", ""):
        # The head listener may bind a wildcard (RAY_TPU_BIND_HOST=0.0.0.0)
        # — unroutable as an advertised address (a remote peer would dial
        # its OWN loopback); fall back to this node's routable IP knob.
        advertise = _cfg.get("node_ip")
    bind = "127.0.0.1" if advertise in ("127.0.0.1", "localhost") else "0.0.0.0"
    try:
        peer_server = PeerServer(authkey, bind, advertise, peer_handler)
        peer_endpoint = peer_server.endpoint
    except OSError:
        peer_server, peer_endpoint = None, None  # no direct path; head relays
    rt.direct = DirectTransport(rt)
    boot_phase("worker::boot::peer_server")

    def try_reconnect() -> bool:
        """Head conn lost: in head-split mode (reconnect window > 0) retry
        the head's FIXED address and re-handshake; a restarted head adopts
        this worker (ray: workers surviving a GCS restart re-register)."""
        from ray_tpu._private import config as _cfg

        window = _cfg.get("reconnect_window_s")
        if window <= 0:
            return False
        import time as _time

        deadline = _time.monotonic() + window
        newconn = None
        while _time.monotonic() < deadline:
            try:
                newconn = wire.batching(wire.connect(address, authkey))
                set_nodelay(newconn)
                break
            except Exception:
                _time.sleep(0.5)
        if newconn is None:
            return False
        # Swap + hello + backlog flush + request-fail + replays run in ONE
        # shared implementation (WorkerRuntime.reconnect_recover — the
        # attached-driver path uses the same one).
        import time as _time

        return rt.reconnect_recover(
            newconn,
            # The trailing list is the relayed-work announcement: tasks
            # this executor still holds (queued or running).  The head
            # re-drives exactly the in-flight work NOT in this list — it
            # was lost with the dead conn (reconciliation handshake,
            # executor leg).
            lambda c: c.send(
                ("ready", worker_id, os.getpid(), node_id, peer_endpoint,
                 rt.actor_announcement(), _time.time(),
                 list(rt.relayed_pending))
            ),
        )

    def recv_loop():
        while True:
            try:
                msg = rt.conn.recv()
            except (EOFError, OSError):
                if not try_reconnect():
                    os._exit(0)
                continue
            kind = msg[0]
            if kind == "reply":
                rt._on_reply(msg[1], msg[2], msg[3])
            elif kind == "pub":
                rt._on_pub(msg[1], msg[2], msg[3])
            elif kind in ("task", "create_actor"):
                # Track BEFORE enqueueing: a reconnect hello built between
                # receipt and execution must still announce this task.
                try:
                    rt.relayed_pending[msg[1].task_id] = None
                except AttributeError:
                    pass
                route_task(msg, None)
            elif kind == "fence":
                # Transport-switch barrier: acking from the recv thread
                # certifies every earlier task on this conn is already in
                # the executor queue — a direct call sent after the ack
                # cannot overtake a relayed one (see peer.py docstring).
                # The head is parked on this ack: flush immediately.
                rt.oneway(("fence_ack", msg[1]))
                try:
                    wire.flush_conn(rt.conn)
                except OSError:
                    pass
            elif kind == "kill":
                os._exit(0)
            elif kind == "shutdown":
                task_q.put((("__shutdown__",), None))

    def _run_and_reply(msg, reply=None):
        spec, blob = msg[1], msg[2]
        if reply is not None and spec.task_id in peer_cancelled:
            peer_cancelled.discard(spec.task_id)
            import cloudpickle

            from ray_tpu.exceptions import TaskCancelledError

            reply.send(
                ("pdone", spec.task_id, [],
                 cloudpickle.dumps(TaskCancelledError(spec.name)))
            )
            return
        import time as _time

        t0 = _time.time()
        try:
            done = _execute(rt, spec, blob)
        except SystemExit:
            # exit_actor() from a concurrent (thread-pool) actor method:
            # in a pool thread SystemExit would be swallowed by the Future,
            # leaving the caller hanging — exit the process here (the
            # actor_exit oneway was already sent by exit_actor()).
            os._exit(0)
        if reply is None:
            # Executor-side stage stamps ride the done message (schema
            # arity 4): recv = frame dequeued, start/end = user code.
            # The head lands them on its clock via the handshake offset
            # and folds them into the task's lifecycle record.
            done = done + (
                {
                    "recv": getattr(spec, "_recv_t", None) or t0,
                    "start": t0,
                    "end": _time.time(),
                },
            )
            try:
                with conn_lock:
                    rt.conn.send(done)
            except OSError:
                pass  # head restarting: this result is lost; recv_loop reconnects
            # Replied (or the send failed — then the result is lost either
            # way): no longer pending, so a reconnect hello will NOT claim
            # it and the head re-drives it if the done never landed.
            rt.relayed_pending.pop(spec.task_id, None)
            return
        # Direct-call completion: registration oneways go to the head first
        # (FIFO behind the guard borrows _store_results already sent), then
        # the caller unblocks via the peer socket.  Inline results send
        # nothing — they are caller-owned, and the serialize-time guard
        # doubles as the caller-cache borrow.
        _task_id, results, err_blob = done[1], done[2], done[3]
        if (
            err_blob is None
            and spec.actor_id is None
            and any(item[1] == "shm" for item in results)
        ):
            # Sealed PLAIN-task results are reconstructable: ship the spec
            # so the head keeps lineage for this lease-dispatched task
            # (ray: task_manager.h:90 — owner-side lineage regardless of
            # transport; actor-method outputs are excluded exactly like the
            # relayed path — re-running a stateful method is not recovery).
            # Must precede the direct_seal below (same FIFO) so lineage
            # exists before the object is ever resolvable.
            rt.oneway(("direct_lineage", spec))
        for item in results:
            oid, kind, data, contained = item
            if kind == "shm":
                # Register the sealed copy with the directory so remote
                # consumers (and capacity accounting) can find it; the head
                # swaps the guard borrows for its stored-object borrows.
                rt.oneway(("direct_seal", oid, data, contained))
        record_peer_task_event(spec, err_blob, t0, _time.time())
        reply.send(("pdone", _task_id, results, err_blob))

    threading.Thread(target=recv_loop, daemon=True, name="worker-recv").start()

    # Materialize working_dir / py_modules BEFORE the ready handshake (no
    # task may run before its code exists).  Packages come over dedicated
    # one-shot kv_fetch connections: the main conn cannot serve requests
    # yet — the owner parks replies behind "ready".
    renv_json = os.environ.get("RAY_TPU_RUNTIME_ENV")
    if renv_json:
        import json as _json

        from ray_tpu._private.runtime_env import apply_worker_runtime_env

        def _fetch(key):
            c = wire.connect(address, authkey)
            try:
                c.send(("kv_fetch", key))
                return c.recv()
            finally:
                c.close()

        try:
            apply_worker_runtime_env(_json.loads(renv_json), kv_get=_fetch)
        except Exception as e:  # noqa: BLE001 — report, then die
            # Setup failure is deterministic: report it as a structured
            # env_failed hello so the head fails the leased task with
            # RuntimeEnvSetupError instead of a retriable worker crash.
            try:
                with conn_lock:
                    conn.send(("env_failed", worker_id, f"{type(e).__name__}: {e}"))
                wire.flush_conn(conn)
            except OSError:
                pass
            sys.exit(1)

    with conn_lock:
        # The trailing time.time() is the clock-offset sample the head
        # uses to merge this process's spans into the cluster timeline.
        conn.send(
            ("ready", worker_id, os.getpid(), node_id, peer_endpoint,
             None, time.time())
        )
    wire.flush_conn(conn)
    boot_phase("worker::boot::ready")
    rt.boot_spans = boot_spans
    ready_sent.set()  # telemetry oneways may ride this conn from here on

    while True:
        try:
            msg, reply = task_q.get_nowait()
        except queue.Empty:
            # About to block on the task queue: flush every pending batch
            # (done/refop runs to the head, pdone runs to peer callers).
            # While tasks are queued back-to-back, consecutive results
            # keep coalescing — the linger sweep bounds their latency.
            wire.flush_dirty()
            msg, reply = task_q.get()
        if msg[0] == "__shutdown__":
            break
        _run_and_reply(msg, reply)
    wire.flush_dirty()
    sys.exit(0)


def _subprocess_entry() -> None:
    """Entry for `python -m ray_tpu._private.worker_proc` (exec'ed by the
    driver's worker pool — see runtime._spawn_worker)."""
    import json

    host = os.environ["RAY_TPU_DRIVER_HOST"]
    port = int(os.environ["RAY_TPU_DRIVER_PORT"])
    authkey = bytes.fromhex(os.environ["RAY_TPU_AUTHKEY"])
    wid = os.environ["RAY_TPU_WORKER_ID"]
    session = os.environ["RAY_TPU_SESSION"]
    env_vars = json.loads(os.environ.get("RAY_TPU_ENV_VARS", "{}"))
    # Under `python -m` this file runs as __main__; call through the
    # canonical module so worker_main's globals (the _runtime singleton)
    # land where `import ray_tpu._private.worker_proc` reads them.
    from ray_tpu._private import worker_proc as canonical

    canonical.worker_main((host, port), authkey, wid, session, env_vars)


if __name__ == "__main__":
    _subprocess_entry()
