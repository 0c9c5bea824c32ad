"""Driver-side runtime: ownership, scheduling, worker pool, actor FSM.

This process plays three reference roles at once (they split into separate
processes when the multi-host DCN transport lands):
  * CoreWorker of the driver -- task submission, object ownership/refcounts
    (ray: src/ray/core_worker/core_worker.h:284, task_manager.h:90,
     reference_count.h:61);
  * raylet/NodeManager -- worker leases, dependency management, dispatch
    (ray: src/ray/raylet/node_manager.h:115, local_task_manager.h:58,
     worker_pool.h:156, dependency_manager.h:51);
  * GCS -- global tables + actor lifecycle FSM
    (ray: src/ray/gcs/gcs_server/gcs_actor_manager.h:258-280).

Design notes (TPU-first): hosts are few and fat (a TPU host drives 4-8 chips),
so a single asio-style control loop per host with direct connections to every
worker replaces the reference's raylet<->GCS<->worker RPC triangle. Tasks are
pushed directly to leased workers (the analogue of
ray: transport/direct_task_transport.h:75), and the object plane is the
host-shared tmpfs store (store.py).
"""

from __future__ import annotations

import atexit
import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Set, Tuple

import cloudpickle

from ray_tpu._private import faults
from ray_tpu._private import ids, lock_watchdog, serialization as ser
from ray_tpu._private import wire as _wire
from ray_tpu._private.gcs import (
    ALIVE,
    DEAD,
    PENDING_CREATION,
    RESTARTING,
    ActorInfo,
    GlobalState,
    NodeInfo,
    PlacementGroupInfo,
    pg_record as _pg_record,
)
from ray_tpu._private.refs import ObjectRef, set_ref_hooks
from ray_tpu._private.scheduler import Scheduler
from ray_tpu._private.store import OwnerStore
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu.exceptions import (
    ActorDiedError,
    GetTimeoutError,
    ObjectLostError,
    OutOfMemoryError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)

_worker_mode = False  # set True inside worker processes (worker_proc.py)

# Lock-discipline checking (SURVEY §5.2): the reference leans on clang
# thread-safety annotations (GUARDED_BY) + TSAN in CI; the Python analogue
# is runtime ownership assertions on every "caller holds self.lock"
# internal.  Enabled via RAY_TPU_DEBUG_LOCKS=1 — the test suite runs with
# it on (tests/conftest.py), production pays only one module-bool check.
_DEBUG_LOCKS = os.environ.get("RAY_TPU_DEBUG_LOCKS") == "1"


def _locked(method):
    """Decorator asserting the runtime lock is held on entry (debug mode)."""
    if not _DEBUG_LOCKS:
        return method
    import functools

    @functools.wraps(method)
    def wrapper(self, *a, **kw):
        if not self.lock._is_owned():
            raise AssertionError(
                f"{method.__name__} requires self.lock held (lock-discipline "
                "violation — see RAY_TPU_DEBUG_LOCKS)"
            )
        return method(self, *a, **kw)

    return wrapper


# How long a lineage re-execution waits on a pending function-export
# fence before its parked gets fail loudly (see _reconstruct).
_FN_FENCE_TIMEOUT_S = 30.0


def _runtime_env_key(renv) -> object:
    """Worker-pool identity of a runtime env: workers are only shared
    between tasks whose env_vars AND code packages match."""
    if not renv:
        return None
    env_vars = renv.get("env_vars") or None
    return (
        tuple(sorted(env_vars.items())) if env_vars else None,
        renv.get("working_dir"),
        tuple(renv.get("py_modules") or ()) or None,
        tuple(renv.get("pip") or ()) or None,
    )


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _detect_tpu_chips() -> int:
    """Local TPU chip count: RAY_TPU_CHIPS env override, else the chips
    this host hands to user space.  Never imports jax (backend init costs
    seconds, opens the chip, and this runs in every ray_tpu.init).

    Older TPU VMs expose one /dev/accel<N> per chip.  v5e hosts expose
    none: each chip is a Google PCI function bound to vfio, and the chips
    this machine may open are the /dev/vfio/<iommu group> files (seen on
    the v5e chip machine: four functions in sysfs, one group file, one
    device in jax — so the PCI list alone over-counts)."""
    env = os.environ.get("RAY_TPU_CHIPS")
    if env:
        try:
            return int(env)
        except ValueError:
            pass  # malformed override: fall through to device detection
    import glob as _glob

    accel = _glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    groups = set()
    for dev in _glob.glob("/sys/bus/pci/devices/*"):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            group = os.path.basename(
                os.path.realpath(os.path.join(dev, "iommu_group"))
            )
        except OSError:
            continue
        if os.path.exists(os.path.join("/dev/vfio", group)):
            groups.add(group)
    return len(groups)


def _exited(pid: Optional[int]) -> bool:
    """No such process, or a zombie: whatever it held (device files, host
    memory) is released, reaped or not."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] in ("Z", "X")
    except (OSError, IndexError):
        return True


class _PopenHandle:
    """subprocess.Popen adapter exposing the mp.Process surface the runtime
    uses (terminate/join/is_alive/pid)."""

    __slots__ = ("_p",)

    def __init__(self, p):
        self._p = p

    def terminate(self):
        self._p.terminate()

    def kill(self):
        self._p.kill()

    def join(self, timeout=None):
        import subprocess

        try:
            self._p.wait(timeout)
        except subprocess.TimeoutExpired:
            pass

    def is_alive(self):
        return self._p.poll() is None

    @property
    def pid(self):
        return self._p.pid


class _ZygoteProcHandle:
    """Handle for a worker forked by the zygote (not our child: no
    waitpid — liveness via kill(pid, 0), termination via signals).  The
    pid lands asynchronously with the zygote's ("forked", ...) reply; a
    handle whose pid never arrives (zygote died mid-request) reads as
    dead after a grace window so the reaper reschedules its lease."""

    __slots__ = ("_pid", "_created", "_zygote")

    def __init__(self, zygote_proc=None):
        self._pid = None
        self._created = time.monotonic()
        self._zygote = zygote_proc

    def set_pid(self, pid: int) -> None:
        self._pid = pid

    def _signal(self, sig) -> None:
        if self._pid is not None:
            try:
                os.kill(self._pid, sig)
            except (OSError, ProcessLookupError):
                pass

    def terminate(self):
        import signal

        self._signal(signal.SIGTERM)

    def kill(self):
        import signal

        self._signal(signal.SIGKILL)

    def join(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.is_alive():
            if deadline is not None and time.monotonic() >= deadline:
                return
            time.sleep(0.05)

    def is_alive(self):
        if self._pid is None:
            # Fork request in flight: the grace applies even while the
            # zygote itself lives — a lost ("forked", ...) reply (zygote
            # conn broke so _zygote_loop exited, or the frame was dropped)
            # leaves no worker process behind this handle, and an
            # unconditional True would wedge its lease as "starting"
            # forever.  The window is generous vs the ~2ms fork + serial
            # attribution so slow-boot storms are not mis-declared dead
            # (the old cascade this guard once caused).
            from ray_tpu._private import config as _config

            return (
                time.monotonic() - self._created
                < _config.get("zygote_fork_grace_s")
            )
        try:
            os.kill(self._pid, 0)
            return True
        except (OSError, ProcessLookupError):
            return False

    @property
    def pid(self):
        return self._pid


class _RemoteProcHandle:
    """Process facade for a worker owned by a node daemon: liveness comes
    from the worker's connection state; terminate routes through the daemon."""

    __slots__ = ("_rt", "_node_id", "_wid", "dead")

    def __init__(self, rt, node_id, wid):
        self._rt = rt
        self._node_id = node_id
        self._wid = wid
        self.dead = False

    def terminate(self):
        self._rt._daemon_send(self._node_id, ("kill_worker", self._wid))

    def kill(self):
        self.terminate()

    def join(self, timeout=None):
        pass  # the daemon reaps its own children

    def is_alive(self):
        # Until the worker's conn EOFs (io loop marks it crashed) we assume
        # it is alive; pre-connect spawn failures surface via the daemon's
        # own death or the lease timeout paths.
        return not self.dead

    @property
    def pid(self):
        return None


class _AdoptedHandle:
    """Process facade for a worker adopted after a head restart: the new
    head never spawned it, so liveness is purely connection state and
    terminate can only ask the worker itself to exit."""

    __slots__ = ("_rt", "_wid", "dead")

    def __init__(self, rt, wid):
        self._rt = rt
        self._wid = wid
        self.dead = False

    def terminate(self):
        h = self._rt.workers.get(self._wid)
        if h is not None and h.conn is not None:
            try:
                h.conn.send(("kill",))
            except OSError:
                pass

    def kill(self):
        self.terminate()

    def join(self, timeout=None):
        pass

    def is_alive(self):
        return not self.dead


class WorkerHandle:
    __slots__ = (
        "worker_id",
        "node_id",
        "env_key",
        "env_vars",
        "proc",
        "conn",
        "state",  # starting | idle | busy | actor | dead
        "pending_sends",
        "current_task",
        "actor_id",
        "known_fns",
        "pid",
        "spawn_ts",
        "idle_since",
    )

    def __init__(self, worker_id, node_id, env_key, env_vars, proc):
        self.worker_id = worker_id
        self.node_id = node_id
        self.env_key = env_key
        self.env_vars = env_vars
        self.proc = proc
        self.conn = None
        self.state = "starting"
        self.pending_sends: List[tuple] = []
        self.current_task: Optional[str] = None
        self.actor_id: Optional[str] = None
        self.known_fns: Set[str] = set()
        self.pid = None
        self.spawn_ts = time.monotonic()
        self.idle_since = 0.0


class _ReadySpill:
    """Disk overflow segment for the ready queue: beyond the
    ready_queue_spill_after backlog, dependency-free plain specs live as
    length-framed pickles in ONE append-only file and reload in FIFO
    chunks as the in-memory backlog drains.  This is what bounds head RSS
    under a 1M-task backlog (a TaskRecord+spec is ~1KB resident; the
    reference absorbs the same backlog across its distributed raylet
    queues — a single-node head needs disk).

    Same-session only: the file dies with the head (spilled overflow
    tasks are NOT in the snapshot's in-flight cap — a client retrying
    across a head bounce re-submits them, the same at-least-once contract
    lease-dispatched direct tasks already carry)."""

    __slots__ = ("path", "_w", "_roff", "count", "appended", "loaded")

    def __init__(self, path: str):
        self.path = path
        self._w = None       # lazily-opened append handle (buffered)
        self._roff = 0       # read offset: everything before it was loaded
        self.count = 0       # frames on disk not yet loaded
        self.appended = 0    # lifetime counters (bench/telemetry surface)
        self.loaded = 0

    def append(self, spec) -> None:
        import pickle as _pickle
        import struct as _struct

        if self._w is None:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            self._w = open(self.path, "ab")
        blob = _pickle.dumps(spec, protocol=5)
        self._w.write(_struct.pack("<I", len(blob)) + blob)
        self.count += 1
        self.appended += 1

    def load(self, n: int) -> List[Any]:
        """Next n specs in FIFO order; resets the file once drained so a
        long-lived head doesn't grow an unbounded tombstone prefix."""
        import pickle as _pickle
        import struct as _struct

        if self.count <= 0 or self._w is None:
            return []
        self._w.flush()
        out: List[Any] = []
        with open(self.path, "rb") as r:
            r.seek(self._roff)
            while len(out) < n and self.count > 0:
                hdr = r.read(4)
                if len(hdr) < 4:
                    break
                (ln,) = _struct.unpack("<I", hdr)
                blob = r.read(ln)
                if len(blob) < ln:
                    break
                out.append(_pickle.loads(blob))
                self.count -= 1
            self._roff = r.tell()
        self.loaded += len(out)
        if self.count <= 0:
            # Fully drained: truncate in place (the append handle's
            # position resets with it).
            self._w.close()
            self._w = open(self.path, "wb")
            self._w.close()
            self._w = open(self.path, "ab")
            self._roff = 0
            self.count = 0
        return out

    def close(self) -> None:
        if self._w is not None:
            try:
                self._w.close()
            except OSError:
                pass
            self._w = None
        try:
            os.unlink(self.path)
        except OSError:
            pass


class _ReadyQueue:
    """Ready tasks bucketed by scheduling shape (ray: ClusterTaskManager
    keys its queues by scheduling class).  Dispatch probes one head task
    per bucket, so a blocked shape costs O(1) per event instead of
    rotating every queued sibling through the deque."""

    __slots__ = ("_rt", "buckets")

    def __init__(self, rt):
        self._rt = rt
        self.buckets: Dict[Any, deque] = {}

    def _shape_of(self, spec) -> tuple:
        if Scheduler.is_pg_task(spec):
            pg_id, want_idx = self._rt.scheduler._pg_for_spec(spec)
            # Bundle index is part of the shape: a full bundle 0 must not
            # block a sibling task targeting free bundle 1.
            return ("pg", pg_id, want_idx, tuple(sorted(spec.resources.items())))
        # Plain-task shape doubles as the lease SchedulingKey (ray:
        # scheduling_key.h = scheduling class + function descriptor):
        # fn_id keeps the leaseholder's fn-blob cache hot, env_key keeps
        # runtime-env workers distinct.  Head-of-line semantics are
        # unchanged — finer buckets, one head probe each.
        return (
            tuple(sorted(spec.resources.items())),
            Runtime._strategy_shape_key(spec.scheduling_strategy),
            spec.fn_id,
            None if not spec.runtime_env else _runtime_env_key(spec.runtime_env),
        )

    def append(self, tid: str, shape=None) -> None:
        if shape is None:
            shape = self._shape_of(self._rt.tasks[tid].spec)
        self.buckets.setdefault(shape, deque()).append(tid)

    def __iter__(self):
        for q in self.buckets.values():
            yield from q

    def __len__(self) -> int:
        return sum(len(q) for q in self.buckets.values())


class TaskLease:
    """One head-side worker lease: a worker bound to a SchedulingKey with
    its resources HELD across tasks (ray: direct_task_transport.h:75 —
    the same pooling the caller-side peer leases do, applied to the
    head's own dispatch loop).  idle_since is None while a task runs on
    the leaseholder; a monotonic stamp while it waits for the next
    same-key task."""

    __slots__ = (
        "lease_id", "key", "worker_id", "node_id", "resources",
        "granted_t", "idle_since", "dispatched", "last_extend_journal",
    )

    def __init__(self, lease_id, key, worker_id, node_id, resources):
        self.lease_id = lease_id
        self.key = key
        self.worker_id = worker_id
        self.node_id = node_id
        self.resources = resources
        self.granted_t = time.monotonic()
        self.idle_since: Optional[float] = None  # a task is running now
        self.dispatched = 1
        self.last_extend_journal = self.granted_t


class TaskRecord:
    __slots__ = (
        "spec", "state", "node_id", "worker_id", "unmet_deps", "cancelled",
        "pg", "start_time", "allow_pending", "stages", "lease",
    )

    def __init__(self, spec):
        self.spec = spec
        self.state = "PENDING"
        self.node_id = None
        self.worker_id = None
        self.unmet_deps = 0
        self.cancelled = False
        self.pg = None  # (pg_id, bundle_index) when resources come from a PG
        self.start_time = None  # wall time when dispatched (timeline)
        # The TaskLease this record dispatched on, when any: the LEASE
        # owns the node resources (release happens at revoke, not per
        # task) — _release_for must not double-release them.
        self.lease = None
        # Re-driven tasks (head-restart recovery) PARK when infeasible —
        # the cluster's daemon nodes rejoin seconds after restore, and
        # failing fast there would defeat the re-drive.
        self.allow_pending = False
        # Lifecycle stage stamps (telemetry.STAGE_ORDER): wall-clock time
        # each stage was entered, on the head clock (executor stamps land
        # via the done message, offset-corrected).  A retried attempt
        # re-stamps, so the record attributes the attempt that finished.
        self.stages: Dict[str, float] = {"submit": time.time()}

    def stamp(self, stage: str) -> None:
        self.stages[stage] = time.time()


class ActorRuntime:
    __slots__ = (
        "info",
        "worker_id",
        "queued",
        "in_flight",
        "expected_death",
        "no_restart",
        "placement",  # ("node", node_id) | ("pg", pg_id, bundle_idx)
        "_creation_crash_retries",
    )

    def __init__(self, info):
        self.info = info
        self.worker_id: Optional[str] = None
        self.queued: deque = deque()  # TaskSpecs waiting for ALIVE
        # task_ids sent to the worker, as an insertion-ordered dict-set:
        # requeue-on-death iterates this to rebuild per-caller call order
        # across a restart, so push order must be recoverable (a plain set
        # iterates in hash order — the direct path's ActorRoute buffer
        # keeps order, and this relayed twin must match; ray:
        # sequential_actor_submit_queue.h orders by sequence number).
        self.in_flight: Dict[str, None] = {}
        self.expected_death = False
        self.no_restart = False
        self.placement = None
        self._creation_crash_retries = 0


class Runtime:
    """Singleton per driver process."""

    def __init__(
        self,
        num_cpus: Optional[int] = None,
        resources: Optional[Dict[str, float]] = None,
        namespace: str = "default",
        session_name: Optional[str] = None,
        snapshot_path: Optional[str] = None,
        listen_port: int = 0,
        authkey: Optional[bytes] = None,
    ):
        # _system_config overrides exported their env form by now: pick up
        # a fault plan configured via ray_tpu.init(_system_config=...).
        faults.refresh_from_env()
        self.session_name = session_name or f"{os.getpid()}-{os.urandom(3).hex()}"
        self.namespace = namespace
        self.state = GlobalState()
        self.store = OwnerStore(self.session_name, spill_dir=f"/tmp/raytpu-spill-{self.session_name}")
        self.store.on_lifecycle = self._on_store_lifecycle
        self.lock = lock_watchdog.make_lock("Runtime.lock", rlock=True)
        self.head_node_id = ids.node_id()
        if num_cpus is None:
            num_cpus = max(os.cpu_count() or 1, 4)
        res = {"CPU": float(num_cpus), **(resources or {})}
        chips = _detect_tpu_chips()
        if chips > 0:
            # TPU is a first-class schedulable resource (the reference's
            # accelerators are GPU-only — accelerators.py:1-7): tasks/actors
            # reserve chips via num_tpus / ScalingConfig.chips_per_worker.
            res.setdefault("TPU", float(chips))
        self.state.register_node(
            NodeInfo(self.head_node_id, dict(res), dict(res), is_head=True)
        )
        self.scheduler = Scheduler(self.state, self.head_node_id)
        self.scheduler.locality_fn = self._deps_locality

        self.workers: Dict[str, WorkerHandle] = {}
        self.idle_pool: Dict[Tuple[str, Any], List[str]] = {}  # (node, env_key) -> worker_ids
        self.starting_pool: Dict[Tuple[str, Any], List[str]] = {}  # spawned, not yet connected
        self.tasks: Dict[str, TaskRecord] = {}
        self.actors: Dict[str, ActorRuntime] = {}
        self.ready_queue = _ReadyQueue(self)
        # ONE pubsub plane for every push mechanism (parked gets, wait
        # tokens, dep resolution here; GCS events and serve long-poll run
        # their own Publisher instances of the same abstraction) —
        # ray: src/ray/pubsub/publisher.h:298.
        from ray_tpu._private.pubsub import Publisher

        # Cross-process pubsub (ray: subscriber.h:70): (channel, key) ->
        # {worker/driver id: once} for ids that asked for pushes; "*" key
        # = wildcard (log streaming).  Fan-out rides the control conns.
        self.remote_subs: Dict[Tuple[str, Any], Dict[str, bool]] = {}
        # Drivers whose conn reset on a live head: death deferred briefly
        # so their reconnect can win the race (did -> deadline).
        self._driver_death_grace: Dict[str, float] = {}
        # Trace-span sink (util/tracing.py; ray: spans land in the GCS task
        # events the same batched way).
        self.trace_spans: deque = deque(maxlen=10000)
        # Per-sender clock-offset estimates (seconds to ADD to a sender's
        # timestamps to land them on this process's clock), sampled from
        # the time.time() the ready/driver/daemon hellos carry.  The spans
        # and task-event batches a sender ships are corrected at ingest so
        # the merged timeline (`ray_tpu timeline`) is one coherent clock.
        self.clock_offsets: Dict[str, float] = {}
        # Telemetry sink: latest pushed metric snapshot per process plus
        # bounded ring-buffer time series (telemetry.py; ray: the GCS-side
        # metrics aggregation the dashboard agent performs).
        from ray_tpu._private import config as _tcfg
        from ray_tpu._private import telemetry as _telemetry

        self.telemetry = _telemetry.TelemetrySink(
            ring_samples=_tcfg.get("telemetry_ring_samples")
        )
        # Object ledger (memory introspection plane): latest pushed live-
        # ref table per process (refs_push oneways), joined with the owner
        # tables below by memory_summary (ray: reference_count.h:61 tables
        # feeding `ray memory`).
        self.ledger = _telemetry.ObjectLedger()
        # Profile sink: latest pushed collapsed-stack table per process
        # (prof_push oneways), merged into the cluster flamegraph by
        # `ray_tpu profile` / /api/profile (profiler.py).
        from ray_tpu._private import profiler as _profiler

        self.profiles = _profiler.ProfileSink()
        # Conn-tracked outstanding ref borrows per WORKER (the driver twin
        # is driver_refs): every refop add/del updates this, so a worker
        # crash mid-hold leaves exactly the refs it still held — flagged
        # as dead-holder leak suspects, then reclaimed after
        # leak_reclaim_grace_s by reclaim_dead_refs.
        self.worker_refs: Dict[str, Dict[str, int]] = {}
        self._dead_refs: Dict[str, Dict[str, Any]] = {}
        # Object metadata the store doesn't keep: creation time + creator
        # process label per live object (ledger age/owner attribution).
        self.object_meta: Dict[str, tuple] = {}
        # Object lifecycle event ring (create/seal/transfer/spill/restore/
        # free), merged into the chrome timeline by dashboard.timeline().
        self.object_events: deque = deque(
            maxlen=max(_tcfg.get("object_events_max"), 16)
        )
        self.pubsub = Publisher()
        import queue as _queue

        # Cross-process delivery queue + sender thread: created BEFORE the
        # hook is installed (snapshot restore publishes during __init__).
        self._pub_queue: "_queue.Queue" = _queue.Queue(maxsize=10000)
        threading.Thread(
            target=self._pub_sender_loop, daemon=True, name="raytpu-pubsend"
        ).start()
        self.pubsub.remote_hook = self._remote_publish
        self.contained_map: Dict[str, List[str]] = {}  # oid -> contained oids
        # Object directory (ray: ownership_based_object_directory.h): which
        # NON-head nodes hold a sealed copy of each object.  Head-node
        # presence is the OwnerStore's own bookkeeping.  Single-controller
        # means every seal/copy/free flows through this process, so the
        # directory needs no pubsub.
        self.object_locations: Dict[str, Set[str]] = {}
        # Packed size per object with a sealed copy anywhere — feeds the
        # BYTES-weighted locality scoring (ray: the hybrid policy's
        # locality/load tradeoff weighs by object size, not count —
        # hybrid_scheduling_policy.h:50 + locality-aware leasing).
        self.object_sizes: Dict[str, int] = {}
        self.node_object_endpoints: Dict[str, Tuple[str, int]] = {}
        # Head-side outbound-transfer admission (the daemon ObjectServer
        # enforces the same bound for its node).
        from ray_tpu._private import config as _cfg

        self._transfer_sem = threading.BoundedSemaphore(
            _cfg.get("object_transfer_max_concurrency")
        )
        self.pending_pgs: List[str] = []
        # Lineage: producer TaskSpec per task-returned object, enabling
        # re-execution when an object's bytes are lost (evicted / spill file
        # gone) — ray: task_manager.h:97 lineage + object_recovery_manager.h:41.
        # Bounded FIFO (the reference bounds by footprint bytes); actor tasks
        # are excluded (actor state is not replayable).
        from collections import OrderedDict

        self.lineage: "OrderedDict[str, Any]" = OrderedDict()
        from ray_tpu._private import config as _config

        self.lineage_max = _config.get("lineage_max_entries")
        # Resolved once (dispatch hot path): lease idle window.
        self._lease_idle_s = _config.get("task_lease_idle_s")
        # Ready-queue disk overflow (bounded head RSS under a 1M-task
        # backlog): lazily created at the first spill.
        self._ready_spill: Optional[_ReadySpill] = None
        self._spill_after = _config.get("ready_queue_spill_after")
        # Lineage re-executions parked on a missing fn blob:
        # fn_id -> (since_mono, [oids]).  Released by the export hook,
        # failed loudly by the io-loop tick after the fence timeout.
        self._fn_fences: Dict[str, tuple] = {}
        self.state.on_function_export = self._on_function_export
        # (histogram, {stage: resolved series key}) — lazy, see
        # _observe_stage_durations.
        self._stage_key_cache = None
        # Footprint bound (bytes of retained args_blob) in addition to the
        # entry-count cap — ray: task_manager.h:97-104 lineage accounting.
        self.lineage_max_bytes = _config.get("lineage_max_bytes")
        self.lineage_bytes = 0
        # With an autoscaler attached, infeasible tasks PARK (the fleet may
        # grow to fit them — ray's default behavior); without one they error
        # fast (a fixed cluster can never run them).
        self.allow_pending_infeasible = False
        # Task-event sink (ray: gcs_task_manager.h:61 ring-buffer storage):
        # bounded history of finished tasks powering the state API + metrics.
        self.task_events: deque = deque(maxlen=_config.get("task_events_max"))
        self.metrics: Dict[str, float] = {
            "tasks_submitted": 0,
            "tasks_finished": 0,
            "tasks_failed": 0,
            "tasks_retried": 0,
            "actors_created": 0,
            "actor_restarts": 0,
            "objects_put": 0,
            "workers_spawned": 0,
            "worker_crashes": 0,
            "pull_parks": 0,
            "journal_appends": 0,
            "journal_fsyncs": 0,
            "journal_entries": 0,
            "task_leases_granted": 0,
            "task_leases_revoked": 0,
            "lease_dispatches": 0,
        }
        # Staggered broadcast admission (see _admit_pull): oid -> grant
        # timestamps of in-flight pulls; round-robin rotation counter.
        # (legacy mode, relay_pipeline=0)
        self._pull_grants: Dict[str, list] = {}
        self._pull_rr = 0
        # Pipelined-broadcast transfer plans (relay_pipeline=1): oid ->
        # {"feeds": {endpoint: {load, sealed, node}}, "pulling":
        # {node_id: (endpoint, granted_at)}}.  Feeds are sealed sources
        # AND in-flight pullers (their boards re-serve mid-transfer);
        # each feed carries at most relay_fanout downstreams, so
        # admission capacity grows with the tree, not with completed
        # rounds.  Loads are soft bounds: releases ride object_copied /
        # re-asks / timestamp decay, never block correctness.
        self._xfer_plans: Dict[str, dict] = {}
        # Per-op counts of synchronous worker requests — the direct
        # transport's "zero head hops on the hot path" claim is asserted
        # against these (tests/test_direct_transport.py).
        from collections import defaultdict

        self.req_counts: Dict[str, int] = defaultdict(int)
        # Per-process wire counters reported by workers/drivers (their
        # physical-write coalescing is invisible to the head's own
        # counters) when RAY_TPU_WIRE_STATS=1.
        self.worker_wire_stats: Dict[str, Dict[str, int]] = {}
        # Direct transport directory: worker_id -> peer (host, port) from
        # the ready handshake (ray: worker addresses in the GCS worker
        # table, resolved once per caller and cached).
        self.worker_peer_endpoints: Dict[str, Tuple[str, int]] = {}
        # Transport-switch fences: fence_id -> (caller, req_id, wid, ep).
        self._pending_fences: Dict[str, tuple] = {}
        self._fence_counter = 0
        # Peer-leased workers (ray: direct_task_transport.h lease pooling):
        # lease_id -> (worker_id, node_id, resources, caller_id).  A leased
        # worker executes tasks pushed straight by the caller; the head
        # only holds the resource reservation.
        self.peer_leases: Dict[str, tuple] = {}
        self._lease_counter = 0
        # Lease grants awaiting a spawning worker's ready handshake:
        # worker_id -> [(caller, req_id, lease_id)].
        self._parked_peer_leases: Dict[str, list] = {}
        # HEAD-side lease reuse (ray: direct_task_transport.h:40-55 —
        # "subsequent same-shape tasks skip the lease round trip"): a
        # worker dispatched a lease-eligible task stays BOUND to that
        # task's SchedulingKey (fn + resource shape + strategy + env),
        # resources held, and same-key tasks dispatch straight onto it —
        # no per-task placement, no pool churn.  Revoked on worker death,
        # idle timeout (RAY_TPU_LEASE_IDLE_S), or on demand when another
        # shape can't place (the idle lease's resources are the slack).
        self.task_leases: Dict[Any, List[TaskLease]] = {}
        self.lease_by_worker: Dict[str, "TaskLease"] = {}
        self._task_lease_seq = 0
        # Adaptive prestart (ray: worker_pool.h:156): pool-miss bursts
        # raise the target; 5 quiet seconds halve it.  Topped up from the
        # io-loop tick.
        self._prestart_target = 0
        self._prestart_miss_t = 0.0
        self._prestart_decay_t = 0.0
        # Zygote fork server (zygote.py): spawned lazily on first local
        # worker spawn; until its handshake lands, spawns exec fresh
        # interpreters.
        self._zygote_conn = None
        self._zygote_proc = None
        self._zygote_spawning = False
        self._zygote_env: Optional[Dict[str, str]] = None
        # Lease-dispatched tasks currently running (caller-reported via
        # batched task_events with state RUNNING): task table visibility
        # for work the head never dispatched (ray: GcsTaskManager fed by
        # TaskEventBuffer, gcs_task_manager.h:61).
        self.direct_running: Dict[str, dict] = {}
        self._direct_done_recent: set = set()
        self._direct_done_order: deque = deque()

        from multiprocessing.connection import Listener

        # listen_port/authkey are fixed (not ephemeral/random) in head-split
        # mode so a restarted head comes back at the SAME address and its
        # daemons/workers can reconnect (ray: the GCS address is stable
        # across gcs_server restarts).
        self._authkey = authkey or os.urandom(16)
        # backlog: many workers connect at once on startup; the default
        # backlog of 1 silently drops simultaneous handshakes (the dropped
        # worker then blocks forever in its auth recv).
        # Loopback by default; RAY_TPU_BIND_HOST=0.0.0.0 exposes the driver
        # to daemons on OTHER machines (required for cloud node providers).
        # No authkey HERE: accept() must not run the challenge inline (it
        # would serialize every connect behind the accept thread) — the
        # per-conn handshake thread runs it (_auth_and_handshake).
        bind_host = _config.get("bind_host")
        self.listener = Listener((bind_host, listen_port), backlog=128)
        self.address = self.listener.address
        self._shutdown = False
        # Trace id of `runtime::init` (set by ray_tpu.init()); the spans of
        # shutdown() join it, so a run's record finds both.
        self.trace_id: Optional[str] = None
        self._conn_to_worker: Dict[Any, str] = {}
        self._conns_version = 0
        # Multi-host plane: per-node daemon processes owning remote worker
        # pools (ray: raylet main.cc) — node_id -> daemon conn, plus the
        # reverse map for EOF (= node death) detection in the io loop.
        self.node_daemons: Dict[str, Any] = {}
        self._conn_to_daemon: Dict[Any, str] = {}
        self._daemon_procs: Dict[str, Any] = {}  # node_id -> Popen (local launch)
        # wid -> (rss, used, limit): daemons report OOM kills BEFORE the
        # SIGKILL so the ensuing crash is classified as retriable OOM.
        self._oom_kills: Dict[str, tuple] = {}
        # wid -> deadline: a daemon-owned worker's conn EOF waits briefly
        # for its daemon's authoritative worker_exited (which says WHY —
        # the two arrive on different sockets and can reorder).
        self._deferred_crashes: Dict[str, float] = {}
        # nid -> last heartbeat time: timeout-based node death detection
        # on top of conn EOF (ray: gcs_health_check_manager.h:39).
        self._daemon_heartbeats: Dict[str, float] = {}
        # wid -> error text: runtime-env setup failures (non-retriable).
        self._env_failures: Dict[str, str] = {}
        # planned node removals: their daemon EOF is routine, not failure
        self._expected_node_removals: "Set[str]" = set()
        # workers on nodes being removed: their EOFs are routine stops
        self._expected_worker_stops: "Set[str]" = set()
        # Elastic capacity (autoscaler plane): journaled node lifecycle —
        # node_id -> {"node_id", "state", ...riders}.  States walk
        # REQUESTED -> STARTING -> ACTIVE -> DRAINING -> DEPARTED; every
        # transition goes through _set_node_lifecycle (journal kind
        # "node_lifecycle") so a restarted head replays them — a node that
        # died mid-DRAINING resumes draining when its daemon re-registers.
        # Only persistable fields live in the record; head-local timing
        # stays in the autoscaler (the PR-11 monotonic-field rule).
        self.node_lifecycle: Dict[str, dict] = {}
        # node_id -> daemon OS pid (from the registration hello): lets the
        # state API name the process a chaos harness must crash-kill to
        # simulate a node death mid-drain.
        self.node_daemon_pids: Dict[str, int] = {}
        # Attached by _private/autoscaler.attach_autoscaler when the
        # autoscale_enabled knob is on (or a test attaches one directly).
        self._autoscaler = None
        # Attached driver clients (head-split mode, head.py): did -> conn,
        # plus the pseudo-node each non-co-located driver reads objects as,
        # and per-driver ref borrows dropped on driver death
        # (ray: gcs_job_manager OnJobFinished cleanup).
        self.drivers: Dict[str, Any] = {}
        self.driver_nodes: Dict[str, str] = {}
        self._conn_to_driver: Dict[Any, str] = {}
        self.driver_refs: Dict[str, Dict[str, int]] = {}
        # Control-plane persistence (ray: gcs storage,
        # gcs/store_client/redis_store_client.h — ours is a snapshot file):
        # named/detached actors, KV, functions, PGs, object directory.
        self.snapshot_path = snapshot_path
        self._journal = None
        self._snapshot_kick = threading.Event()
        if snapshot_path:
            from ray_tpu._private.gcs_storage import (
                make_mutation_journal,
                make_snapshot_storage,
            )

            self._snapshot_storage = make_snapshot_storage(snapshot_path)
            if _config.get("gcs_journal"):
                self._journal = make_mutation_journal(
                    snapshot_path, self.session_name
                )
            self._journal_compact_bytes = _config.get("gcs_journal_compact_bytes")
        else:
            self._snapshot_storage = None
        self._restored_actors: Set[str] = set()
        # Inline-result lineage (oids whose bytes lived ONLY in this
        # process): journaled + snapshotted so a post-restart get() can
        # re-execute the producer instead of erroring/parking forever.
        self._inline_lineage: Set[str] = set()
        # Log pipeline (ray: log_monitor.py + driver print subscriber):
        # head workers' stdout/stderr redirect into per-worker files under
        # log_dir; a LogMonitor tails them (daemons tail their own nodes
        # and forward over their conns); every line lands in a per-worker
        # ring buffer (CLI/dashboard) and echoes to this process's stdout.
        self.log_dir = f"/tmp/raytpu-logs-{self.session_name}"
        # Structured cluster events (SURVEY §2.1 event framework —
        # ray: src/ray/util/event.h:102): severity/source records of
        # control-plane transitions, durable JSONL + in-memory ring.
        from ray_tpu._private.events import EventLog

        self.events = EventLog(os.path.join(self.log_dir, "events.jsonl"))
        self.events.emit(
            "INFO", "runtime", "session started", session=self.session_name
        )
        # Planning failures that need operator eyes (inconsistent
        # mesh_coord labels) surface through the same event log.
        self.scheduler.events = self.events
        # RESHAPING pg_ids already announced through the mesh.member_death
        # fault point (the sweep fires it once per episode, off the lock).
        self._remesh_announced: "Set[str]" = set()
        self.worker_logs: Dict[str, deque] = {}
        self.log_to_driver = _config.get("log_to_driver") != 0
        from ray_tpu._private.log_monitor import LogMonitor

        self._log_monitor = LogMonitor(self.log_dir, self._on_log_lines)
        if snapshot_path:
            self._restore_snapshot()
            if self._journal is not None:
                # Fold the just-replayed journal into a fresh snapshot NOW:
                # the reset inside _write_snapshot would otherwise race a
                # crash-before-first-tick (old snapshot on disk, replayed
                # entries gone).
                try:
                    self._write_snapshot()
                except Exception:
                    pass
                # Mutations from here on are journaled (the hook is
                # installed before the accept/io threads below can deliver
                # any request).
                self.state.journal_hook = self._journal_append
            threading.Thread(
                target=self._snapshot_loop, daemon=True, name="raytpu-snapshot"
            ).start()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="raytpu-accept"
        )
        self._io_thread = threading.Thread(target=self._io_loop, daemon=True, name="raytpu-io")
        self._accept_thread.start()
        self._io_thread.start()
        # Telemetry plane, head side: arm the flight recorder in this
        # process (workers/daemons/drivers arm their own at entry) and
        # start the aggregation tick — the head "pushes to itself" by
        # ingesting its own registry + internal queue-depth gauges, then
        # folds the cluster aggregate into the time-series rings.
        _telemetry.install(faults._PROC_TAG)
        if _config.get("metrics_push_ms") > 0:
            threading.Thread(
                target=self._telemetry_loop, daemon=True,
                name="raytpu-telemetry",
            ).start()

        # Head-node OOM protection: the head process doubles as this node's
        # daemon for locally-spawned workers, so it runs the same memory
        # monitor a node daemon does (ray: memory_monitor.h:52 — the raylet
        # embeds the monitor; our daemon nodes run their own copy).
        self._mem_monitor = None
        refresh_ms = _config.get("memory_monitor_refresh_ms")
        if refresh_ms > 0:
            from ray_tpu._private.memory_monitor import MemoryMonitor

            def _local_workers():
                with self.lock:
                    return {
                        wid: (h.pid, h.spawn_ts)
                        for wid, h in self.workers.items()
                        if isinstance(h.proc, _PopenHandle)
                        and h.pid
                        and h.state != "dead"
                    }

            def _oom_kill(wid, rss, used, limit):
                with self.lock:
                    h = self.workers.get(wid)
                    if h is None or h.state == "dead":
                        return
                    self._oom_kills[wid] = (rss, used, limit)
                    try:
                        h.proc.kill()
                    except OSError:
                        pass
                # reaper/conn-EOF classifies the death as OOM via the flag

            self._mem_monitor = MemoryMonitor(
                _local_workers,
                _oom_kill,
                limit_bytes=_config.get("memory_limit_bytes"),
                threshold=_config.get("memory_usage_threshold"),
                interval_s=refresh_ms / 1000.0,
                policy=_config.get("oom_worker_killing_policy"),
            )
            self._mem_monitor.start()

        set_ref_hooks(self._addref_local, self._decref_local)
        atexit.register(self.shutdown)

        # Prestart a warm worker pool (ray: src/ray/raylet/worker_pool.h:156
        # prestarts workers per language): exec'ed workers pay a fresh
        # interpreter start, so overlap that cost with driver setup.
        with self.lock:
            for _ in range(
                min(
                    int(self.state.nodes[self.head_node_id].resources.get("CPU", 0)),
                    _config.get("worker_prestart_count"),
                )
            ):
                self._spawn_worker(self.head_node_id, None, None, prestart=True)

        # Elastic capacity: the demand-driven reconcile loop (its own
        # thread, off the runtime lock) when the knob asks for it.
        if _config.get("autoscale_enabled"):
            from ray_tpu._private.autoscaler import attach_autoscaler

            attach_autoscaler(self)

    # ------------------------------------------------------------------
    # log pipeline (ray: log_monitor.py + worker print redirection)

    def _on_log_lines(self, wid: str, stream: str, lines: List[str]) -> None:
        from ray_tpu._private import config as _config

        buf = self.worker_logs.get(wid)
        if buf is None:
            buf = self.worker_logs.setdefault(
                wid, deque(maxlen=_config.get("worker_log_ring_lines"))
            )
        buf.extend(lines)
        # Log channel on the shared pubsub plane (ray: the reference's log
        # channel is a publisher channel too) — dashboards/CLIs can follow
        # a worker's output push-style instead of polling get_logs.
        self.pubsub.publish("logs", wid, stream, lines)
        if self.log_to_driver:
            from ray_tpu._private.log_monitor import format_log_lines

            out = format_log_lines(wid, stream, lines)
            try:
                import sys

                sys.stdout.write(out)
                sys.stdout.flush()
            except (OSError, ValueError):
                pass  # driver stdout closed (interpreter teardown)

    def get_logs(self, wid: str, n: Optional[int] = None) -> List[str]:
        buf = self.worker_logs.get(wid)
        if buf is None:
            return []
        lines = list(buf)
        return lines[-n:] if n else lines

    # ------------------------------------------------------------------
    # control-plane persistence (ray: gcs storage + gcs_actor_manager
    # recovery; ours snapshots the metadata tables to one file)

    def _snapshot_loop(self) -> None:
        while not self._shutdown:
            # The kick short-circuits the tick when the journal crosses its
            # compaction threshold (the snapshot folds the journal in).
            self._snapshot_kick.wait(0.5)
            self._snapshot_kick.clear()
            if self._shutdown:
                return
            try:
                self._write_snapshot()
            except Exception:
                pass  # next tick retries; persistence is best-effort

    def head_telemetry_snapshot(self) -> dict:
        """This process's telemetry snapshot plus the head-internal gauges
        remote processes can't see (scheduler/lease queue depths, journal
        counters, store occupancy).  Used by the telemetry tick AND the
        read-time fresh ingest (state API / prometheus endpoint) so both
        carry the same fields."""
        from ray_tpu._private import telemetry as _telemetry

        with self.lock:
            internal = {
                "head_ready_queue_depth": float(len(self.ready_queue)),
                "head_live_tasks": float(len(self.tasks)),
                "head_peer_leases": float(len(self.peer_leases)),
                "head_pending_fences": float(len(self._pending_fences)),
                "head_live_workers": float(
                    sum(1 for h in self.workers.values() if h.state != "dead")
                ),
                "journal_entries": float(
                    self._journal.entries if self._journal else 0
                ),
                "journal_appends": float(
                    self._journal.writes if self._journal
                    else self.metrics["journal_appends"]
                ),
                "journal_fsyncs": float(
                    self._journal.fsyncs if self._journal
                    else self.metrics["journal_fsyncs"]
                ),
                "head_task_leases": float(
                    sum(len(p) for p in self.task_leases.values())
                ),
                "task_leases_granted": float(
                    self.metrics["task_leases_granted"]
                ),
                "task_leases_revoked": float(
                    self.metrics["task_leases_revoked"]
                ),
                "lease_dispatches": float(self.metrics["lease_dispatches"]),
                "head_ready_spilled": float(
                    self._ready_spill.count if self._ready_spill else 0
                ),
                "tasks_finished": float(self.metrics["tasks_finished"]),
                "tasks_failed": float(self.metrics["tasks_failed"]),
                # Elastic-capacity demand gauges (O(shapes): bucket heads
                # are the oldest entries, counts come from deque lengths).
                "autoscale_demand_tasks": float(len(self.ready_queue)),
                "autoscale_demand_buckets": float(
                    len(self.ready_queue.buckets)
                ),
                "autoscale_pending_bundles": float(
                    sum(
                        len(pg.bundles)
                        for pg in self.state.placement_groups.values()
                        if pg.state in ("PENDING", "RESHAPING")
                    )
                ),
                "autoscale_nodes_active": float(
                    sum(
                        1 for r in self.node_lifecycle.values()
                        if r.get("state") == "ACTIVE"
                    )
                ),
                "autoscale_nodes_draining": float(
                    sum(
                        1 for r in self.node_lifecycle.values()
                        if r.get("state") == "DRAINING"
                    )
                ),
            }
        internal["object_store_bytes_used"] = float(self.store.shm_usage())
        internal["objects_spilled"] = float(len(self.store._spilled))
        return _telemetry.snapshot_process(extra=internal)

    def _telemetry_loop(self) -> None:
        """Head-side telemetry tick (telemetry.py): snapshot this
        process's registry + internal queue-depth gauges into the sink,
        then fold the cluster aggregate into the time-series rings.  One
        sample per metrics_push_ms — same period the remote pushers use."""
        from ray_tpu._private import config as _config

        period = max(_config.get("metrics_push_ms"), 250) / 1000.0
        while not self._shutdown:
            time.sleep(period)
            if self._shutdown:
                return
            try:
                self.telemetry.ingest("head", self.head_telemetry_snapshot())
                self._ledger_tick()
                self.telemetry.sample()
            except Exception:
                pass  # telemetry must never take the control plane down

    def _journal_append(self, entry: tuple) -> None:
        """GlobalState journal hook + inline-lineage writer: mirror one
        control-plane mutation into the append-only journal (group-
        committed — see MutationJournal).  Best-effort by contract — a
        failed append degrades this mutation back to snapshot-tick
        durability, and the reconciliation handshake covers the actor
        records regardless."""
        j = self._journal
        if j is None:
            return
        try:
            j.append(entry)
        except Exception:
            return
        # Mirror the journal's own counters (the flusher thread advances
        # writes/fsyncs asynchronously; entries advance here).  NOTE the
        # post-group-commit meaning: journal_appends = PHYSICAL writes,
        # journal_entries = logical mutations — their ratio is the
        # group-commit factor, same shape as wire writes_per_op.
        self.metrics["journal_entries"] = j.entries
        self.metrics["journal_appends"] = j.writes
        self.metrics["journal_fsyncs"] = j.fsyncs
        if j.size_bytes() >= self._journal_compact_bytes:
            self._snapshot_kick.set()

    def _set_node_lifecycle(self, node_id: str, state: str, **kw) -> None:
        """Journaled node-lifecycle transition (REQUESTED -> STARTING ->
        ACTIVE -> DRAINING -> DEPARTED).  Caller holds self.lock.  The
        record carries only persistable riders (reason, provider tag);
        head-local monotonic timing lives with the autoscaler, never in
        the journal — a replayed DRAINING node re-arms fresh windows."""
        rec = self.node_lifecycle.setdefault(node_id, {"node_id": node_id})
        if rec.get("state") == "DEPARTED":
            # Terminal: a node that died mid-drain must keep its death
            # record even if the in-flight drain step finishes its (now
            # empty) evacuation and tries to close the drain as planned.
            return
        if rec.get("state") == state and all(
            rec.get(k) == v for k, v in kw.items()
        ):
            return  # no-op re-assertion: don't re-journal it
        rec["state"] = state
        rec.update(kw)
        self._journal_append(("node_lifecycle", node_id, state, dict(kw)))
        self.events.emit(
            "INFO", "autoscale", "node lifecycle", node_id=node_id,
            state=state, **kw,
        )

    def demand_summary(self) -> dict:
        """The head's published resource-demand view — what the autoscaler
        reconciles against and `ray_tpu status` renders: unplaceable/queued
        SchedulingKey buckets with wait-age, pending + RESHAPING placement
        -group bundles, and serve deployments' replica targets (published
        into the KV plane by the serve controller's reconcile loop)."""
        now_wall = time.time()
        with self.lock:
            buckets = []
            for shape, q in self.ready_queue.buckets.items():
                # Buckets are FIFO: the head task is the oldest, so the
                # scan stays O(shapes), never O(queued tasks).
                head = None
                for tid in q:
                    rec = self.tasks.get(tid)
                    if rec is not None and not rec.cancelled:
                        head = rec
                        break
                if head is None:
                    continue
                t = head.stages.get("queued") or head.stages.get("submit")
                buckets.append(
                    {
                        "key": repr(shape),
                        "resources": dict(head.spec.resources),
                        "count": len(q),
                        "wait_s": round(max(now_wall - t, 0.0), 3)
                        if t is not None
                        else 0.0,
                    }
                )
            spilled = self._ready_spill.count if self._ready_spill else 0
            pending_bundles = []
            for pg in self.state.placement_groups.values():
                if pg.state in ("PENDING", "RESHAPING"):
                    pending_bundles.append(
                        {
                            "pg_id": pg.pg_id,
                            "state": pg.state,
                            "bundles": [dict(b) for b in pg.bundles],
                        }
                    )
        import json as _json

        serve_targets = {}
        raw = self.state.kv_get("replica_targets", "serve")
        if raw:
            try:
                serve_targets = _json.loads(raw.decode())
            except (ValueError, UnicodeDecodeError):
                serve_targets = {}
        return {
            "task_buckets": buckets,
            "queued_tasks": sum(b["count"] for b in buckets) + spilled,
            "max_wait_s": max((b["wait_s"] for b in buckets), default=0.0),
            "pending_bundles": pending_bundles,
            "serve_targets": serve_targets,
        }

    def _write_snapshot(self) -> None:
        from ray_tpu._private.gcs import actor_record

        # Lock order everywhere else is self.lock -> state.lock (handshake
        # and io threads take self.lock then call into GlobalState); taking
        # them in the opposite order here would be an ABBA deadlock.
        with self.lock, self.state.lock:
            # EVERY live actor record is persisted — anonymous ones too
            # (ray: gcs_actor_manager keeps all records in the GCS tables;
            # only terminal DEAD rows are dropped, restore skips them
            # anyway).  Anonymous records are what let a replica that died
            # during a head outage be re-resolved and restarted.
            actors = [
                actor_record(info)
                for info in self.state.actors.values()
                if info.state != DEAD
            ]
            # In-flight PLAIN task specs: a head crash mid-flight re-drives
            # them on restart so their results still materialize for
            # reconnected drivers (ray: lineage-based resubmission after
            # GCS failover).  Actor work re-drives via the actor records;
            # oversized arg blobs are skipped — their argument objects
            # would not survive the head's store anyway.
            from ray_tpu._private import config as _cfg

            max_blob = _cfg.get("snapshot_inflight_max_blob_bytes")
            max_tasks = _cfg.get("snapshot_inflight_max_tasks")
            inflight = []
            for rec in self.tasks.values():
                spec = rec.spec
                if (
                    spec.actor_id is None
                    and not spec.is_actor_creation
                    and not rec.cancelled
                    and len(spec.args_blob or b"") <= max_blob
                ):
                    inflight.append(spec)
                    if len(inflight) >= max_tasks:
                        break
            snap = {
                "session": self.session_name,
                "kv": {ns: dict(d) for ns, d in self.state.kv.items()},
                "functions": dict(self.state.functions),
                "actors": actors,
                "placement_groups": {
                    pid: _pg_record(pg)
                    for pid, pg in self.state.placement_groups.items()
                    if pg.state != "REMOVED"
                },
                "object_locations": {
                    k: set(v) for k, v in self.object_locations.items()
                },
                "object_sizes": dict(self.object_sizes),
                "inflight_tasks": inflight,
                "jobs": {jid: dict(rec) for jid, rec in self.state.jobs.items()},
                # Autoscaler node-lifecycle table (journal kind
                # "node_lifecycle" folds in on top at restore).
                "node_lifecycle": {
                    nid: dict(rec) for nid, rec in self.node_lifecycle.items()
                },
                # Completed inline results' producer specs (bounded: a
                # subset of the lineage table, which lineage_max_bytes /
                # lineage_max_entries already cap) — these bytes live only
                # in this process, so lineage is their ONLY recovery.
                "lineage": [
                    (oid, self.lineage[oid])
                    for oid in self.lineage
                    if oid in self._inline_lineage
                ],
            }
        self._snapshot_storage.save(self.session_name, snap)
        if self._journal is not None:
            # Compaction: the snapshot now contains every journaled
            # mutation.  Skipped when the save above raised (the journal
            # then still replays over the PREVIOUS snapshot).
            self._journal.reset()

    def _restore_snapshot(self) -> None:
        """Replay persisted control state on head restart: KV, exported
        functions, the object directory, PGs (re-reserved as nodes return),
        inline-result lineage, the job table, and ALL actor records —
        named, detached, AND anonymous (recreated from their creation
        specs; live-worker adoption / re-announcement upgrades this when
        the worker reconnects).  The rebuilt actor table is snapshot +
        journal replay; the reconciliation handshake layers worker
        re-announcements on top."""
        snap = self._snapshot_storage.load(self.session_name)
        journal_entries = self._journal.replay() if self._journal is not None else []
        if snap is None and not journal_entries:
            return
        snap = snap or {}
        from ray_tpu._private import config as _config
        for ns, d in snap.get("kv", {}).items():
            self.state.kv.setdefault(ns, {}).update(d)
        self.state.import_functions(snap.get("functions", {}))
        for oid, locs in snap.get("object_locations", {}).items():
            self.object_locations.setdefault(oid, set()).update(locs)
            # Surviving node copies must satisfy gets on the restarted
            # head: without the readiness mark, a get would park forever
            # next to bytes the directory knows about.
            self.store.mark_remote_sealed(oid)
        self.object_sizes.update(snap.get("object_sizes", {}))
        # PG table: snapshot rows merged with journal replay below (the
        # dict form is pg_record; pre-remesh snapshots held 4-tuples).
        pgs_by_id: Dict[str, dict] = {}
        for pid, rec in snap.get("placement_groups", {}).items():
            if isinstance(rec, dict):
                pgs_by_id[pid] = dict(rec)
            else:
                bundles, strategy, name, pstate = rec
                pgs_by_id[pid] = {
                    "pg_id": pid, "bundles": bundles, "strategy": strategy,
                    "name": name, "state": pstate,
                }
        # ---- merge the actor/job tables: snapshot + journal replay.  The
        # journal holds every mutation since the snapshot's tick (torn
        # tail already truncated by replay()), so applying the entries in
        # order rebuilds the tables as of the crash.
        actors_by_id = {a["actor_id"]: dict(a) for a in snap.get("actors", [])}
        jobs: Dict[str, dict] = {
            jid: dict(rec) for jid, rec in snap.get("jobs", {}).items()
        }
        node_lc: Dict[str, dict] = {
            nid: dict(rec)
            for nid, rec in snap.get("node_lifecycle", {}).items()
        }
        restored_lineage = list(snap.get("lineage", []))
        for entry in journal_entries:
            try:
                kind = entry[0]
                if kind == "actor_register":
                    rec = dict(entry[1])
                    actors_by_id[rec["actor_id"]] = rec
                elif kind == "actor_state":
                    _, aid, astate, kw = entry
                    rec = actors_by_id.get(aid)
                    if rec is not None:
                        rec["state"] = astate
                        for k, v in kw.items():
                            rec[k] = v
                elif kind == "job_state":
                    _, jid, jstate, kw = entry
                    jobs.setdefault(jid, {"job_id": jid}).update(
                        {"state": jstate, **kw}
                    )
                elif kind == "pg_register":
                    rec = dict(entry[1])
                    pgs_by_id[rec["pg_id"]] = rec
                elif kind == "pg_state":
                    _, pid, pstate, kw = entry
                    rec = pgs_by_id.get(pid)
                    if rec is not None:
                        rec["state"] = pstate
                        rec.update(kw)
                elif kind == "node_lifecycle":
                    _, nid, nstate, kw = entry
                    rec = node_lc.setdefault(nid, {"node_id": nid})
                    rec["state"] = nstate
                    rec.update(kw)
                elif kind == "lineage":
                    restored_lineage.append((entry[1], entry[2]))
                elif kind == "function":
                    # Function exports journaled since the last snapshot:
                    # without these, a lineage re-execution of a task whose
                    # fn was exported within the final 0.5s tick fails
                    # "unknown function" (the PR-4 residual).
                    self.state.import_functions({entry[1]: entry[2]})
            except (IndexError, KeyError, TypeError, ValueError):
                continue  # malformed journal entry: skip, don't block boot
        for jid, rec in jobs.items():
            kw = {k: v for k, v in rec.items() if k not in ("job_id", "state")}
            self.state.set_job_state(jid, rec.get("state", "RUNNING"), **kw)
        # Node-lifecycle restore decisions (the journal-coverage lint's
        # KNOWN_KINDS entry documents these):
        #   DEPARTED  — stays departed (terminal; a retried drain across the
        #               bounce answers instead of re-draining a ghost);
        #   DRAINING  — resumes draining: the daemon's re-registration
        #               re-marks NodeInfo.draining and the reconciler picks
        #               the drain back up with FRESH timing windows (the
        #               PR-11 rule: never skip ahead on stale wall-clock);
        #   REQUESTED/STARTING — kept as-is; the reconciler re-checks them
        #               against the provider and re-arms the launch timeout;
        #   ACTIVE    — re-confirmed by the daemon's reconnect (the death
        #               path flips it to DEPARTED if it never comes back).
        self.node_lifecycle.update(node_lc)
        for pid, rec in pgs_by_id.items():
            if pid in self.state.placement_groups:
                continue
            try:
                pg = PlacementGroupInfo(
                    pid, rec["bundles"], rec["strategy"], name=rec.get("name"),
                    orig_bundles=[
                        dict(b)
                        for b in (rec.get("orig_bundles") or rec["bundles"])
                    ],
                    generation=int(rec.get("generation", 0)),
                    lost_node=rec.get("lost_node"),
                )
            except (KeyError, TypeError):
                continue  # malformed record: skip, don't block boot
            pstate = rec.get("state", "PENDING")
            if pstate == "REMOVED":
                # Kept (not re-queued) so a retried pg_remove/pg_state
                # across the bounce answers instead of "unknown pg".
                pg.state = "REMOVED"
                self.state.restore_pg(pg)
            elif pstate == "RESHAPING":
                # Died mid-reshape: resume the episode.  The wait deadline
                # is head-local and NOT persisted — the sweep re-arms a
                # fresh remesh_wait_s window on first sight (a bounce
                # extends the replacement wait; it never skips straight to
                # shrink on stale wall-clock).
                pg.state = "RESHAPING"
                self.state.restore_pg(pg)
            else:
                # PENDING and CREATED both re-reserve: bundle reservations
                # are volatile, the rebuilt node table re-acquires them.
                self.state.restore_pg(pg)
                self.pending_pgs.append(pid)
        # Inline-result lineage: the bytes died with the old head, but the
        # producer specs survive — a get() on one of these re-executes from
        # lineage instead of parking forever (ray: task_manager.h:97 +
        # object_recovery_manager.h:41 across GCS failover).
        with self.lock:
            for oid, spec in restored_lineage:
                try:
                    self._lineage_record(oid, spec)
                    self._inline_lineage.add(oid)
                except Exception:
                    continue
        for a in actors_by_id.values():
            if a["state"] == DEAD or a["actor_id"] in self.state.actors:
                continue
            spec = a["creation_spec"]
            if spec is None:
                continue
            if (
                a.get("owner_did")
                and not a["detached"]
                and jobs.get(a["owner_did"], {}).get("state") == "FINISHED"
            ):
                continue  # non-detached actor whose owner job already ended
            info = ActorInfo(
                actor_id=a["actor_id"],
                name=a["name"],
                namespace=a["namespace"],
                max_restarts=a["max_restarts"],
                num_restarts=a.get("num_restarts", 0),
                creation_spec=spec,
                detached=a["detached"],
                owner_did=a.get("owner_did"),
                state=RESTARTING,
                worker_id=a.get("worker_id"),
                node_id=a.get("node_id"),
            )
            try:
                self.state.register_actor(info)
            except ValueError:
                continue
            self.actors[spec.actor_id] = ActorRuntime(info)
            self._restored_actors.add(spec.actor_id)
        if self._restored_actors:
            # Give live workers the adoption grace to reconnect and re-bind
            # (actor memory state PRESERVED); whatever stays unbound is then
            # respawned from its creation spec (state reset; anonymous
            # actors charge their restart budget for the outage death) —
            # ray: gcs_actor_manager reconstruction after GCS restart.
            t = threading.Timer(
                _config.get("actor_adopt_grace_s"), self._respawn_unbound_actors
            )
            t.daemon = True
            t.start()
            # Restored NON-detached actors whose owner driver never
            # re-attaches die with their job, exactly as they would have
            # on a live head (ray: OnJobFinished) — after a window long
            # enough for the owner's own reconnect loop to win.
            orphan_grace = max(
                _config.get("reconnect_window_s"),
                _config.get("actor_adopt_grace_s"),
            ) + 2.0
            orphans = [
                aid
                for aid in self._restored_actors
                if (ar := self.actors.get(aid)) is not None
                and ar.info.owner_did
                and not ar.info.detached
            ]
            if orphans:
                t2 = threading.Timer(
                    orphan_grace, self._reap_ownerless_actors, args=(orphans,)
                )
                t2.daemon = True
                t2.start()
        # Re-drive tasks that were in flight at the crash: their results
        # never sealed (or survive on a node — then the resubmit is
        # skipped), so reconnected drivers' gets park until the re-run
        # completes (ray: owner-side resubmission after failover).  Chains
        # re-drive together (a dep produced by another re-driven task
        # resolves when it runs); a dep with NO surviving copy and NO
        # re-driven producer is unrecoverable — its task fails with
        # ObjectLostError now instead of parking forever.  Infeasible
        # shapes PARK (allow_pending) until the daemons rejoin.
        inflight = snap.get("inflight_tasks", [])
        will_produce = {o for s in inflight for o in s.return_ids()}
        for spec in inflight:
            if all(self.store.is_ready(o) for o in spec.return_ids()):
                continue
            lost = [
                d for d in spec.deps
                if not self.store.is_ready(d) and d not in will_produce
            ]
            if lost:
                self.events.emit(
                    "WARNING", "runtime",
                    "re-driven task dropped: input lost with the old head",
                    task=spec.name, missing=lost[0],
                )
                for oid in spec.return_ids():
                    self.store.put_error(oid, ObjectLostError(lost[0]))
                    self._object_ready(oid)
                continue
            spec.attempt = 0
            try:
                self.submit_task(spec, allow_pending=True)
            except Exception:
                continue  # malformed snapshot entry: skip, don't block boot

    def _respawn_unbound_actors(self) -> None:
        """Adoption grace expired: recreate restored actors whose worker
        never came back.  Named/detached actors respawn unconditionally
        (persistent by contract); anonymous actors — the records this PR
        made durable — charge their restart budget for the outage death,
        exactly as a live-head worker crash would (ray:
        gcs_actor_manager.h:258 counts ALIVE->dead transitions)."""
        specs = []
        with self.lock:
            doomed = []
            for aid in list(self._restored_actors):
                ar = self.actors.get(aid)
                self._restored_actors.discard(aid)
                if not (
                    ar is not None
                    and ar.info.state == RESTARTING
                    and ar.worker_id is None
                    and ar.info.creation_spec is not None
                ):
                    continue
                info = ar.info
                info.worker_id = None
                if info.detached or info.name:
                    specs.append(info.creation_spec)
                elif info.max_restarts == -1 or info.num_restarts < info.max_restarts:
                    self.metrics["actor_restarts"] += 1
                    self.events.emit(
                        "WARNING", "actor",
                        "anonymous actor restarting after head outage",
                        actor_id=aid, restart=info.num_restarts + 1,
                    )
                    # set_actor_state journals the charged budget, so a
                    # SECOND head bounce restores the decremented budget.
                    self.state.set_actor_state(
                        aid, RESTARTING, num_restarts=info.num_restarts + 1
                    )
                    specs.append(info.creation_spec)
                else:
                    doomed.append((aid, ar))
            for aid, ar in doomed:
                self.state.set_actor_state(
                    aid, DEAD,
                    death_cause="died during head outage; restart budget exhausted",
                )
                self._fail_actor_queue(ar, ActorDiedError(aid))
        for spec in specs:
            self.submit_task(spec)

    def _reap_ownerless_actors(self, candidates: List[str]) -> None:
        """Owner-reconnect grace expired: restored non-detached actors
        whose owning driver (job) never re-attached die with their job —
        the restarted head finishes what OnJobFinished would have done on
        a live head, and journals the job as FINISHED so the NEXT bounce
        does not resurrect them."""
        doomed = []
        with self.lock:
            for aid in candidates:
                ar = self.actors.get(aid)
                if ar is None or ar.info.state == DEAD:
                    continue
                did = ar.info.owner_did
                if did and did not in self.drivers:
                    doomed.append((aid, did))
            for _aid, did in doomed:
                if self.state.jobs.get(did, {}).get("state") != "FINISHED":
                    self.state.set_job_state(did, "FINISHED", reason="never re-attached")
        for aid, _did in doomed:
            self.events.emit(
                "INFO", "actor", "reaping actor of non-returning owner",
                actor_id=aid,
            )
            self.kill_actor(aid, no_restart=True)

    # ------------------------------------------------------------------
    # refcounting (owner side)

    def _addref_local(self, oid: str) -> None:
        self.store.add_ref(oid)

    def _decref_local(self, oid: str) -> None:
        if self._shutdown:
            return
        contained = None
        with self.lock:
            if self.store.refcount(oid) == 1:
                contained = self.contained_map.pop(oid, None)
            freed = self.store.remove_ref(oid)
            if freed:
                # No ref can ever need this object again — its lineage
                # entry is dead weight (ray: lineage release callback,
                # task_manager.h:116).
                entry = self.lineage.pop(oid, None)
                if entry is not None:
                    self.lineage_bytes -= self._lineage_cost(entry)
                self._inline_lineage.discard(oid)
                self.object_sizes.pop(oid, None)
                self.object_meta.pop(oid, None)
                self._xfer_plans.pop(oid, None)  # freed mid-broadcast
                # Remote copies die with the ownership release (ray: the
                # owner's directory drives eviction on every holder node).
                locs = self.object_locations.pop(oid, None)
                if locs:
                    for n in locs:
                        self._daemon_send(n, ("delete_object", oid))
        if contained:
            for c in contained:
                self._decref_local(c)

    def _store_contained(self, oid: str, contained: List[str]) -> None:
        if not contained:
            return
        with self.lock:
            self.contained_map[oid] = list(contained)
        for c in contained:
            self.store.add_ref(c)

    # ------------------------------------------------------------------
    # object ledger (memory introspection plane)

    def _obj_event(self, oid: str, event: str, nbytes=None, node=None) -> None:
        """Append one object lifecycle event (bounded ring; deque append
        is GIL-atomic — callable from under the store lock)."""
        try:
            self.object_events.append(
                {
                    "t": time.time(),
                    "oid": oid,
                    "event": event,
                    "bytes": nbytes,
                    "node": node or self.head_node_id,
                }
            )
        except Exception:
            pass  # observability never takes the control plane down

    def _on_store_lifecycle(self, oid: str, event: str, nbytes) -> None:
        # OwnerStore hook: spill/restore/free transitions (may fire under
        # store._lock — keep this append-only).
        self._obj_event(oid, event, nbytes)

    def _note_object(self, oid: str, creator: str) -> None:
        """First sighting of a sealed object: creation time + creator for
        the ledger's age/owner attribution (GIL-atomic dict write)."""
        if oid not in self.object_meta:
            self.object_meta[oid] = (time.time(), creator)

    def reclaim_dead_refs(self, force: bool = False) -> int:
        """Drop the outstanding ref borrows of crashed processes whose
        reclaim grace lapsed (the dead-holder leak suspects): each borrow
        decrefs like the lost refop del would have, freeing the bytes the
        dead holder pinned.  Returns the number of holders reclaimed.
        Runs on the io-loop reap tick; force=True (tests, shutdown paths)
        ignores the grace."""
        now = time.monotonic()
        with self.lock:
            doomed = [
                (wid, rec)
                for wid, rec in self._dead_refs.items()
                if force or now >= rec["reclaim_at"]
            ]
            for wid, _rec in doomed:
                self._dead_refs.pop(wid, None)
        for wid, rec in doomed:
            refs = rec.get("refs") or {}
            self.events.emit(
                "INFO", "object", "dead holder refs reclaimed",
                worker_id=wid, objects=len(refs),
                node_id=rec.get("node"),
            )
            for oid, n in refs.items():
                for _ in range(max(int(n), 0)):
                    self._decref_local(oid)
        return len(doomed)

    def _ledger_conn_refs(self):
        """Holder-side inputs of the ledger join: conn-tracked borrow
        tables (workers + attached drivers), this head process's own
        live-ref table, the pushed refs_push snapshots (sites/owned
        enrichment), and node/pid attribution per holder."""
        from ray_tpu._private import refs as refs_mod

        with self.lock:
            conn_refs: Dict[str, Dict[str, int]] = {
                w: dict(m) for w, m in self.worker_refs.items() if m
            }
            for did, m in self.driver_refs.items():
                if m:
                    conn_refs[did] = dict(m)
            proc_info: Dict[str, tuple] = {}
            for wid, h in self.workers.items():
                if h.state != "dead":
                    proc_info[wid] = (h.node_id, h.pid)
            for did in self.drivers:
                proc_info[did] = (self.driver_nodes.get(did), None)
        head_snap = refs_mod.snapshot_refs()
        conn_refs["head"] = {
            oid: rec[0] for oid, rec in head_snap["refs"].items()
        }
        proc_info["head"] = (self.head_node_id, os.getpid())
        pushed = self.ledger.snapshot()
        pushed["head"] = head_snap
        return conn_refs, pushed, proc_info

    def memory_records(self, limit: Optional[int] = None) -> List[dict]:
        """Per-object ledger records: the owner tables (store, directory,
        sizes, meta) joined with every holder-side ref table — the
        `ray memory` data model (SURVEY §2.1)."""
        from ray_tpu._private import config as _config
        from ray_tpu._private import telemetry as _telemetry

        store_table, rc, ready = self.store.snapshot_table()
        with self.lock:
            locations = {
                o: sorted(s) for o, s in self.object_locations.items()
            }
            sizes = dict(self.object_sizes)
            meta = dict(self.object_meta)
            dead = {w: dict(r) for w, r in self._dead_refs.items()}
        conn_refs, pushed, proc_info = self._ledger_conn_refs()
        recs = _telemetry.build_memory_records(
            store_table, rc, ready, locations, sizes, meta,
            conn_refs, pushed, dead, proc_info,
            now=time.time(), leak_age_s=_config.get("leak_age_s"),
        )
        return recs[:limit] if limit else recs

    def memory_summary(
        self,
        group_by: Optional[str] = None,
        top: int = 20,
        include_events: bool = False,
    ) -> dict:
        from ray_tpu._private import telemetry as _telemetry

        out = _telemetry.summarize_memory_records(
            self.memory_records(), group_by=group_by, top=top
        )
        if include_events:
            out["events"] = list(self.object_events)[-200:]
        return out

    # ------------------------------------------------------------------
    # profiling plane (profiler.py): cluster-wide sampling control + merge

    def profile_start(self, hz: Optional[float] = None) -> dict:
        """Start the sampler cluster-wide: locally in this process, and by
        pubsub broadcast in every subscribed worker ("profiler" channel,
        key "ctl").  Idempotent; returns the effective rate."""
        from ray_tpu._private import profiler as _profiler

        eff = _profiler.start(hz)
        self.pubsub.publish("profiler", "ctl", "start", eff)
        self.events.emit(
            "INFO", "profiler", "cluster-wide sampling started", hz=eff
        )
        return {"hz": eff}

    def profile_stop(self) -> dict:
        """Stop sampling cluster-wide.  Workers push a final table on the
        stop broadcast; tables already pushed stay in the sink for
        profile_report (cumulative payloads make this race-free)."""
        from ray_tpu._private import profiler as _profiler

        self.pubsub.publish("profiler", "ctl", "stop")
        _profiler.stop()
        return {"stopped": True}

    def profile_report(
        self, node: Optional[str] = None, pid: Optional[int] = None
    ) -> dict:
        """Merged flamegraph: every pushed per-process table plus a fresh
        local snapshot, optionally filtered to one node or pid."""
        from ray_tpu._private import profiler as _profiler

        snap = _profiler.snapshot_payload()
        if snap.get("n"):
            self.profiles.ingest("head", snap, node=self.head_node_id)
        return self.profiles.merged(node=node, pid=pid)

    def task_summary_local(self, slow: int = 10) -> dict:
        """Stage-attributed task summary over the finished-task ring +
        live tasks (the `ray_tpu tasks` body; pure fold in telemetry.py)."""
        from ray_tpu._private import telemetry as _telemetry

        now = time.time()
        with self.lock:
            events = [dict(e) for e in self.task_events]
            live = []
            for tid, rec in self.tasks.items():
                stages = dict(rec.stages)
                last = max(stages.values()) if stages else now
                live.append(
                    {
                        "task_id": tid,
                        "name": rec.spec.name,
                        "state": rec.state,
                        "stages": stages,
                        "age_s": round(now - stages.get("submit", last), 6),
                        "stuck_s": round(now - last, 6),
                    }
                )
        out = _telemetry.summarize_task_events(events, live, slow=slow)
        out["live"] = sorted(live, key=lambda t: -t["stuck_s"])[: max(slow, 0)]
        return out

    def _blocked_get_detail(self, oids) -> str:
        """Critical-path hint for a timed-out get(): which lifecycle stage
        each still-pending producing task is stuck in, and for how long —
        the one-line diagnosis a p99 hunt needs (never raises)."""
        try:
            from ray_tpu._private import telemetry as _telemetry

            now = time.time()
            parts = []
            with self.lock:
                for oid in list(oids)[:4]:
                    tid = oid.split(":")[1] if oid.startswith("o:") else None
                    rec = self.tasks.get(tid) if tid else None
                    if rec is None:
                        continue
                    present = [
                        s for s in _telemetry.STAGE_ORDER
                        if isinstance(rec.stages.get(s), (int, float))
                    ]
                    if not present:
                        continue
                    last = present[-1]
                    label = _telemetry.STAGE_LABELS.get(last, last)
                    durs = _telemetry.stage_durations(rec.stages)
                    hist = " ".join(
                        f"{k}={v:.3f}s" for k, v in durs.items()
                    )
                    parts.append(
                        f"task {tid} ({rec.spec.name}) stuck in stage "
                        f"'{label}' for {now - rec.stages[last]:.3f}s"
                        + (f" after [{hist}]" if hist else "")
                    )
            return "; ".join(parts)
        except Exception:
            return ""

    def get_logs_all(self, n: Optional[int] = None) -> dict:
        """Aggregate log tail across every worker that produced output,
        with node/pid attribution (`ray_tpu logs --all`)."""
        with self.lock:
            wids = list(self.worker_logs)
            info = {
                wid: (h.node_id, h.pid) for wid, h in self.workers.items()
            }
        out = {}
        for wid in wids:
            node, pid = info.get(wid, (None, None))
            out[wid] = {
                "node": node,
                "pid": pid,
                "lines": self.get_logs(wid, n),
            }
        return out

    def _ledger_tick(self) -> None:
        """Refresh the Prometheus-facing ledger gauges (per-node store/
        spilled bytes, per-node leak-suspect bytes) from a fresh join,
        and run the orphan reclaim sweep.  Runs on the head telemetry
        thread each push tick."""
        from ray_tpu._private import config as _config
        from ray_tpu._private import telemetry as _telemetry

        records = self.memory_records()
        summary = _telemetry.summarize_memory_records(records, top=0)
        # Orphan reclaim: a NO-LIVE-HOLDER suspect that stays flagged
        # across leak_orphan_reclaim_s of consecutive ticks has no path
        # back to a positive refcount (any process that could still send
        # the missing add would list the oid in its pushed ref table and
        # un-flag it) — free it, LOUDLY.  The shape this closes: after a
        # head bounce the restored store has no refcounts, a re-driven
        # task re-seals its result at rc 0, and the owner's already-sent
        # release sits buffered forever (the chaos soak's ledger
        # convergence assertion found exactly this).
        grace = _config.get("leak_orphan_reclaim_s")
        if grace > 0 and _config.get("refs_push"):
            now = time.monotonic()
            flagged = getattr(self, "_orphan_flagged", None)
            if flagged is None:
                flagged = self._orphan_flagged = {}
            current = {
                r["object_id"]: r
                for r in records
                if r["leak"] == "no-live-holder"
            }
            for oid in list(flagged):
                if oid not in current:
                    flagged.pop(oid, None)
            for oid, r in current.items():
                first = flagged.setdefault(oid, now)
                if now - first < grace:
                    continue
                flagged.pop(oid, None)
                self.events.emit(
                    "WARNING", "object",
                    "orphaned object reclaimed (no live holder)",
                    object_id=oid, size_bytes=r["size_bytes"],
                    age_s=r["age_s"],
                )
                self._decref_local(oid)  # rc 0 + known -> frees the bytes
        g_bytes, g_leak = _telemetry.ledger_gauges()
        leak_by_node: Dict[str, float] = {}
        for r in summary["leaks"]:
            node = next(
                (
                    h["node"]
                    for h in r["holders"]
                    if h.get("dead") and h.get("node")
                ),
                None,
            ) or "head"
            leak_by_node[node] = leak_by_node.get(node, 0.0) + float(
                r["size_bytes"] or 0
            )
        nodes = set(summary["nodes"]) | set(leak_by_node)
        stale = getattr(self, "_ledger_gauge_nodes", set()) - nodes
        for node, rec in summary["nodes"].items():
            g_bytes.set(
                rec["store_bytes"], tags={"node": str(node), "tier": "store"}
            )
            g_bytes.set(
                rec["spilled_bytes"],
                tags={"node": str(node), "tier": "spilled"},
            )
        for node in nodes:
            g_leak.set(leak_by_node.get(node, 0.0), tags={"node": str(node)})
        for node in stale:  # removed nodes zero out instead of lingering
            g_bytes.set(0.0, tags={"node": str(node), "tier": "store"})
            g_bytes.set(0.0, tags={"node": str(node), "tier": "spilled"})
            g_leak.set(0.0, tags={"node": str(node)})
        self._ledger_gauge_nodes = nodes

    # ------------------------------------------------------------------
    # worker pool (ray: src/ray/raylet/worker_pool.h:156)

    def _daemon_send(self, node_id: str, msg: tuple) -> None:
        conn = self.node_daemons.get(node_id)
        if conn is None:
            return
        try:
            conn.send(msg)
        except OSError:
            pass

    def _on_driver_death(self, did: str) -> None:
        """An attached driver's conn EOF'ed (exit or kill -9): the head
        lives on.  Drop the driver's ref borrows, kill its non-detached
        actors; lifetime="detached" actors keep serving
        (ray: gcs_actor_manager OnJobFinished + gcs_job_manager)."""
        self.telemetry.forget(did)
        self.ledger.forget(did)
        self.profiles.forget(did)
        with self.lock:
            self.drivers.pop(did, None)
            self.driver_nodes.pop(did, None)
            self._drop_remote_subs(did)
            self.state.set_job_state(did, "FINISHED", reason="driver death")
            refs = self.driver_refs.pop(did, {})
            doomed = [
                aid
                for aid, ar in self.actors.items()
                if ar.info.owner_did == did
                and not ar.info.detached
                and ar.info.state != DEAD
            ]
        for oid, count in refs.items():
            for _ in range(count):
                self._decref_local(oid)
        for aid in doomed:
            self.kill_actor(aid, no_restart=True)

    @_locked
    def _on_daemon_death(self, node_id: str) -> None:
        """Caller holds self.lock.  Node failure: the daemon's whole worker
        pool dies with it (the daemon terminates its children on exit)."""
        self.node_daemons.pop(node_id, None)
        self.node_object_endpoints.pop(node_id, None)
        self._daemon_heartbeats.pop(node_id, None)
        self.node_daemon_pids.pop(node_id, None)
        if node_id in self._expected_node_removals:
            self._expected_node_removals.discard(node_id)
            self.events.emit("INFO", "node", "node removed", node_id=node_id)
            planned = True
        else:
            self.events.emit("ERROR", "node", "node died", node_id=node_id)
            planned = False
        # Lifecycle: any tracked node leaving — planned depart OR death
        # (including a death MID-DRAIN, which from here on is exactly the
        # existing death path: lineage/retry covers what evacuation had
        # not yet moved) — lands in the terminal DEPARTED state.
        if node_id in self.node_lifecycle:
            self._set_node_lifecycle(
                node_id, "DEPARTED",
                reason="removed" if planned else "died",
            )
        # Copies on the dead node are gone; objects whose ONLY copy lived
        # there become lost-bytes (gets fall through to lineage
        # reconstruction, exactly like a lost spill file).
        for oid in list(self.object_locations):
            locs = self.object_locations[oid]
            locs.discard(node_id)
            if not locs:
                del self.object_locations[oid]
        # Transfer plans: the dead node's in-flight slot frees, and any
        # relay feed it was serving is withdrawn — downstreams fall back
        # to the sealed tail of their plan or re-ask (re-plan, not wedge).
        for oid in list(self._xfer_plans):
            self._release_pull_slot_locked(oid, node_id)
            st = self._xfer_plans.get(oid)
            if st is None:
                continue
            for ep, f in list(st["feeds"].items()):
                if f.get("node") == node_id:
                    del st["feeds"][ep]
        self.state.remove_node(node_id)
        for wid, h in list(self.workers.items()):
            if h.node_id == node_id and h.state != "dead":
                if isinstance(h.proc, _RemoteProcHandle):
                    h.proc.dead = True
                self._on_worker_crash(wid)
        # A MESH gang that lost this host is torn as a whole: withdraw it
        # and open a RESHAPING episode (the io-loop sweep advances it).
        self._withdraw_mesh_gangs(node_id)

    def _child_env(self, extra: Dict[str, str]) -> Dict[str, str]:
        """Base env for child processes (workers/daemons): driver address +
        authkey + a PYTHONPATH carrying the driver's module search path."""
        import sys

        host, port = self.address
        env = os.environ.copy()
        env.update(
            {
                "RAY_TPU_DRIVER_HOST": host,
                "RAY_TPU_DRIVER_PORT": str(port),
                "RAY_TPU_AUTHKEY": self._authkey.hex(),
            }
        )
        env.update(extra)
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        paths = [pkg_root] + [p for p in sys.path if p] + (
            env.get("PYTHONPATH", "").split(os.pathsep) if env.get("PYTHONPATH") else []
        )
        env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(paths))
        return env

    def add_daemon_node(
        self,
        num_cpus: float = 1.0,
        resources: Optional[Dict] = None,
        labels: Optional[Dict[str, str]] = None,
        wait_timeout: float = 30.0,
        store_root: Optional[str] = None,
    ) -> str:
        """Launch a node daemon PROCESS on this machine and wait for it to
        register (the test-side analogue of starting a raylet on another
        host; in a real deployment the daemon starts remotely pointing at
        this driver's address)."""
        import json
        import subprocess
        import sys

        nid = ids.node_id()
        env = self._child_env(
            {
                "RAY_TPU_NODE_CONFIG": json.dumps(
                    {
                        "node_id": nid,
                        "session": self.session_name,
                        "num_cpus": num_cpus,
                        "resources": resources or {},
                        "labels": labels or {},
                        "store_root": store_root,
                    }
                ),
            }
        )
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_daemon"],
            env=env,
            close_fds=True,
        )
        self._daemon_procs[nid] = proc
        deadline = time.monotonic() + wait_timeout
        while time.monotonic() < deadline:
            if nid in self.node_daemons:
                return nid
            if proc.poll() is not None:
                self._daemon_procs.pop(nid, None)
                raise RuntimeError(f"node daemon exited rc={proc.returncode}")
            time.sleep(0.01)
        # Kill the straggler BEFORE raising, or it could register moments
        # later as a phantom node the caller was told doesn't exist.
        try:
            proc.terminate()
        except OSError:
            pass
        self._daemon_procs.pop(nid, None)
        raise TimeoutError("node daemon did not register in time")

    def _spawn_worker(self, node_id: str, env_key, renv, prestart: bool = False) -> WorkerHandle:
        if node_id in self.node_daemons:
            # Remote-node spawn: the daemon execs the worker on its host;
            # the worker connects straight back to this driver.
            wid = ids.worker_id()
            self.metrics["workers_spawned"] += 1
            self._daemon_send(node_id, ("spawn_worker", wid, renv or {}))
            handle = WorkerHandle(
                wid, node_id, env_key, renv, _RemoteProcHandle(self, node_id, wid)
            )
            self.workers[wid] = handle
            if prestart:
                self.starting_pool.setdefault((node_id, env_key), []).append(wid)
            return handle
        return self._spawn_local_worker(node_id, env_key, renv, prestart)

    def _spawn_local_worker(self, node_id: str, env_key, renv, prestart: bool = False) -> WorkerHandle:
        # Workers are exec'ed as fresh interpreters (`python -m ..worker_proc`)
        # rather than multiprocessing children: mp's spawn/forkserver children
        # re-import the driver's __main__ module during bootstrap, which
        # re-runs unguarded user scripts (and fork would inherit the driver's
        # threads + live XLA client).  Matches the reference, whose raylet
        # execs default_worker.py (ray: src/ray/raylet/worker_pool.h:156,
        # python/ray/_private/workers/default_worker.py).  When the zygote
        # fork server is up, spawns fork from its pre-imported interpreter
        # instead (~2ms vs ~250ms) — see zygote.py.
        import subprocess
        import sys

        wid = ids.worker_id()
        self.metrics["workers_spawned"] += 1
        from ray_tpu._private.runtime_env import worker_env_entries

        env_vars = (renv or {}).get("env_vars") or {}
        extra = {
            "RAY_TPU_WORKER_ID": wid,
            "RAY_TPU_SESSION": self.session_name,
            # stdout redirects to a log file (block-buffered by default):
            # unbuffered, or prints sit invisible until the worker exits.
            "PYTHONUNBUFFERED": "1",
            # Head-node workers share the HEAD store (explicit, so a
            # RAY_TPU_STORE_DIR inherited from any outer environment can
            # never leak a foreign node's store into these workers).
            "RAY_TPU_STORE_DIR": self.store.shm.dir,
            **worker_env_entries(renv),
        }
        proc = self._zygote_fork(wid, extra, env_vars)
        if proc is None:
            env = self._child_env(extra)
            # runtime_env vars must exist at interpreter start (a
            # sitecustomize may read them before worker_main applies them).
            env.update({k: str(v) for k, v in env_vars.items()})
            from ray_tpu._private.log_monitor import open_worker_logs

            outf, errf = open_worker_logs(self.log_dir, wid)
            try:
                popen = subprocess.Popen(
                    [sys.executable, "-m", "ray_tpu._private.worker_proc"],
                    env=env,
                    close_fds=True,
                    stdout=outf,
                    stderr=errf,
                )
            finally:
                outf.close()  # the child holds its own dups; files outlive it
                errf.close()
            proc = _PopenHandle(popen)
        handle = WorkerHandle(wid, node_id, env_key, renv, proc)
        self.workers[wid] = handle
        if prestart:
            # Only unleased spawns are advertised as leasable; a demand spawn
            # is handed straight to its task.
            self.starting_pool.setdefault((node_id, env_key), []).append(wid)
        return handle

    def _zygote_fork(self, wid: str, extra: Dict[str, str], env_vars) -> Optional[_ZygoteProcHandle]:
        """Request a worker fork from the zygote; None = use the exec path
        (zygote not up yet / just died — it is (re)spawned in the
        background so the NEXT spawn forks)."""
        from ray_tpu._private import config as _config

        if not _config.get("use_zygote"):
            return None
        conn = self._zygote_conn
        if conn is None:
            self._ensure_zygote()
            return None
        # Start from the driver-env delta since the zygote's spawn: the
        # exec path re-snapshots os.environ per spawn, and fork-served
        # workers must not silently diverge (e.g. a token exported after
        # init must reach both kinds of worker).
        base = self._zygote_env or {}
        overrides = {
            k: v for k, v in os.environ.items() if base.get(k) != v
        }
        overrides.update(extra)
        overrides.update({k: str(v) for k, v in (env_vars or {}).items()})
        from ray_tpu._private.log_monitor import worker_log_paths

        os.makedirs(self.log_dir, exist_ok=True)
        out_path, err_path = worker_log_paths(self.log_dir, wid)
        try:
            conn.send(("fork", wid, overrides, out_path, err_path))
        except OSError:
            self._zygote_conn = None
            self._ensure_zygote()
            return None
        return _ZygoteProcHandle(self._zygote_proc)

    def _ensure_zygote(self) -> None:
        """Spawn the fork server (once; respawned if it dies).  Never
        blocks: callers fall back to exec'ed workers until the zygote's
        handshake lands."""
        import subprocess
        import sys

        if self._shutdown:
            return
        if self._zygote_spawning:
            # Pending spawn — unless it died before ever handshaking
            # (import crash): then respawn.
            if not (
                self._zygote_conn is None
                and self._zygote_proc is not None
                and self._zygote_proc.poll() is not None
            ):
                return
        self._zygote_spawning = True
        env = self._child_env({"PYTHONUNBUFFERED": "1"})
        self._zygote_env = dict(env)  # per-fork overrides diff against this
        try:
            self._zygote_proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.zygote"],
                env=env,
                close_fds=True,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
        except OSError:
            self._zygote_spawning = False

    def _lease_worker(self, node_id: str, spec: TaskSpec) -> WorkerHandle:
        renv = spec.runtime_env or None
        env_key = _runtime_env_key(renv)
        pool = self.idle_pool.get((node_id, env_key))
        while pool:
            wid = pool.pop()
            h = self.workers.get(wid)
            if h is not None and h.state == "idle":
                return h
        # A spawned-but-not-yet-connected worker is leasable: its task is
        # queued in pending_sends and flushed on connect.
        pool = self.starting_pool.get((node_id, env_key))
        while pool:
            wid = pool.pop()
            h = self.workers.get(wid)
            if h is not None and h.state == "starting":
                return h
        if env_key is None and node_id == self.head_node_id:
            # Pool miss under default env: learn the burst size so the
            # next wave binds to prestarted workers instead of paying a
            # boot on the critical path (ray: worker_pool.h:156 prestart;
            # the io-loop tick tops the pool back up to this target while
            # the driver waits on results — converting barrier idle time
            # into worker boots).
            self._prestart_target = min(self._prestart_target + 1, 64)
            self._prestart_miss_t = time.monotonic()
        return self._spawn_worker(node_id, env_key, renv)

    def _return_worker(self, h: WorkerHandle) -> None:
        if h.state == "dead":
            return
        # Safety net: returning a still-leased worker (conn-reset
        # re-drive, any future path) must revoke its lease first or the
        # held resources would strand.  No recursion — revoke pops the
        # binding before it ever calls back here.
        le = self.lease_by_worker.get(h.worker_id)
        if le is not None:
            self._revoke_lease_locked(
                le, cause="worker_returned", return_worker=False
            )
        h.state = "idle"
        h.current_task = None
        h.idle_since = time.monotonic()
        self.idle_pool.setdefault((h.node_id, h.env_key), []).append(h.worker_id)

    def _send(self, h: WorkerHandle, msg: tuple) -> None:
        if h.conn is None:
            h.pending_sends.append(msg)
        else:
            try:
                # error -> the existing OSError path (delivery lost, like a
                # conn that broke mid-send); drop -> same, minus the raise.
                if faults.ENABLED and faults.point(
                    "head.send", key=msg[0] if msg else None
                ) == "drop":
                    return
                h.conn.send(msg)
            except OSError:
                pass

    # ------------------------------------------------------------------
    # IO threads

    def _accept_loop(self):
        # Each connection's first-message handshake runs on its own thread:
        # a starting worker opens a kv_fetch side-channel BEFORE sending
        # "ready" on its main conn, so a serial accept loop would deadlock
        # (blocked recv'ing the main conn's handshake while the fetch conn
        # waits for service).
        from ray_tpu._private import wire
        from ray_tpu._private.netutil import set_nodelay

        while not self._shutdown:
            try:
                conn = self.listener.accept()
            except (OSError, EOFError):
                if self._shutdown:
                    return
                continue
            except Exception:
                continue  # accept-level failure; keep serving
            set_nodelay(conn)
            # The authkey challenge runs on the per-conn thread, NOT here:
            # inline challenges serialize every connect behind one thread —
            # at a 200-worker burst that was a measured ~16ms × N accept
            # queue (the head's own connect RTT to a busy fresh child).
            threading.Thread(
                target=self._auth_and_handshake, args=(conn,), daemon=True,
                name="raytpu-handshake",
            ).start()

    def _auth_and_handshake(self, rawconn) -> None:
        """Mutual HMAC challenge (what Listener(authkey=...) ran inline in
        accept), then the application handshake.  Same order as the stdlib
        server side — deliver first, answer second — so unchanged clients
        (multiprocessing.connection.Client with authkey) interoperate."""
        from multiprocessing.connection import answer_challenge, deliver_challenge

        from ray_tpu._private import wire

        try:
            deliver_challenge(rawconn, self._authkey)
            answer_challenge(rawconn, self._authkey)
        except Exception:  # stranger failed the auth challenge
            try:
                rawconn.close()
            except OSError:
                pass
            return
        self._handshake(wire.wrap(rawconn))

    def _handshake(self, conn) -> None:
        from ray_tpu._private.wire import PROTOCOL_VERSION, ProtocolError

        try:
            first = conn.recv()
        except ProtocolError as e:
            # Version/schema mismatch: tell the peer WHY before closing —
            # the clean rejection the raw-pickle plane never had
            # (ray: gRPC status + proto version negotiation).
            try:
                conn.send(("protocol_error", PROTOCOL_VERSION, str(e)))
            except OSError:
                pass
            conn.close()
            return
        except (OSError, EOFError):
            conn.close()
            return
        try:
            self._dispatch_handshake(conn, first)
        except Exception:
            import traceback

            traceback.print_exc()
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch_handshake(self, conn, first) -> None:
        from ray_tpu._private import wire

        if first[0] in ("ready", "driver", "daemon"):
            # Long-lived control conns get the coalescing sender: the
            # head's reply/pub/fence streams to this peer ride one
            # physical write per flush wave instead of one per frame.
            # Wrapped BEFORE registration so every map (conn_to_*,
            # selector) holds the same object identity.  One-shot conns
            # (kv/object fetch) and the zygote stay direct.
            conn = wire.batching(conn)
        if first[0] == "kv_fetch":
            # One-shot fetch channel: a STARTING worker materializes its
            # runtime-env packages before its main conn says "ready"
            # (the main conn can't serve requests yet — replies park
            # behind the ready handshake).
            try:
                conn.send(self.state.kv_get(first[1]))
            except OSError:
                pass
            conn.close()
            return
        if first[0] == "object_fetch":
            # One-shot transfer conn: a remote node pulls an object from
            # the HEAD store (this listener doubles as the head's object
            # server — no extra port).  Same streaming body as the daemon
            # ObjectServer, same admission bound, served on this
            # handshake thread.  Relay-capable peers (3rd field) may be
            # served out of an in-flight pull's transfer board.
            from ray_tpu._private import object_plane

            relay_ok = len(first) > 2 and bool(first[2])
            with self._transfer_sem:
                object_plane.stream_object(
                    conn, self.store.get_raw_packed, first[1],
                    self.store.read_board if relay_ok else None,
                )
            return
        if first[0] == "driver":
            # Attached driver client (head-split mode): ("driver", did,
            # pid[, t_sent]).  Reply with session metadata, then a second
            # message declares whether the driver co-locates with the head
            # store (zero-copy reads) or stays remote (ray://-style: conn
            # + transfer plane).
            did, _pid = first[1], first[2]
            if len(first) > 3 and isinstance(first[3], float):
                self.clock_offsets[did] = time.time() - first[3]
            try:
                from ray_tpu._private import config as _config

                conn.send(
                    (
                        "driver_ack",
                        {
                            "session": self.session_name,
                            "namespace": self.namespace,
                            "store_dir": self.store.shm.dir,
                            # Clients adopt the HEAD's reconnect window (the
                            # env knob lives in the head process, not in
                            # every attaching driver).
                            "reconnect_window_s": _config.get("reconnect_window_s"),
                        },
                    )
                )
                second = conn.recv()
            except (OSError, EOFError):
                conn.close()
                return
            shared = bool(second[2]) if second[0] == "driver_store" else False
            with self.lock:
                old = self.drivers.get(did)
                if old is not None and old is not conn:
                    # Reconnect over a LIVE head (transient TCP reset): the
                    # old conn's pending EOF must clean only itself — not
                    # declare the reconnected driver dead (the EOF handler
                    # checks drivers[did] identity) — and the borrow counts
                    # this driver still holds must survive.
                    self._conn_to_driver.pop(old, None)
                    try:
                        old.close()
                    except OSError:
                        pass
                self.drivers[did] = conn
                self._driver_death_grace.pop(did, None)  # reconnect won
                self.driver_nodes[did] = (
                    self.head_node_id if shared else f"drvnode-{did}"
                )
                self.driver_refs.setdefault(did, {})
                self._conn_to_driver[conn] = did
                self._conns_version += 1
                # Attached drivers are this build's jobs (ray:
                # gcs_job_manager): the journaled transition lets a
                # restarted head know which owners were already live.
                self.state.set_job_state(did, "RUNNING", pid=_pid)
            return
        if first[0] == "daemon":
            # Node daemon registration: ("daemon", node_id, cfg, pid).
            _, node_id, cfg, _pid = first
            res = {"CPU": float(cfg.get("num_cpus", 1.0)), **(cfg.get("resources") or {})}
            if isinstance(cfg.get("clock"), float):
                self.clock_offsets[f"daemon:{node_id}"] = (
                    time.time() - cfg["clock"]
                )
            with self.lock:
                if node_id not in self.state.nodes:
                    self.state.register_node(
                        NodeInfo(
                            node_id, dict(res), dict(res),
                            labels=dict(cfg.get("labels") or {}),
                        )
                    )
                ep = cfg.get("object_endpoint")
                if ep:
                    self.node_object_endpoints[node_id] = tuple(ep)
                self.node_daemons[node_id] = conn
                self._conn_to_daemon[conn] = node_id
                self.node_daemon_pids[node_id] = int(_pid)
                self._conns_version += 1
                # Lifecycle: a provider-launched node registering flips
                # REQUESTED/STARTING -> ACTIVE; a node that was DRAINING
                # when the head bounced RESUMES draining — the volatile
                # NodeInfo.draining flag is re-derived from the journaled
                # lifecycle record, so no new leases land on it and the
                # reconciler picks the drain back up.
                lc = self.node_lifecycle.get(node_id)
                if lc is not None:
                    if lc.get("state") in ("REQUESTED", "STARTING"):
                        self._set_node_lifecycle(node_id, "ACTIVE")
                    elif lc.get("state") == "DRAINING":
                        self.state.set_node_draining(node_id, True)
                self.events.emit("INFO", "node", "node registered", node_id=node_id)
                # Fresh liveness clock: a stale entry from a previous
                # incarnation of this node_id would instantly time the
                # reconnected daemon out before its first heartbeat.
                self._daemon_heartbeats[node_id] = time.monotonic()
                self._dispatch()
            return
        if first[0] == "zygote":
            # Fork server up: route subsequent local spawns through it.
            with self.lock:
                self._zygote_conn = conn
                self._zygote_spawning = False
            threading.Thread(
                target=self._zygote_loop, args=(conn,), daemon=True,
                name="raytpu-zygote",
            ).start()
            return
        if first[0] == "env_failed":
            # The worker's runtime-env setup failed BEFORE it could serve:
            # deterministic (a retry reinstalls the same broken env), so
            # its leased task fails with RuntimeEnvSetupError, not a
            # retriable crash (ray: RuntimeEnvSetupError semantics).
            with self.lock:
                h = self.workers.get(first[1])
                if h is not None and h.state != "dead":
                    # (storing for an already-classified worker would leak)
                    self._env_failures[first[1]] = str(first[2])
                    self._deferred_crashes.pop(first[1], None)
                    self._on_worker_crash(first[1])
            conn.close()
            return
        if first[0] != "ready":
            conn.close()
            return
        wid = first[1]
        if len(first) > 6 and isinstance(first[6], float):
            # Clock-offset estimate: receive time minus the sender's send
            # stamp (includes one-way latency — ms on loopback, fine for
            # ordering spans across processes in the merged timeline).
            self.clock_offsets[wid] = time.time() - first[6]
        adopted = False
        with self.lock:
            if len(first) > 4 and first[4]:
                self.worker_peer_endpoints[wid] = tuple(first[4])
            h = self.workers.get(wid)
            if h is None:
                h = self._adopt_worker(conn, first)
                if h is None:
                    conn.close()
                    return
                adopted = True
            else:
                h.pid = first[2]
        if adopted:
            return
        # Flush messages queued while the worker was starting OFF the
        # runtime lock (pipe I/O under the global lock stalls the whole
        # control plane if the pipe buffer is full; the concurrency lint's
        # blocking-under-lock pass flags the old shape).  Ordering holds:
        # h.conn stays None until the backlog drains, so concurrent
        # _send()s keep appending to pending_sends and every queued frame
        # precedes the first direct send; no other thread sees this conn
        # before the publication block below registers it.
        while True:
            with self.lock:
                pending = h.pending_sends
                if not pending:
                    h.conn = conn
                    # The reconnect landed: cancel any pending EOF-grace
                    # crash (set by the daemon-report defer) — firing it
                    # now would kill the healed worker.
                    self._deferred_crashes.pop(wid, None)
                    if h.state == "starting":
                        h.state = "idle"
                        h.idle_since = time.monotonic()
                        sp = self.starting_pool.get((h.node_id, h.env_key))
                        if sp and wid in sp:
                            sp.remove(wid)
                        self.idle_pool.setdefault(
                            (h.node_id, h.env_key), []
                        ).append(wid)
                    self._conn_to_worker[conn] = wid
                    self._conns_version += 1
                    self._grant_parked_leases(wid)
                    break
                h.pending_sends = []
                self._pending_send_flushes = (
                    getattr(self, "_pending_send_flushes", 0) + len(pending)
                )
                # Task frames queued while the worker booted go out NOW:
                # stamp their "pushed" stage (still under the lock — the
                # record may be concurrently finished by another conn).
                push_t = time.time()
                for msg in pending:
                    if msg[0] in ("task", "create_actor"):
                        prec = self.tasks.get(msg[1].task_id)
                        if prec is not None:
                            prec.stages.setdefault("pushed", push_t)
            for msg in pending:
                try:
                    conn.send(msg)
                except OSError:
                    pass
        announced = (
            first[7] if len(first) > 7 and isinstance(first[7], list) else None
        )
        if announced is not None:
            # Reconnect hello with an executor announcement: re-drive the
            # relayed work the dead conn lost (see _redrive_worker_relays).
            with self.lock:
                self._redrive_worker_relays(h, wid, set(announced))
        with self.lock:
            self._dispatch()

    @_locked
    def _adopt_worker(self, conn, first) -> Optional[WorkerHandle]:
        """Caller holds self.lock.  A worker this head never spawned says
        "ready": after a head restart, surviving workers reconnect within
        the window and are adopted — a restored actor bound to the worker
        resumes ALIVE with its memory state intact (ray: workers
        re-registering with a restarted GCS via raylet resubscription).
        Note: adopted actors occupy node resources the fresh scheduler has
        not reserved; transient overcommit until they exit is accepted."""
        from ray_tpu._private import config as _config

        if _config.get("reconnect_window_s") <= 0:
            return None  # classic mode: unknown workers are rejected
        wid, pid = first[1], first[2]
        node_id = first[3] if len(first) > 3 else None
        announce = first[5] if len(first) > 5 else None
        nid = node_id or self.head_node_id
        if nid in self.node_daemons:
            proc: Any = _RemoteProcHandle(self, nid, wid)
        else:
            proc = _AdoptedHandle(self, wid)
        h = WorkerHandle(wid, nid, None, None, proc)
        h.conn = conn
        h.pid = pid
        self.workers[wid] = h
        self._conn_to_worker[conn] = wid
        self._conns_version += 1
        bound = None
        for aid, ar in self.actors.items():
            if ar.info.worker_id == wid and ar.info.state == RESTARTING:
                bound = aid
                break
        if bound is None and announce is not None:
            # Reconciliation: the worker re-announced the live actor it
            # hosts.  Normally the journal already restored the record
            # (the loop above missed only because worker_id drifted); with
            # the journal lost or disabled, the announcement itself
            # carries the creation spec and re-registers the actor — the
            # third leg of snapshot + journal + re-announcement.
            bound = self._reconcile_announced_actor(wid, nid, announce)
        if bound is not None:
            ar = self.actors[bound]
            ar.worker_id = wid
            h.state = "actor"
            h.actor_id = bound
            self._restored_actors.discard(bound)
            self.state.set_actor_state(bound, ALIVE, worker_id=wid, node_id=nid)
            self._on_actor_alive(bound)
        else:
            h.state = "idle"
            # Stamp idleness NOW: the constructor default of 0.0 reads as
            # idle-since-boot and the reaper would kill the adoptee on its
            # next tick — destroying what adoption exists to preserve.
            h.idle_since = time.monotonic()
            self.idle_pool.setdefault((nid, None), []).append(wid)
        self._dispatch()
        return h

    @_locked
    def _reconcile_announced_actor(self, wid: str, nid: str, announce) -> Optional[str]:
        """Caller holds self.lock.  A reconnecting worker announced the
        actor it hosts: bind it to the restored record, or — when NO
        record survived (journal disabled/lost) — re-register the actor
        from the announced creation spec (ray: workers re-registering
        their actors with a restarted GCS).  Returns the actor_id to bind
        or None (worker is adopted as a plain idle worker)."""
        try:
            aid = announce.get("actor_id")
            spec = announce.get("creation_spec")
        except AttributeError:
            return None
        if not aid:
            return None
        ar = self.actors.get(aid)
        info = self.state.get_actor(aid)
        if ar is not None and info is not None:
            if info.state not in (RESTARTING, PENDING_CREATION) or ar.worker_id:
                return None  # DEAD, or another instance already bound
            creation = info.creation_spec
            rec = self.tasks.get(creation.task_id) if creation is not None else None
            if rec is not None:
                if rec.state not in ("PENDING", "READY") or rec.cancelled:
                    return None  # a respawn already started: it wins
                # A queued-but-undispatched respawn loses to the LIVE
                # instance (memory state preserved beats state reset).
                rec.cancelled = True
                self.tasks.pop(creation.task_id, None)
            return aid
        if spec is None:
            return None
        info = ActorInfo(
            actor_id=aid,
            name=getattr(spec, "actor_name", None),
            namespace=getattr(spec, "actor_namespace", None) or self.namespace,
            max_restarts=getattr(spec, "max_restarts", 0),
            creation_spec=spec,
            detached=getattr(spec, "lifetime", None) == "detached",
            state=RESTARTING,
            worker_id=wid,
            node_id=nid,
        )
        try:
            self.state.register_actor(info)  # journals the rebuilt record
        except ValueError:
            return None  # name re-taken while the record was lost
        self.actors[aid] = ActorRuntime(info)
        self.events.emit(
            "WARNING", "actor",
            "actor record rebuilt from worker re-announcement",
            actor_id=aid, worker_id=wid,
        )
        return aid

    @_locked
    def _redrive_worker_relays(self, h, wid: str, announced: set) -> None:
        """Caller holds self.lock.  A reconnecting worker announced the
        relayed tasks it still holds (queued or executing).  In-flight
        work the head attributes to this worker that the worker does NOT
        hold was lost with the dead conn — a task push that never
        arrived, or a done/result frame that died in the socket.

        Plain tasks are provably not running anywhere (the worker doesn't
        have them), so they retry on their budget or fail loudly — never
        wedge a get().  Lost actor calls carry the at-most-once
        uncertainty (the call may have EXECUTED with only its done lost):
        budgeted ones (max_task_retries) re-push to the live instance —
        the contract that allows re-execution — and unbudgeted ones fail
        with the same uncertainty error a worker crash yields."""
        if h.actor_id is not None:
            ar = self.actors.get(h.actor_id)
            if ar is None:
                return
            lost = [t for t in ar.in_flight if t not in announced]
            for tid in lost:
                rec = self.tasks.get(tid)
                ar.in_flight.pop(tid, None)
                if rec is None:
                    continue
                if rec.spec.attempt < rec.spec.max_retries:
                    rec.spec.attempt += 1
                    self.metrics["tasks_retried"] += 1
                    self._push_actor_task(ar, rec)
                    continue
                err = WorkerCrashedError(
                    f"relayed actor call {rec.spec.name} was lost with its "
                    "connection (io fabric reset); the call may or may not "
                    "have executed — set max_task_retries to allow re-drive"
                )
                self.tasks.pop(tid, None)
                for oid in rec.spec.return_ids():
                    self.store.put_error(oid, err)
                    self._object_ready(oid)
                for c in rec.spec.contained_refs:
                    self._decref_local(c)
            if lost:
                self.events.emit(
                    "WARNING", "worker",
                    "re-drove relayed actor calls lost with conn",
                    worker_id=wid, actor_id=h.actor_id, lost=len(lost),
                )
            return
        tid = h.current_task
        if tid is None or tid in announced:
            return
        rec = self.tasks.get(tid)
        h.current_task = None
        if h.state == "busy":
            self._return_worker(h)
        if rec is None or rec.cancelled:
            return
        self.events.emit(
            "WARNING", "worker", "re-driving relayed task lost with conn",
            worker_id=wid, task=rec.spec.name,
        )
        if rec.spec.attempt < rec.spec.max_retries:
            rec.spec.attempt += 1
            self._retry_task_record(rec)
        else:
            self._fail_task_record(rec, wid, WorkerCrashedError(
                f"task {rec.spec.name}'s result was lost with its "
                "connection (io fabric reset) after its retry budget"
            ))

    def _io_loop(self):
        import selectors

        from ray_tpu._private import config as _cfg

        sel = selectors.DefaultSelector()
        registered: set = set()
        registered_version = -1
        last_reap = 0.0
        last_topup = 0.0
        while not self._shutdown:
            # Reap workers that died before ever connecting (spawn failure,
            # import crash): conn-EOF detection can't see them.
            now = time.monotonic()
            if now - last_reap > 0.5:
                last_reap = now
                with self.lock:
                    for wid, h in list(self.workers.items()):
                        if (
                            h.conn is None
                            and h.state not in ("dead",)
                            and h.proc is not None
                            and not h.proc.is_alive()
                        ):
                            if (
                                h.state == "starting"
                                and wid not in self._env_failures
                                and wid not in self._deferred_crashes
                            ):
                                # Give a possible env_failed hello (separate
                                # conn) a beat to land before classifying.
                                self._deferred_crashes[wid] = now + 2.0
                            elif wid not in self._deferred_crashes:
                                self._on_worker_crash(wid)
                    # Deferred daemon-worker EOFs whose daemon never
                    # reported (hung daemon / lost message): classify now.
                    for wid, deadline in list(self._deferred_crashes.items()):
                        if now >= deadline:
                            self._deferred_crashes.pop(wid, None)
                            h = self.workers.get(wid)
                            if h is not None and h.state != "dead":
                                self._on_worker_crash(wid)
                    # Drivers whose conn reset on a live head and never
                    # re-handshook within the grace: now they're dead.
                    for did, deadline in list(self._driver_death_grace.items()):
                        if now >= deadline:
                            self._driver_death_grace.pop(did, None)
                            if did in self.drivers and self.drivers[
                                did
                            ] not in self._conn_to_driver:
                                self._on_driver_death(did)
                    # Task leases idle past RAY_TPU_LEASE_IDLE_S return
                    # their worker + resources to the shared pool.
                    if self.task_leases:
                        self._revoke_idle_leases(now)
                    # Function-export fences that timed out fail loudly.
                    if self._fn_fences:
                        self._sweep_fn_fences(now)
                    # Idle-worker reaping (ray: worker_pool idle killing):
                    # default-env head workers beyond the prestart floor
                    # that sat idle >60s exit, so a burst's pool shrinks
                    # back instead of holding memory forever.
                    floor = max(
                        _cfg.get("worker_prestart_count"), self._prestart_target
                    )
                    pool = self.idle_pool.get((self.head_node_id, None))
                    if pool and len(pool) > floor:
                        killed = 0
                        for wid in list(pool):
                            if len(pool) <= floor or killed >= 8:
                                break
                            h = self.workers.get(wid)
                            if h is None:
                                pool.remove(wid)
                                continue
                            if h.state == "idle" and now - h.idle_since > 60.0:
                                pool.remove(wid)
                                killed += 1
                                self._expected_worker_stops.add(wid)
                                self._send(h, ("kill",))
                    # Heartbeat timeouts: a hung (not dead) daemon or a
                    # half-open conn keeps the socket alive but stops
                    # heartbeating — declare the node dead so its leased
                    # tasks retry elsewhere instead of wedging.
                    hb_timeout = _cfg.get("health_check_timeout_ms") / 1000.0
                    if hb_timeout > 0:
                        for dconn, nid in list(self._conn_to_daemon.items()):
                            last = self._daemon_heartbeats.get(nid)
                            if last is None:
                                # Pre-heartbeat daemons (or ones from an
                                # older protocol) start their clock at
                                # first sight, not at epoch.
                                self._daemon_heartbeats[nid] = now
                            elif now - last > hb_timeout:
                                self.events.emit(
                                    "WARNING", "node",
                                    "heartbeat timeout: declaring node dead",
                                    node_id=nid, silent_s=round(now - last, 1),
                                )
                                self._conn_to_daemon.pop(dconn, None)
                                self._conns_version += 1
                                self._daemon_heartbeats.pop(nid, None)
                                try:
                                    dconn.close()
                                except OSError:
                                    pass
                                self._on_daemon_death(nid)
                # Dead-holder ref reclaim rides the same tick (its own
                # lock dance inside; decrefs may fan daemon deletes).
                if self._dead_refs:
                    self.reclaim_dead_refs()
                # Elastic MESH gangs: advance RESHAPING episodes.  Off the
                # runtime lock — the reshape fault points can delay/crash;
                # each mutation step re-takes the lock and re-checks.
                self._sweep_reshaping_pgs(now)
            if self._prestart_target > 0 and now - last_topup > 0.05:
                # Throttled: an every-iteration lock acquire here convoys
                # with the hot message path during drains.
                last_topup = now
                with self.lock:
                    t = self._prestart_target
                    if now - self._prestart_miss_t > 5.0:
                        if now - self._prestart_decay_t > 5.0:
                            self._prestart_target = t // 2
                            self._prestart_decay_t = now
                    else:
                        key = (self.head_node_id, None)
                        have = len(self.idle_pool.get(key) or ()) + len(
                            self.starting_pool.get(key) or ()
                        )
                        # ≤8 spawns per tick bounds the lock hold; the loop
                        # runs ≥20Hz so a 50-wide burst refills within a
                        # wave's barrier.
                        for _ in range(min(t - have, 8)):
                            self._spawn_worker(
                                self.head_node_id, None, None, prestart=True
                            )
            # Persistent epoll registration (diffed, not rebuilt): the old
            # per-iteration `multiprocessing.connection.wait` constructed a
            # poll set of ALL conns on EVERY wakeup — O(live workers) per
            # message, the measured collapse at 800+ live actors (ray:
            # asio's reactor keeps persistent registrations the same way).
            if self._conns_version != registered_version:
                with self.lock:
                    registered_version = self._conns_version
                    current = (
                        set(self._conn_to_worker)
                        | set(self._conn_to_daemon)
                        | set(self._conn_to_driver)
                    )
                for conn in registered - current:  # removals FIRST (fd reuse)
                    try:
                        sel.unregister(conn)
                    except (KeyError, ValueError, OSError):
                        pass
                for conn in current - registered:
                    try:
                        sel.register(conn, selectors.EVENT_READ)
                    except (KeyError, ValueError, OSError):
                        current.discard(conn)
                registered = current
            if not registered:
                time.sleep(0.02)
                continue
            try:
                readable = [key.fileobj for key, _ in sel.select(timeout=0.05)]
            except OSError:
                continue
            # Daemon conns first: an OOM-kill report must be applied
            # before the victim worker's own conn EOF (same select round)
            # so the crash classifies as OOM, not a generic worker death.
            readable.sort(key=lambda c: c not in self._conn_to_daemon)
            for conn in readable:
                nid = self._conn_to_daemon.get(conn)
                if nid is not None:
                    # Drain the whole readable run INCLUDING decoded batch
                    # sub-frames: a daemon's heartbeat piggybacks on its
                    # log_lines/worker_exited batch, and a buffered tail
                    # would otherwise strand until the next physical frame.
                    dmsgs = []
                    try:
                        dmsgs.append(conn.recv())
                        while len(dmsgs) < 256 and conn.poll(0):
                            dmsgs.append(conn.recv())
                        while conn.pending_frames():
                            dmsgs.append(conn.recv())
                    except (EOFError, OSError):
                        for dmsg in dmsgs:
                            self._handle_daemon_msg(nid, dmsg)
                        self._daemon_conn_eof(conn, nid)
                        continue
                    for dmsg in dmsgs:
                        self._handle_daemon_msg(nid, dmsg)
                    continue
                did = self._conn_to_driver.get(conn)
                if did is not None:
                    # Drain like a worker conn (attached drivers batch
                    # their oneway/req streams too), including any decoded
                    # sub-frames left past the cap.
                    eof = False
                    msgs = []
                    try:
                        msgs.append(conn.recv())
                        while len(msgs) < 256 and conn.poll(0):
                            msgs.append(conn.recv())
                        while conn.pending_frames():
                            msgs.append(conn.recv())
                    except (EOFError, OSError):
                        eof = True
                    for msg in msgs:
                        try:
                            self._handle_msg(did, msg)
                        except Exception:
                            import traceback

                            traceback.print_exc()
                    if not eof:
                        continue
                    self._driver_conn_eof(conn, did)
                    continue
                wid = self._conn_to_worker.get(conn)
                if wid is None:
                    continue
                # Drain the conn: receive every queued message, THEN handle
                # the run in batches under one lock acquisition.  Per-message
                # lock round-trips convoy against the N submitting client
                # threads (measured: 4-client task throughput collapsed 4x
                # with per-message locking; the reference batches the same
                # way in its io-service event handlers).  The cap bounds
                # PHYSICAL reads; decoded batch sub-frames past it are
                # drained too — the socket shows no data for them, so the
                # selector would never wake for a buffered tail.
                eof = False
                msgs = []
                try:
                    msgs.append(conn.recv())
                    while len(msgs) < 256 and conn.poll(0):
                        msgs.append(conn.recv())
                    while conn.pending_frames():
                        msgs.append(conn.recv())
                except (EOFError, OSError):
                    eof = True
                if msgs:
                    self._handle_msgs(wid, msgs)
                if eof:
                    self._worker_conn_eof(conn, wid)
            # End of the select round: every reply/pub/fence queued while
            # handling this wave goes out as one physical write per conn
            # (the flush-before-blocking-wait rule — select() is this
            # thread's blocking wait).
            _wire.flush_dirty()

    # Conn-EOF paths of the io loop.

    def _daemon_conn_eof(self, conn, nid: str) -> None:
        with self.lock:
            self._conn_to_daemon.pop(conn, None)
            self._conns_version += 1
            self._on_daemon_death(nid)

    def _driver_conn_eof(self, conn, did: str) -> None:
        from ray_tpu._private import config as _config

        with self.lock:
            self._conn_to_driver.pop(conn, None)
            self._conns_version += 1
            superseded = self.drivers.get(did) is not conn
        if not superseded:
            window = _config.get("reconnect_window_s")
            if window > 0:
                # Transient reset on a LIVE head: give the driver's
                # reconnect loop a beat before freeing its refs and
                # killing its actors (a same-millisecond EOF would
                # otherwise always beat the re-handshake).
                with self.lock:
                    self._driver_death_grace[did] = (
                        time.monotonic() + min(window, 5.0)
                    )
            else:
                self._on_driver_death(did)

    def _worker_conn_eof(self, conn, wid: str) -> None:
        with self.lock:
            self._conn_to_worker.pop(conn, None)
            self._conns_version += 1
            h = self.workers.get(wid)
            if (
                h is not None
                and isinstance(h.proc, _RemoteProcHandle)
                and h.node_id in self.node_daemons
                and wid not in self._oom_kills
            ):
                # Daemon-owned worker: wait briefly for the daemon's
                # worker_exited (carries the OOM rider) before
                # classifying the crash.
                self._deferred_crashes[wid] = time.monotonic() + 2.0
            else:
                self._on_worker_crash(wid)

    def _handle_daemon_msg(self, nid: str, dmsg) -> None:
        if not (isinstance(dmsg, tuple) and dmsg):
            return
        if dmsg[0] == "log_lines":
            # A remote node's monitor forwarded fresh worker output: same
            # sink as head-local files.
            self._on_log_lines(dmsg[1], dmsg[2], dmsg[3])
        elif dmsg[0] == "heartbeat":
            self._daemon_heartbeats[nid] = time.monotonic()
        elif dmsg[0] == "metrics_push":
            self.telemetry.ingest(f"daemon:{nid}", dmsg[1])
        elif dmsg[0] == "worker_oom_killed":
            with self.lock:
                self._oom_kills[dmsg[1]] = dmsg[2:]
        elif dmsg[0] == "worker_exited":
            # A remote child died (possibly before connecting): the
            # driver-side reaper can't see it, the daemon can.
            with self.lock:
                h = self.workers.get(dmsg[1])
                if h is not None and isinstance(h.proc, _RemoteProcHandle):
                    h.proc.dead = True
                self._deferred_crashes.pop(dmsg[1], None)
                if h is not None and h.state != "dead":
                    # The daemon's report is authoritative on WHY: its OOM
                    # rider survives even when the victim's own conn EOF
                    # won the message race.
                    if len(dmsg) > 3 and dmsg[3] is not None:
                        self._oom_kills.setdefault(dmsg[1], tuple(dmsg[3]))
                    if (
                        h.conn is None
                        and h.state == "starting"
                        and dmsg[1] not in self._oom_kills
                        and dmsg[1] not in self._env_failures
                    ):
                        # A starting worker that died without connecting
                        # usually failed env setup; its env_failed hello
                        # rides a separate conn — wait briefly so the
                        # crash classifies as RuntimeEnvSetupError, not a
                        # retriable generic death.
                        self._deferred_crashes[dmsg[1]] = (
                            time.monotonic() + 2.0
                        )
                    else:
                        self._on_worker_crash(dmsg[1])
                else:
                    # Crash already classified (EOF saw the earlier
                    # worker_oom_killed): drop any re-inserted rider or it
                    # leaks forever.
                    self._oom_kills.pop(dmsg[1], None)

    # ------------------------------------------------------------------
    # message handling

    def _handle_msgs(self, wid: str, msgs: List[tuple]) -> None:
        """Handle a drained run of messages, folding consecutive hot-path
        kinds (done/refop) into ONE lock acquisition.  Failures are
        per-message: one bad handler must not drop the already-drained
        messages behind it (a swallowed 'done' wedges its task forever)."""
        import traceback

        i, n = 0, len(msgs)
        while i < n:
            if msgs[i][0] in ("done", "refop"):
                with self.lock:
                    while i < n and msgs[i][0] in ("done", "refop"):
                        try:
                            self._handle_hot_locked(wid, msgs[i])
                        except Exception:
                            traceback.print_exc()
                        i += 1
            else:
                try:
                    self._handle_msg(wid, msgs[i])
                except Exception:
                    traceback.print_exc()
                i += 1

    @_locked
    def _handle_hot_locked(self, wid: str, msg: tuple) -> None:
        # caller holds self.lock
        if msg[0] == "done":
            self._on_task_done(
                wid, msg[1], msg[2], msg[3],
                timing=msg[4] if len(msg) > 4 else None,
            )
            return
        # Every sender's outstanding borrows are conn-tracked (drivers in
        # driver_refs, workers in worker_refs): a holder dying mid-hold
        # leaves exactly the refs its lost dels would have released — the
        # ledger flags them as dead-holder leak suspects and
        # reclaim_dead_refs drops them after the grace.
        tracked = self.driver_refs.get(wid)
        if tracked is None:
            tracked = self.worker_refs.get(wid)
            if tracked is None:
                tracked = self.worker_refs.setdefault(wid, {})
        if msg[1] == "add":
            self.store.add_ref(msg[2])
            tracked[msg[2]] = tracked.get(msg[2], 0) + 1
        else:
            self._decref_local(msg[2])
            c = tracked.get(msg[2], 0) - 1
            if c > 0:
                tracked[msg[2]] = c
            else:
                tracked.pop(msg[2], None)

    def _handle_msg(self, wid: str, msg: tuple) -> None:
        kind = msg[0]
        if kind in ("done", "refop"):
            with self.lock:
                self._handle_hot_locked(wid, msg)
        elif kind == "object_copied":
            # A worker pulled a copy into its node's store: record it so
            # siblings on that node read locally — unless the object was
            # freed while the pull was in flight (then reap the orphan).
            # The optional 4th field is the transfer path ("pull"/"relay")
            # the puller used — released slot + ledger label.
            oid, size = msg[1], msg[2]
            via = msg[3] if len(msg) > 3 else "pull"
            with self.lock:
                node = self._worker_node(wid)
                grants = self._pull_grants.get(oid)
                if grants:
                    grants.pop()  # this puller's grant: capacity freed
                    if not grants:
                        self._pull_grants.pop(oid, None)
                self._release_pull_slot_locked(oid, node)
                if wid in self.drivers and node != self.head_node_id:
                    return  # remote driver's private store: nobody else reads it
                if node == self.head_node_id:
                    # The worker wrote straight into the HEAD store's shm:
                    # without accounting, _free would never delete the
                    # segment and capacity tracking would undercount.
                    if self.store.is_ready(oid):
                        self.store.mark_shm_sealed(oid, size)
                    else:
                        self.store.shm.delete(oid)
                elif self.store.is_ready(oid):
                    self.object_locations.setdefault(oid, set()).add(node)
                    self.object_sizes.setdefault(oid, size)
                else:
                    self._daemon_send(node, ("delete_object", oid))
                    return
                # Ledger/timeline label carries the transfer path: a
                # "relay" event proves the copy rode an in-flight feed.
                self._obj_event(
                    oid, "relay" if via == "relay" else "transfer", size, node
                )
                # Unpark staggered pullers: the source set just grew
                # (deferred callbacks run after the lock drops).
                deferred = self.pubsub.publish("object_copied", oid, oid)
            for cb in deferred:
                cb(oid)
        elif kind == "actor_exit":
            with self.lock:
                ar = self.actors.get(msg[1])
                if ar:
                    ar.expected_death = True
                    ar.no_restart = True
        elif kind == "actor_announce":
            # Reconciliation hints from reconnecting CALLERS: each entry
            # names a direct actor route the peer held when the old head
            # died.  The rebuilt table (snapshot + journal + hosting-worker
            # re-announcement) normally already accounts for every one; an
            # entry it can't account for is surfaced as a WARNING event so
            # a durability gap is visible instead of silent.
            with self.lock:
                for aid, ep in msg[1]:
                    if self.state.get_actor(aid) is None:
                        self.events.emit(
                            "WARNING", "actor",
                            "peer re-announced an actor with no surviving record",
                            actor_id=aid, reporter=wid,
                            endpoint=list(ep) if ep else None,
                        )
        elif kind == "task_events":
            # Batched task-state reports for peer-executed (direct) tasks:
            # restores state-API/metrics visibility without a per-task
            # head message on the latency path.  RUNNING events come from
            # the CALLER at lease dispatch; completion events come from the
            # EXECUTOR — different processes, so a completion may arrive
            # first (the recent-done set keeps such entries from sticking
            # as RUNNING forever).
            off = self.clock_offsets.get(wid, 0.0)
            with self.lock:
                for e in msg[1]:
                    if off and isinstance(e.get("end_time"), float):
                        # Land the sender's timestamps on the head clock so
                        # the merged timeline orders across processes.
                        e["end_time"] += off
                        for s, v in list((e.get("stages") or {}).items()):
                            if isinstance(v, (int, float)):
                                e["stages"][s] = v + off
                    tid = e.get("task_id")
                    if e.get("state") == "RUNNING":
                        if tid not in self._direct_done_recent:
                            # Bounded: crashes on BOTH sides of a direct
                            # call can orphan an entry (no terminal event
                            # ever arrives), so cap with FIFO eviction.
                            while len(self.direct_running) >= 4096:
                                self.direct_running.pop(
                                    next(iter(self.direct_running))
                                )
                            self.direct_running[tid] = e
                        continue
                    self.direct_running.pop(tid, None)
                    if len(self._direct_done_recent) >= 4096:
                        self._direct_done_recent.discard(
                            self._direct_done_order.popleft()
                        )
                    self._direct_done_recent.add(tid)
                    self._direct_done_order.append(tid)
                    self.metrics["tasks_submitted"] += 1
                    self.metrics[
                        "tasks_finished" if e.get("state") == "FINISHED"
                        else "tasks_failed"
                    ] += 1
                    self.task_events.append(e)
                    # Direct-task events carry executor-side stage
                    # durations (exec_queue/running): same histograms as
                    # head-dispatched tasks, so `ray_tpu tasks --summary`
                    # spans both transports.
                    self._observe_stage_durations(e.get("durations"))
        elif kind == "spans":
            # Worker-side trace spans (util/tracing.py), batched off the
            # latency path like task events.  Corrected onto the head
            # clock at ingest (handshake-estimated offset) so the merged
            # timeline is one coherent clock across processes.
            from ray_tpu.util.tracing import apply_clock_offset

            spans = apply_clock_offset(msg[1], self.clock_offsets.get(wid, 0.0))
            with self.lock:
                self.trace_spans.extend(spans)
        elif kind == "metrics_push":
            # Periodic per-process telemetry snapshot (telemetry.py):
            # latest wins per sender; the head's telemetry tick folds the
            # aggregate into the time-series rings.
            self.telemetry.ingest(wid, msg[1])
        elif kind == "refs_push":
            # Periodic per-process live-ref table (refs.py snapshot_refs):
            # the worker leg of the object ledger — droppable, latest wins
            # per sender, joined with the owner tables by memory_summary.
            self.ledger.ingest(wid, msg[1])
        elif kind == "prof_push":
            # Periodic per-process collapsed-stack table (profiler.py):
            # cumulative since start, so latest-wins ingest + a sum across
            # senders is exact even when droppable pushes are lost.
            self.profiles.ingest(wid, msg[1], node=self._worker_node(wid))
        elif kind == "wire_stats":
            # Per-process wire counters reported by workers/drivers when
            # RAY_TPU_WIRE_STATS=1 (keyed by sender; cluster_metrics sums
            # them with the head's own counters).
            with self.lock:
                self.worker_wire_stats[wid] = dict(msg[1])
        elif kind == "direct_lineage":
            # A lease-dispatched task produced shm results: remember its
            # spec so the head can re-execute the producer if the bytes are
            # later lost (ray: task_manager.h:90 keeps lineage for ALL
            # direct tasks, not just relayed ones).
            spec = msg[1]
            if spec.actor_id is None:  # actor outputs are never re-executed
                with self.lock:
                    for rid in spec.return_ids():
                        self._lineage_record(rid, spec)
        elif kind == "subscribe":
            once = bool(msg[3]) if len(msg) > 3 else False
            with self.lock:
                subs = self.remote_subs.setdefault((msg[1], msg[2]), {})
                # A persistent subscription must never be downgraded by a
                # later once-subscribe from the same process.
                subs[wid] = subs.get(wid, once) and once
        elif kind == "unsubscribe":
            with self.lock:
                subs = self.remote_subs.get((msg[1], msg[2]))
                if subs is not None:
                    subs.pop(wid, None)
                    if not subs:
                        self.remote_subs.pop((msg[1], msg[2]), None)
        elif kind == "lease_return":
            with self.lock:
                self._release_peer_lease_locked(msg[1], return_worker=True)
        elif kind == "fence_ack":
            with self.lock:
                ent = self._pending_fences.pop(msg[1], None)
            if ent is not None:
                caller, req_id, awid, ep, restartable = ent
                self._reply(caller, req_id, True, ("direct", awid, ep, restartable))
        elif kind == "direct_seal":
            # A direct call's large result, sealed in the callee's node
            # store: enter it in the directory/accounting and hold the
            # caller's reference (released by the caller's refop del).
            # The executor's serialize-time guard borrows are swapped for
            # the stored-object borrows _store_contained just took.
            oid, size, contained = msg[1], msg[2], msg[3]
            with self.lock:
                self._store_contained(oid, contained)
                for c in contained:
                    self._decref_local(c)
                self._record_sealed(wid, oid, size)
                self.store.add_ref(oid)
                self._object_ready(oid)
        elif kind == "promote":
            # A caller-owned inline result escaped its owner: register the
            # bytes here so any process can resolve the ref.  Idempotent —
            # a shm twin may already be registered via direct_seal.
            oid, packed, contained = msg[1], msg[2], msg[3]
            with self.lock:
                if not self.store.is_ready(oid):
                    self._store_contained(oid, contained)
                    self._put_packed(oid, packed)
                    self._note_object(oid, wid)
                    self._obj_event(oid, "seal", len(packed))
                    from ray_tpu._private import telemetry as _tele

                    _tele.count_copy("promote", len(packed))
                    self.store.add_ref(oid)
                    self._object_ready(oid)
        elif kind == "promote_error":
            oid = msg[1]
            with self.lock:
                if not self.store.is_ready(oid):
                    self.store.put_error(oid, cloudpickle.loads(msg[2]))
                    self.store.add_ref(oid)
                    self._object_ready(oid)
        elif kind in ("seal_ow", "put_ow"):
            # Fire-and-forget worker put (locally-minted id; for seal_ow the
            # segment is already in the worker's node store, for put_ow the
            # packed bytes ride the message).
            oid, data, contained = msg[1], msg[2], msg[3]
            with self.lock:
                self.metrics["objects_put"] += 1
                self._store_contained(oid, contained)
                if kind == "seal_ow":
                    self._record_sealed(wid, oid, data)
                else:
                    self._put_packed(oid, data)
                    self._note_object(oid, wid)
                    self._obj_event(oid, "seal", len(data))
                self._object_ready(oid)
        elif kind == "req":
            req_id, op, payload = msg[1], msg[2], msg[3]
            try:
                result = self._handle_req(wid, req_id, op, payload)
            except Exception as e:  # reply with error
                self._reply(wid, req_id, False, e)
                return
            if result is not _PARKED:
                self._reply(wid, req_id, True, result)

    @_locked
    def _drop_remote_subs(self, wid: str) -> None:
        for ck, subs in list(self.remote_subs.items()):
            subs.pop(wid, None)
            if not subs:
                self.remote_subs.pop(ck, None)

    def _remote_publish(self, channel: str, key: Any, args: tuple) -> None:
        """Publisher hook: push this publish to remote subscribers over
        their control conns (pubsub.py remote delivery).  Exact-key and
        wildcard ("*") subscriptions both fire; the frame carries the key
        so wildcard subscribers can route.

        Delivery is ASYNC via a dedicated sender thread: publishes run
        under the runtime lock, and a subscriber that stops draining its
        conn would otherwise block the send — and with it the whole
        control plane (the same reason in-process subscribers have
        deferred=True)."""
        if not self.remote_subs:
            return
        if faults.ENABLED:
            try:
                if faults.point("pubsub.publish", key=str(channel)) == "drop":
                    return  # publish lost before fan-out
            except faults.InjectedFault:
                return  # same observable outcome as drop for a publish
        with self.lock:
            entries = self.remote_subs.get((channel, key))
            wildcard = self.remote_subs.get((channel, "*"))
            targets = dict(wildcard or ())
            if entries:
                targets.update(entries)
            # once-flagged in EITHER registration (the merge above lets an
            # exact persistent sub shadow a wildcard once flag).
            once_wids = {
                wid
                for d in (entries, wildcard)
                if d
                for wid, once in d.items()
                if once
            }
        delivered = []
        for wid, _once in targets.items():
            try:
                self._pub_queue.put_nowait((wid, ("pub", channel, key, args)))
            except Exception:
                # Full: push dropped (subscriber hopelessly behind).  The
                # once-sub is NOT consumed — a one-shot event must not
                # vanish because a log flood filled the queue.
                continue
            delivered.append(wid)
        if once_wids.intersection(delivered):
            with self.lock:
                # Consume delivered once-entries from BOTH the exact-key
                # and the wildcard registration (a once+wildcard sub must
                # not fire on every later publish forever), and ONLY
                # still-once entries: a re-subscribe (or persistent
                # upgrade) that landed during the send window must
                # survive this delivery.
                for ck in ((channel, key), (channel, "*")):
                    entries = self.remote_subs.get(ck)
                    if not entries:
                        continue
                    for wid in delivered:
                        if entries.get(wid) is True:
                            entries.pop(wid, None)
                    if not entries:
                        self.remote_subs.pop(ck, None)

    def _pub_sender_loop(self) -> None:
        import queue as _queue

        while not getattr(self, "_shutdown", False):
            try:
                wid, msg = self._pub_queue.get(timeout=1.0)
            except Exception:
                continue
            # Drain the whole publish WAVE before flushing: a publish
            # fanning to N subscribers (or a burst of publishes) lands as
            # one physical write per subscriber conn, replacing the old
            # per-subscriber per-message write loop.
            while True:
                self._reply_raw(wid, msg)
                try:
                    wid, msg = self._pub_queue.get_nowait()
                except _queue.Empty:
                    break
            # This thread is about to block in get(): flush first.
            _wire.flush_dirty()

    def _reply_raw(self, wid: str, msg: tuple) -> None:
        # Resolve the conn UNDER the lock, send OUTSIDE it: a subscriber
        # that stops draining must only stall the sender thread, never
        # the control plane (TypedConn.send serializes per-conn writers).
        with self.lock:
            h = self.workers.get(wid)
            if h is not None:
                if h.conn is None:
                    h.pending_sends.append(msg)
                    return
                conn = h.conn
            else:
                conn = self.drivers.get(wid)
        if conn is not None:
            try:
                conn.send(msg)
            except OSError:
                pass

    def _reply(self, wid: str, req_id: int, ok: bool, value: Any) -> None:
        with self.lock:
            h = self.workers.get(wid)
            if h is not None:
                self._send(h, ("reply", req_id, ok, value))
                return
            conn = self.drivers.get(wid)
        if conn is not None:
            try:
                conn.send(("reply", req_id, ok, value))
            except OSError:
                pass  # driver died; its EOF cleanup is in flight

    def _handle_req(self, wid: str, req_id: int, op: str, payload: Any) -> Any:
        self.req_counts[op] += 1
        if op == "get_object":
            return self._req_get_object(wid, req_id, payload)
        if op == "sync":
            return None  # put-backpressure barrier (worker flushes oneways)
        if op == "resolve_actor":
            return self._req_resolve_actor(wid, req_id, *payload)
        if op == "lease_worker":
            return self._req_lease_worker(wid, req_id, *payload)
        if op == "get_function":
            blob = self.state.get_function(payload)
            if blob is None:
                raise KeyError(f"unknown function {payload}")
            return blob
        if op == "export_function":
            fn_id, blob = payload
            self.state.export_function(fn_id, blob)
            return None
        if op == "submit":
            return self.submit_task(payload)
        if op == "actor_call":
            return self.submit_actor_task(payload)
        if op == "create_actor":
            return self.create_actor(
                payload, owner_did=wid if wid in self.drivers else None
            )
        if op == "get_actor_named":
            name, nsp = payload
            info = self.state.get_named_actor(name, nsp or self.namespace)
            if info is None or info.state == DEAD:
                raise ValueError(f"no actor named {name!r}")
            spec = info.creation_spec
            return (
                info.actor_id,
                spec.actor_method_names or [],
                getattr(spec, "actor_max_concurrency", 1),
                getattr(spec, "actor_max_task_retries", 0),
            )
        if op == "actor_state":
            info = self.state.get_actor(payload)
            return info.state if info else None
        if op == "kill_actor":
            actor_id, no_restart = payload
            self.kill_actor(actor_id, no_restart)
            return None
        if op == "cancel":
            oid, force = payload
            self.cancel(oid, force)
            return None
        if op == "wait_objects":
            return self._req_wait_objects(wid, req_id, *payload)
        if op == "kv_put":
            self.state.kv_put(*payload)
            return None
        if op == "kv_get":
            return self.state.kv_get(*payload)
        if op == "kv_del":
            self.state.kv_del(*payload)
            return None
        if op == "kv_keys":
            return self.state.kv_keys(*payload)
        if op == "pg_create":
            bundles, strategy, name = payload[0], payload[1], payload[2]
            pg_id = payload[3] if len(payload) > 3 else None
            return self.create_placement_group(bundles, strategy, name, pg_id).pg_id
        if op == "pg_state":
            pg = self.state.placement_groups.get(payload)
            return pg.state if pg else None
        if op == "pg_remove":
            self.remove_placement_group(payload)
            return None
        if op == "pg_info":
            return self.pg_info(payload)
        if op == "pg_reshape":
            return self.pg_reshape(payload)
        if op == "cluster_resources":
            return self.cluster_resources()
        if op == "available_resources":
            return self.available_resources()
        if op == "get_logs":
            return self.get_logs(*payload)
        if op == "telemetry":
            # Attached-driver surface for `ray_tpu metrics` / `status`.
            return self.telemetry.summary()
        if op == "demand_summary":
            # Elastic-capacity demand view (`ray_tpu status` / the
            # autoscaler's attached-mode consumers).
            return self.demand_summary()
        if op == "node_lifecycle":
            # Journaled node-lifecycle records (tests/soaks verify replay
            # across head bounces through this).
            with self.lock:
                return {
                    nid: dict(rec)
                    for nid, rec in self.node_lifecycle.items()
                }
        if op == "node_drain":
            # Attached-mode drain trigger (the soak's scale-down lever;
            # ray: DrainNode RPC).  The embedded reconciler advances the
            # drain through evacuation + depart.
            return self.start_node_drain(payload)
        if op == "telemetry_series":
            return self.telemetry.series_snapshot(payload)
        if op == "memory_summary":
            # Object-ledger join for `ray_tpu memory` / /api/memory from
            # an attached client: same answer the head-local API gives.
            return self.memory_summary(**(payload or {}))
        if op == "list_object_refs":
            return self.memory_records(limit=(payload or {}).get("limit"))
        if op == "get_logs_all":
            return self.get_logs_all(payload)
        if op == "profile":
            # Cluster-wide sampling profiler (profiler.py): ("start", hz),
            # ("stop",), or ("report", {node,pid}).  start/stop broadcast
            # over pubsub to every subscribed worker; report merges the
            # pushed tables plus a fresh local snapshot.  None of these
            # block — the CLI does the sampling-window sleep client-side.
            action = payload[0]
            if action == "start":
                return self.profile_start(payload[1] if len(payload) > 1 else None)
            if action == "stop":
                return self.profile_stop()
            if action == "status":
                # Late-subscriber sync: a worker that subscribed after a
                # cluster-wide start polls this once and catches up.
                from ray_tpu._private import profiler as _profiler

                return _profiler.status()
            if action == "report":
                return self.profile_report(
                    **(payload[1] if len(payload) > 1 and payload[1] else {})
                )
            raise ValueError(f"unknown profile action {action!r}")
        if op == "state_list":
            # Attachable state API (util/state.py): --address clients and
            # the dashboard route list_* verbs here and get the head's
            # answers instead of requiring an in-process runtime.
            verb, kwargs = payload
            from ray_tpu.util import state as _state_api

            fns = {
                "tasks": _state_api.list_tasks,
                "actors": _state_api.list_actors,
                "objects": _state_api.list_objects,
                "nodes": _state_api.list_nodes,
                "workers": _state_api.list_workers,
                "placement_groups": _state_api.list_placement_groups,
                "cluster_events": _state_api.list_cluster_events,
                "summarize_tasks": _state_api.summarize_tasks,
                "cluster_metrics": _state_api.cluster_metrics,
                "spans": _state_api.list_spans,
                "task_summary": _state_api.task_summary,
            }
            fn = fns.get(verb)
            if fn is None:
                raise ValueError(f"unknown state verb {verb!r}")
            return fn(**(kwargs or {}))
        if op == "timeline":
            # Merged chrome-trace timeline (`ray_tpu timeline` from an
            # attached driver): task rows + clock-corrected spans from
            # every process of the cluster.  The optional payload is a
            # window ({"last": seconds} / {"since": epoch-seconds}) so
            # the export is bounded by the span ring, not a full dump.
            from ray_tpu.dashboard import timeline as _timeline

            window = payload if isinstance(payload, dict) else {}
            return _timeline(
                last=window.get("last"), since=window.get("since")
            )
        raise ValueError(f"unknown op {op}")

    def _req_resolve_actor(self, wid: str, req_id: int, actor_id: str,
                           need_fence: bool):
        """Directory lookup for the direct transport (peer.py).

        Replies ("direct", worker_id, endpoint, restartable).  Restartable
        actors are direct-eligible too — the caller's transport follows
        the restart FSM through "pending" replies while RESTARTING and
        re-resolves the new instance's endpoint (ray:
        direct_actor_task_submitter.h:67).  When the caller previously
        relayed calls (need_fence), the reply is parked until a marker
        flushed through the actor worker's control conn is acked: every
        relayed call is then provably in the executor queue, so the
        caller's first direct push cannot overtake one.
        """
        with self.lock:
            info = self.state.get_actor(actor_id)
            ar = self.actors.get(actor_id)
            if info is None or ar is None or info.state == DEAD:
                return ("dead", None, None, False)
            restartable = (info.max_restarts or 0) != 0
            if info.state != ALIVE or not ar.worker_id:
                return ("pending", None, None, restartable)
            ep = self.worker_peer_endpoints.get(ar.worker_id)
            h = self.workers.get(ar.worker_id)
            if ep is None or h is None or h.conn is None:
                return ("ineligible", None, None, restartable)
            if not need_fence:
                return ("direct", ar.worker_id, ep, restartable)
            self._fence_counter += 1
            fid = f"f{self._fence_counter}"
            self._pending_fences[fid] = (wid, req_id, ar.worker_id, ep, restartable)
            self._send(h, ("fence", fid))
            return _PARKED

    def _req_lease_worker(self, wid: str, req_id: int, resources: Dict[str, float]):
        """Grant a reusable worker lease for one scheduling key
        (ray: NodeManager::HandleRequestWorkerLease, node_manager.h:508 +
        the submitter-side pooling of direct_task_transport.h:75).

        The reservation goes through the same scheduler as head-dispatched
        tasks, so policy (incl. spillback to another node when one fills)
        and backpressure (("busy",) when the cluster is full → the caller
        relays through the queued head path) are inherited rather than
        reimplemented.  A grant on a still-spawning worker parks until its
        ready handshake delivers the peer endpoint."""
        probe = TaskSpec(
            task_id="lease-probe", name="lease", fn_id="", args_blob=b"",
            resources=dict(resources),
        )
        with self.lock:
            try:
                node = self.scheduler.select_node(probe)
            except ValueError:
                return ("infeasible",)
            if node is None or not self.scheduler.acquire(node, probe.resources):
                return ("busy",)
            h = self._lease_worker(node, probe)
            h.state = "peer_leased"
            self._lease_counter += 1
            lease_id = f"lease-{self._lease_counter}"
            self.peer_leases[lease_id] = (h.worker_id, node, dict(resources), wid)
            ep = self.worker_peer_endpoints.get(h.worker_id)
            if h.conn is not None and ep is not None:
                return ("ok", lease_id, h.worker_id, ep)
            if h.conn is not None and ep is None:
                # Connected worker without a peer listener (bind failed):
                # useless for direct push — undo the grant.
                self._release_peer_lease_locked(lease_id, return_worker=True)
                return ("busy",)
            self._parked_peer_leases.setdefault(h.worker_id, []).append(
                (wid, req_id, lease_id)
            )
            return _PARKED

    @_locked
    def _release_peer_lease_locked(self, lease_id: str, return_worker: bool) -> None:
        rec = self.peer_leases.pop(lease_id, None)
        if rec is None:
            return
        worker_id, node, resources, _caller = rec
        self.scheduler.release(node, resources)
        h = self.workers.get(worker_id)
        if return_worker and h is not None and h.state == "peer_leased":
            self._return_worker(h)
        self._dispatch()

    @_locked
    def _grant_parked_leases(self, wid: str) -> None:
        """Caller holds self.lock: a worker's ready handshake landed —
        complete lease grants that were waiting on its peer endpoint."""
        parked = self._parked_peer_leases.pop(wid, None)
        if not parked:
            return
        ep = self.worker_peer_endpoints.get(wid)
        for caller, req_id, lease_id in parked:
            if ep is not None and lease_id in self.peer_leases:
                self._reply(caller, req_id, True, ("ok", lease_id, wid, ep))
            else:
                # No peer endpoint (listener bind failed) or the lease was
                # already released: the worker itself is alive and
                # connected — return it to the pool or it would sit in
                # state "peer_leased" forever, invisible to the scheduler.
                self._release_peer_lease_locked(lease_id, return_worker=True)
                self._reply(caller, req_id, True, ("busy",))

    def _zygote_loop(self, conn) -> None:
        """Recv loop for the zygote's conn: pid attributions for forked
        workers and exit reports for reaped ones (boot crashes that never
        produced a worker conn to EOF)."""
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            if msg[0] == "forked":
                wid, pid = msg[1], msg[2]
                with self.lock:
                    h = self.workers.get(wid)
                    if h is not None and isinstance(h.proc, _ZygoteProcHandle):
                        h.proc.set_pid(pid)
            elif msg[0] == "worker_exited":
                wid = msg[1]
                with self.lock:
                    h = self.workers.get(wid)
                    if h is None or h.state == "dead":
                        continue
                    if (
                        h.conn is None
                        and h.state == "starting"
                        and wid not in self._env_failures
                        and wid not in self._deferred_crashes
                    ):
                        # Boot crash: give a possible env_failed hello
                        # (separate conn) a beat before classifying, like
                        # the reaper does.
                        self._deferred_crashes[wid] = time.monotonic() + 2.0
                    else:
                        self._on_worker_crash(wid)
        with self.lock:
            if self._zygote_conn is conn:
                self._zygote_conn = None
                self._zygote_spawning = False

    def _admit_pull(self, wid: str, req_id: int, oid: str, eps: list):
        """Broadcast admission.  Two regimes:

        relay_pipeline=1 (default) — PIPELINED TRANSFER PLAN: the reply's
        endpoint list is [assigned feed] + sealed-source fallbacks.  A
        feed is a sealed copy OR a node still pulling (its transfer board
        re-serves landed chunks mid-flight, object_plane._stream_relay),
        each carrying at most relay_fanout downstreams; every admitted
        puller immediately registers as a feed itself, so an N-node cold
        broadcast forms a chain/tree where all hops stream concurrently.
        A dead relay costs its downstreams one fallback hop (the sealed
        tail of their plan) or one re-ask (which re-plans); it never
        wedges the broadcast.

        relay_pipeline=0 — classic STAGGERED rounds (ray: push_manager.h
        bounds in-flight pushes; the pull twin bounds concurrent pulls
        per SOURCE COPY): grants capped at sealed copies, excess pullers
        park until object_copied grows the source set — ~log2(N)
        source-bandwidth rounds."""
        from ray_tpu._private import config as _cfg

        import time as _t

        now = _t.monotonic()
        horizon = now - _cfg.get("object_transfer_timeout_s")
        if not _cfg.get("relay_pipeline"):
            with self.lock:
                grants = [t for t in self._pull_grants.get(oid, ()) if t > horizon]
                if len(grants) >= max(len(eps), 1):
                    self._pull_grants[oid] = grants
                    self.metrics["pull_parks"] += 1
                    self._park_pull(wid, req_id, oid)
                    return _PARKED
                grants.append(now)
                self._pull_grants[oid] = grants
                self._pull_rr += 1
                k = self._pull_rr % len(eps) if eps else 0
            return ("pull", eps[k:] + eps[:k])
        fanout = max(_cfg.get("relay_fanout"), 1)
        with self.lock:
            node = self._worker_node(wid)
            st = self._xfer_plans.setdefault(oid, {"feeds": {}, "pulling": {}})
            feeds, pulling = st["feeds"], st["pulling"]
            for ep in eps:  # sealed sources may have grown since last ask
                f = feeds.setdefault(
                    tuple(ep), {"load": 0, "sealed": False, "node": None}
                )
                f["sealed"] = True
            for n_, (_ep, ts_) in list(pulling.items()):
                if ts_ < horizon:  # dead puller that never reported back
                    self._release_pull_slot_locked(oid, n_)
            st = self._xfer_plans.setdefault(oid, {"feeds": feeds, "pulling": pulling})
            if node in pulling:
                # A re-ask from a node already pulling means its previous
                # plan failed (or a sibling worker races it): release the
                # old slot and re-plan fresh.
                self._release_pull_slot_locked(oid, node)
                st = self._xfer_plans.setdefault(
                    oid, {"feeds": feeds, "pulling": pulling}
                )
            # SEALED-FIRST: fill the sources' fanout before chaining off
            # relays — bushier trees mean fewer checksummed relay hops
            # (each hop costs a verify+re-sum of the whole object, about
            # a memcpy's worth of CPU) and shorter failure cascades,
            # while the per-feed fanout bound still caps source egress.
            cands = [
                (not f["sealed"], f["load"], ep)
                for ep, f in feeds.items()
                if f["load"] < fanout and f.get("node") != node
            ]
            if not cands:
                self.metrics["pull_parks"] += 1
                self._park_pull(wid, req_id, oid)
                return _PARKED
            cands.sort(key=lambda c: (c[0], c[1]))
            _relay, _load, feed_ep = cands[0]
            feeds[feed_ep]["load"] += 1
            pulling[node] = (feed_ep, now)
            rep = self.node_object_endpoints.get(node)
            if rep is not None and tuple(rep) != feed_ep:
                # The requester's node serves its own in-flight pull's
                # board from now on: register it as a relay feed.
                rf = feeds.setdefault(
                    tuple(rep), {"load": 0, "sealed": False, "node": node}
                )
                rf["node"] = node
            plan = [list(feed_ep)] + [
                list(ep) for ep in eps if tuple(ep) != feed_ep
            ]
        return ("pull", plan)

    def _release_pull_slot_locked(self, oid: str, node: str) -> None:
        """Caller holds self.lock.  Free `node`'s slot in oid's transfer
        plan (its pull finished, failed, or decayed); drop the plan when
        fully quiesced — sealed feeds rebuild from the directory on the
        next ask."""
        st = self._xfer_plans.get(oid)
        if st is None:
            return
        ent = st["pulling"].pop(node, None)
        if ent is not None:
            f = st["feeds"].get(ent[0])
            if f is not None and f["load"] > 0:
                f["load"] -= 1
        if not st["pulling"] and not any(
            f["load"] > 0 for f in st["feeds"].values()
        ):
            self._xfer_plans.pop(oid, None)

    def _park_pull(self, wid: str, req_id: int, oid: str) -> None:
        """Caller holds self.lock.  Park a staggered puller until a new
        copy registers (or a 5s fallback timer — a failed pull must not
        strand the queue), then re-run the admission."""
        token = {"done": False, "sub": None, "timer": None}

        def serve(_oid=None):
            with self.lock:
                if token["done"]:
                    return
                token["done"] = True
                if token["sub"] is not None:
                    self.pubsub.unsubscribe(token["sub"])
                if token["timer"] is not None:
                    token["timer"].cancel()
            try:
                result = self._req_get_object(wid, req_id, oid)
            except Exception as e:  # noqa: BLE001 — reply with the error
                self._reply(wid, req_id, False, e)
                return
            if result is not _PARKED:
                self._reply(wid, req_id, True, result)

        token["sub"] = self.pubsub.subscribe(
            "object_copied", oid, lambda _o: serve(), once=True, deferred=True
        )
        t = threading.Timer(5.0, serve)
        t.daemon = True
        token["timer"] = t
        t.start()

    def _park_get(self, wid: str, req_id: int, oid: str) -> None:
        """Caller holds self.lock: one once-subscription per parked get;
        the reply runs DEFERRED (outside the runtime lock — it does store
        reads and a conn send)."""
        import functools

        self.pubsub.subscribe(
            "object_ready", oid,
            functools.partial(self._serve_parked_get, wid, req_id),
            once=True, deferred=True,
        )

    def _serve_parked_get(self, wid: str, req_id: int, oid: str) -> None:
        try:
            value = self._object_reply_value(oid, self._worker_node(wid))
            if isinstance(value, tuple) and value[0] == "pull":
                # The just-computed-object broadcast is the thundering
                # herd: N parked gets wake together — admission must gate
                # them exactly like first-ask pulls.
                value = self._admit_pull(wid, req_id, oid, value[1])
                if value is _PARKED:
                    return
            self._reply(wid, req_id, True, value)
        except Exception as e:  # noqa: BLE001 — reply with the error
            self._reply(wid, req_id, False, e)

    def _req_get_object(self, wid: str, req_id: int, oid: str):
        with self.lock:
            if not self.store.is_ready(oid):
                # A lost-but-lineaged object (typically a journaled inline
                # result whose bytes died with the previous head) would
                # otherwise park forever: kick a reconstruction first, then
                # park behind it.  Harmless when the producer is already in
                # flight (_reconstruct dedupes by task_id).
                if oid in self.lineage:
                    self._reconstruct(oid)
                self._park_get(wid, req_id, oid)
                return _PARKED
        try:
            value = self._object_reply_value(oid, self._worker_node(wid))
            if isinstance(value, tuple) and value[0] == "pull":
                return self._admit_pull(wid, req_id, oid, value[1])
            return value
        except ObjectLostError:
            # Bytes vanished (evicted past spill / spill file lost): lineage
            # re-execution (ray: object_recovery_manager.h:41) — park the
            # request behind the reconstructed producer.
            with self.lock:
                if self._reconstruct(oid):
                    self._park_get(wid, req_id, oid)
                    return _PARKED
            raise

    def _req_wait_objects(
        self, wid: str, req_id: int, oids: List[str], num_returns: int,
        timeout: Optional[float],
    ):
        """Event-driven worker wait (replaces the old check_ready poll loop):
        park until num_returns of oids are ready, reply with the flag list.
        A timer bounds parked time when the caller gave a timeout."""
        with self.lock:
            flags = [self.store.is_ready(o) for o in oids]
            pendings = [o for o, f in zip(oids, flags) if not f]
            if sum(flags) >= num_returns or not pendings:
                return flags
            if timeout is not None and timeout <= 0:
                return flags
            import functools

            token = {
                "need": num_returns - sum(flags),
                "wid": wid,
                "req_id": req_id,
                "oids": oids,
                "done": False,
                "timer": None,
                "subs": [],
            }
            for o in pendings:
                token["subs"].append(
                    self.pubsub.subscribe(
                        "object_ready", o,
                        functools.partial(self._on_wait_oid_ready, token),
                        once=True,
                    )
                )
            if timeout is not None:
                t = threading.Timer(timeout, self._wait_token_timeout, args=(token,))
                t.daemon = True
                token["timer"] = t
                t.start()
            return _PARKED

    @_locked
    def _on_wait_oid_ready(self, token, _oid: str) -> None:
        # runs inline inside publish, under self.lock (_object_ready holds it)
        token["need"] -= 1
        if token["need"] <= 0:
            self._wait_token_reply(token)

    @_locked
    def _wait_token_reply(self, token) -> None:
        """Caller holds self.lock.  Reply once and drop the token's
        remaining subscriptions (a timed-out token would otherwise leak
        until its oids happen to become ready)."""
        if token["done"]:
            return
        token["done"] = True
        if token["timer"] is not None:
            token["timer"].cancel()
        for sub in token["subs"]:
            self.pubsub.unsubscribe(sub)
        flags = [self.store.is_ready(o) for o in token["oids"]]
        self._reply(token["wid"], token["req_id"], True, flags)

    def _wait_token_timeout(self, token) -> None:
        with self.lock:
            self._wait_token_reply(token)

    @staticmethod
    def _lineage_cost(spec) -> int:
        return len(spec.args_blob or b"") + 256  # blob + record overhead

    @_locked
    def _lineage_record(self, oid: str, spec) -> None:
        """Caller holds self.lock.  Remember oid's producer spec for
        lineage reconstruction, within the LRU budget (ray:
        task_manager.h:97-104 lineage footprint accounting)."""
        if oid not in self.lineage:
            self.lineage_bytes += self._lineage_cost(spec)
        self.lineage[oid] = spec
        while self.lineage and (
            len(self.lineage) > self.lineage_max
            or self.lineage_bytes > self.lineage_max_bytes
        ):
            evicted, old = self.lineage.popitem(last=False)
            self.lineage_bytes -= self._lineage_cost(old)
            self._inline_lineage.discard(evicted)

    @_locked
    def _reconstruct(self, oid: str) -> bool:
        """Re-execute the producer task of a lost object.  Caller holds
        self.lock.  Returns False when no lineage exists (driver put() /
        actor-task outputs / lineage evicted)."""
        spec = self.lineage.get(oid)
        if spec is None:
            return False
        if spec.task_id in self.tasks:
            return True  # reconstruction already in flight
        if spec.fn_id and self.state.get_function(spec.fn_id) is None:
            # PR-4 edge, closed: the fn blob isn't exported yet (a journal
            # torn-tail ate the export, or the re-execution raced the
            # owner's re-export after a head bounce).  PARK this
            # reconstruction on a function-export FENCE instead of
            # dispatching a task that can only fail "unknown function" —
            # the export hook re-kicks it, and the io-loop tick fails it
            # loudly after _FN_FENCE_TIMEOUT_S so a never-returning owner
            # can't wedge the get forever.
            since, oids = self._fn_fences.setdefault(
                spec.fn_id, (time.monotonic(), [])
            )
            if oid not in oids:
                oids.append(oid)
            with self.store._available:
                for rid in spec.return_ids():
                    self.store._ready.pop(rid, None)
            self.events.emit(
                "WARNING", "lineage",
                "re-execution parked on pending function export",
                fn_id=spec.fn_id, object_id=oid,
            )
            return True
        # Dependencies may have been freed since the original run: recurse
        # up the lineage first (ray: recovery walks the lineage DAG).  A dep
        # that is "ready" but with lost bytes is handled lazily when the
        # worker's get parks on it.  This must run BEFORE invalidating this
        # task's own readiness flags: a dep with no lineage aborts the whole
        # reconstruction, and popped flags would leave every sibling return
        # id permanently un-ready (gets would park forever instead of
        # raising ObjectLostError).
        for d in set(spec.deps):
            if not self.store.is_ready(d) and not self._reconstruct(d):
                return False
        # Invalidate readiness of every return of this task so gets re-park
        # and wait() blocks until the re-execution completes.
        with self.store._available:
            for rid in spec.return_ids():
                self.store._ready.pop(rid, None)
        self.submit_task(spec)
        return True

    def _on_function_export(self, fn_id: str) -> None:
        """GlobalState export hook (fires OUTSIDE state.lock): release
        lineage re-executions parked on this function's fence."""
        with self.lock:
            ent = self._fn_fences.pop(fn_id, None)
            if ent is None:
                return
            for oid in ent[1]:
                try:
                    self._reconstruct(oid)
                except Exception:
                    continue

    def _sweep_fn_fences(self, now_mono: float) -> None:
        """io-loop tick (holds self.lock): a fence nobody re-exported
        within the timeout fails its parked gets LOUDLY instead of
        parking them forever."""
        for fn_id, (since, oids) in list(self._fn_fences.items()):
            if now_mono - since < _FN_FENCE_TIMEOUT_S:
                continue
            self._fn_fences.pop(fn_id, None)
            err = ObjectLostError(
                f"lineage re-execution waited {_FN_FENCE_TIMEOUT_S:.0f}s "
                f"for function {fn_id} to be re-exported; the owner never "
                "re-exported it"
            )
            for oid in oids:
                self.store.put_error(oid, err)
                self._object_ready(oid)
            self.events.emit(
                "WARNING", "lineage", "function-export fence timed out",
                fn_id=fn_id, objects=len(oids),
            )

    def _worker_node(self, wid: str) -> str:
        h = self.workers.get(wid)
        if h is not None:
            return h.node_id
        # Attached drivers read objects as their negotiated pseudo-node:
        # the head node when co-located (zero-copy), a store-less node id
        # when remote (forces inline/pull replies).
        return self.driver_nodes.get(wid, self.head_node_id)

    def _record_sealed(self, wid: str, oid: str, size: int) -> None:
        """A worker sealed a large result into ITS node's store: head-node
        seals land in the owner store's accounting; remote seals only enter
        the object directory (the bytes live on that node until pulled)."""
        node = self._worker_node(wid)
        with self.lock:
            self.object_sizes[oid] = size
        self._note_object(oid, wid)
        self._obj_event(oid, "seal", size, node)
        if node == self.head_node_id:
            self.store.mark_shm_sealed(oid, size)
            return
        with self.lock:
            self.object_locations.setdefault(oid, set()).add(node)
        self.store.mark_remote_sealed(oid)

    def _head_transfer_endpoint(self) -> Tuple[str, int]:
        """The address other nodes pull head-store objects from.  The
        listener may bind a wildcard (RAY_TPU_BIND_HOST=0.0.0.0), which is
        not routable — advertise the node_ip knob instead."""
        host, port = self.address
        if host in ("0.0.0.0", "", "::"):
            from ray_tpu._private import config as _config

            host = _config.get("node_ip")
        return (host, port)

    def _pull_endpoints(self, oid: str, exclude_head: bool = False) -> list:
        """Endpoints currently holding a copy, head store first (its
        listener serves object_fetch one-shots)."""
        eps = []
        if not exclude_head and self.store.has_local(oid):
            eps.append(self._head_transfer_endpoint())
        with self.lock:
            for n in self.object_locations.get(oid, ()):  # remote copies
                ep = self.node_object_endpoints.get(n)
                if ep is not None:
                    eps.append(ep)
        return eps

    def _object_reply_value(self, oid: str, requester_node: Optional[str] = None):
        """Build the get_object reply for a requester on requester_node:
        "inline" (small, bytes ride the control conn), "shm" (a copy is in
        the requester's OWN node store — mmap it), or ("pull", endpoints)
        (fetch over the transfer plane)."""
        err = self.store.error_for(oid)
        if err is not None:
            raise err
        if requester_node is None:
            requester_node = self.head_node_id
        if requester_node != self.head_node_id:
            with self.lock:
                local_copy = requester_node in self.object_locations.get(oid, ())
            if local_copy:
                return ("shm", None)
            obj = self.store._mem.get(oid)
            if obj is None:
                eps = self._pull_endpoints(oid)
                if eps:
                    return ("pull", eps)
                raise ObjectLostError(oid)
            # small: inline below
        else:
            if oid in self.store._in_shm:
                return ("shm", None)
            obj = self.store.get_sealed(oid)  # mem, or restore-from-spill
            if obj is None:
                eps = self._pull_endpoints(oid, exclude_head=True)
                if eps:
                    return ("pull", eps)
                raise ObjectLostError(oid)
            if oid in self.store._in_shm:  # a restore re-sealed it locally
                return ("shm", None)
        import pickle

        packed = bytes(
            ser.pack(bytes(obj.payload), [pickle.PickleBuffer(b) for b in obj.buffers])
        )
        return ("inline", packed)

    def _put_packed(self, oid: str, packed: bytes) -> None:
        payload, bufs = ser.unpack(memoryview(packed))
        import pickle

        self.object_sizes[oid] = len(packed)
        self.store.put_serialized(oid, bytes(payload), [pickle.PickleBuffer(b) for b in bufs])

    # ------------------------------------------------------------------
    # object readiness fan-out

    @_locked
    def _on_dep_ready(self, tid: str, _oid: str) -> None:
        # runs inline inside publish, under self.lock (_object_ready holds it)
        rec = self.tasks.get(tid)
        if rec is None:
            return
        rec.unmet_deps -= 1
        if rec.unmet_deps <= 0 and rec.state == "PENDING":
            rec.state = "READY"
            rec.stamp("queued")
            self.ready_queue.append(tid)

    def _object_ready(self, oid: str) -> None:
        with self.lock:
            # One publish fans out to every subscriber family: wait tokens
            # and dep-resolution run inline (they mutate scheduler state
            # under this lock); parked-get replies come back deferred and
            # run after the lock drops.
            deferred = self.pubsub.publish("object_ready", oid, oid)
            err = self.store.error_for(oid)
            if err is not None:
                # Propagate the error to ALREADY-QUEUED dependents eagerly:
                # bucketed dispatch only probes bucket heads, so a dependent
                # parked behind a blocked head would otherwise hang instead
                # of failing fast (the failure path is rare — an O(queue)
                # scan here costs nothing on the hot path).
                for shape in list(self.ready_queue.buckets.keys()):
                    q = self.ready_queue.buckets.get(shape)
                    if q is None:  # emptied by a nested propagation
                        continue
                    doomed = [
                        t for t in q
                        if (r := self.tasks.get(t)) is not None
                        and oid in r.spec.deps
                    ]
                    if doomed:
                        keep = deque(t for t in q if t not in set(doomed))
                        if keep:
                            self.ready_queue.buckets[shape] = keep
                        else:
                            self.ready_queue.buckets.pop(shape, None)
                        for t in doomed:
                            rec = self.tasks.get(t)
                            if rec is not None:
                                self._finish_with_error(rec, err, release=False)
            self._dispatch()
        for cb in deferred:
            cb(oid)

    # ------------------------------------------------------------------
    # submission (ray: CoreWorker::SubmitTask -> direct_task_transport.h:75)

    def submit_task(self, spec: TaskSpec, allow_pending: bool = False) -> List[str]:
        if (
            spec.runtime_env
            and not spec.runtime_env.get("_resolved")
            and (
                spec.runtime_env.get("working_dir")
                or spec.runtime_env.get("py_modules")
            )
        ):
            # Package local dirs into content-addressed KV entries ONCE;
            # workers fetch + extract (ray: runtime_env packaging/uri_cache).
            from ray_tpu._private.runtime_env import resolve_runtime_env

            spec.runtime_env = resolve_runtime_env(
                spec.runtime_env,
                lambda uri, data: self.state.kv_put(uri, data),
                self.session_name,
            )
        rec = TaskRecord(spec)
        rec.allow_pending = allow_pending
        return_ids = spec.return_ids()
        with self.lock:
            # Idempotent by task id: a client retrying across a head bounce
            # (its reply was lost) must not double-register the task
            # (ray: GCS dedupes re-registrations after failover the same
            # way).  Already-running: same record; already-finished: the
            # results are in the store.
            if spec.task_id in self.tasks or (
                return_ids and all(self.store.is_ready(o) for o in return_ids)
            ):
                return return_ids
            self.metrics["tasks_submitted"] += 1
            if spec.is_actor_creation:
                self.metrics["actors_created"] += 1
            if (
                self._spill_after > 0
                and len(self.tasks) >= self._spill_after
                and not spec.deps
                and not spec.contained_refs
                and not spec.runtime_env
                and self._lease_eligible(spec)
            ):
                # Backlog overflow: the spec rides a disk segment instead
                # of ~1KB of head memory; _dispatch reloads FIFO chunks
                # as the in-memory backlog drains.  No TaskRecord, no
                # dedupe entry — an overflow task re-submitted across a
                # head bounce re-runs (at-least-once, same contract as
                # direct dispatch).
                if self._ready_spill is None:
                    self._ready_spill = _ReadySpill(os.path.join(
                        f"/tmp/raytpu-spill-{self.session_name}",
                        "ready_overflow.bin",
                    ))
                self._ready_spill.append(spec)
                return return_ids
            self.tasks[spec.task_id] = rec
            for c in spec.contained_refs:
                self.store.add_ref(c)  # arg borrow for the task's lifetime
            import functools

            unmet = 0
            for d in set(spec.deps):
                if not self.store.is_ready(d):
                    self.pubsub.subscribe(
                        "object_ready", d,
                        functools.partial(self._on_dep_ready, spec.task_id),
                        once=True,
                    )
                    unmet += 1
            rec.unmet_deps = unmet
            if unmet == 0:
                rec.state = "READY"
                rec.stamp("queued")
                shape = self.ready_queue._shape_of(spec)
                # Submit→running FAST PATH: deps ready, bucket empty, and
                # an idle same-key leaseholder exists — push straight to
                # it and skip the whole dispatch scan (per-submit cost
                # O(1), not O(shapes)).  Dep errors still fail the task
                # exactly as the scan would.
                if not self.ready_queue.buckets.get(shape):
                    dep_err = None
                    for d in spec.deps:
                        e = self.store.error_for(d)
                        if e is not None:
                            dep_err = e
                            break
                    if dep_err is not None:
                        self._finish_with_error(rec, dep_err, release=False)
                        return return_ids
                    le = self._idle_lease_for(shape)
                    if le is not None:
                        self._dispatch_on_lease(le, rec)
                        return return_ids
                self.ready_queue.append(spec.task_id, shape)
            self._dispatch()
        return return_ids

    def create_actor(self, spec: TaskSpec, owner_did: Optional[str] = None) -> str:
        with self.lock:
            if spec.actor_id in self.actors:
                return spec.actor_id  # client retry across a head bounce
        info = ActorInfo(
            actor_id=spec.actor_id,
            name=spec.actor_name,
            max_restarts=spec.max_restarts,
            creation_spec=spec,
            namespace=spec.actor_namespace or self.namespace,
            owner_did=owner_did,
            detached=spec.lifetime == "detached",
        )
        self.state.register_actor(info)
        with self.lock:
            self.actors[spec.actor_id] = ActorRuntime(info)
        self.submit_task(spec)
        return spec.actor_id

    def submit_actor_task(self, spec: TaskSpec) -> List[str]:
        return_ids = spec.return_ids()
        with self.lock:
            if spec.task_id in self.tasks or (
                return_ids and all(self.store.is_ready(o) for o in return_ids)
            ):
                return return_ids  # client retry across a head bounce
            ar = self.actors.get(spec.actor_id)
            info = self.state.get_actor(spec.actor_id)
            if ar is None or info is None or info.state == DEAD:
                for oid in return_ids:
                    self.store.put_error(oid, ActorDiedError(spec.actor_id))
                    self._object_ready(oid)
                return return_ids
            rec = TaskRecord(spec)
            self.tasks[spec.task_id] = rec
            for c in spec.contained_refs:
                self.store.add_ref(c)
            # Actor calls are pushed directly to the actor's worker in
            # submission order (ray: direct_actor_task_submitter.h:67);
            # dependency resolution happens executor-side via parked gets.
            if info.state == ALIVE and ar.worker_id:
                self._push_actor_task(ar, rec)
            else:
                ar.queued.append(spec.task_id)
        return return_ids

    def _push_actor_task(self, ar: ActorRuntime, rec: TaskRecord) -> None:
        h = self.workers.get(ar.worker_id)
        if h is None:
            ar.queued.append(rec.spec.task_id)
            return
        rec.state = "RUNNING"
        rec.start_time = time.time()
        rec.stages["leased"] = rec.start_time
        rec.worker_id = h.worker_id
        rec.node_id = h.node_id
        ar.in_flight[rec.spec.task_id] = None
        blob = None
        if rec.spec.fn_id not in h.known_fns:
            blob = self.state.get_function(rec.spec.fn_id)
            h.known_fns.add(rec.spec.fn_id)
        self._send(h, ("task", rec.spec, blob))
        if h.conn is not None:
            rec.stamp("pushed")  # else: stamped at the pending-send flush

    # ------------------------------------------------------------------
    # dispatch loop (ray: cluster_task_manager.h + local_task_manager.h)

    @staticmethod
    def _strategy_shape_key(strategy):
        """Stable equality key for head-of-line grouping — the default repr
        embeds the instance address, which would make every task its own
        shape and silently disable the blocking."""
        from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

        if isinstance(strategy, NodeAffinitySchedulingStrategy):
            return ("affinity", strategy.node_id, strategy.soft)
        return strategy if isinstance(strategy, (str, type(None))) else repr(strategy)

    # ------------------------------------------------------------------
    # head-side lease reuse (ray: direct_task_transport.h:40-55 — the
    # SchedulingKey-keyed lease pool, applied to the head's own relayed
    # dispatch): the first task of a key pays full placement and BINDS
    # its worker to the key with resources held; same-key tasks then
    # bypass the scheduler entirely and push straight onto an idle
    # leaseholder.  All helpers run under self.lock.

    @staticmethod
    def _lease_eligible(spec) -> bool:
        return (
            spec.actor_id is None
            and not spec.is_actor_creation
            and spec.placement_group_id is None
            and spec.scheduling_strategy in (None, "DEFAULT", "SPREAD")
        )

    def _idle_lease_for(self, key) -> Optional[TaskLease]:
        leases = self.task_leases.get(key)
        if not leases:
            return None
        for le in list(leases):
            if le.idle_since is None:
                continue
            node = self.state.nodes.get(le.node_id)
            if node is not None and node.draining:
                # A late same-key task must NOT ride an idle lease onto a
                # draining node — revoke the binding (resources released,
                # worker returned for the depart to reap) so the task
                # re-drives through full placement elsewhere.
                self._revoke_lease_locked(le, cause="drain")
                continue
            h = self.workers.get(le.worker_id)
            if h is None or h.state != "busy" or h.current_task is not None:
                # Defensive: the crash path revokes synchronously, so a
                # stale binding here means the worker moved on without
                # us — drop the lease WITHOUT re-releasing resources (a
                # double release would inflate the node ledger).
                self._revoke_lease_locked(
                    le, cause="stale", release=False, return_worker=False
                )
                continue
            return le
        return None

    def _grant_lease_locked(self, key, h, node, spec) -> TaskLease:
        self._task_lease_seq += 1
        le = TaskLease(
            f"tl-{self._task_lease_seq}", key, h.worker_id, node,
            dict(spec.resources),
        )
        self.task_leases.setdefault(key, []).append(le)
        self.lease_by_worker[h.worker_id] = le
        self.metrics["task_leases_granted"] += 1
        self._journal_append(
            ("lease", "grant", le.lease_id, repr(key), h.worker_id, node,
             dict(spec.resources))
        )
        return le

    def _dispatch_on_lease(self, le: TaskLease, rec: TaskRecord) -> None:
        """Fast path: push a ready same-key task straight onto an idle
        leaseholder — no placement, no resource churn, no pool ops."""
        h = self.workers[le.worker_id]
        le.idle_since = None
        le.dispatched += 1
        self.metrics["lease_dispatches"] += 1
        now = time.monotonic()
        if now - le.last_extend_journal > self._lease_idle_s * 0.5:
            # Extends journal at half-idle-window granularity: restart
            # diagnostics see the lease was hot without paying one entry
            # per task (group commit batches these anyway).
            le.last_extend_journal = now
            self._journal_append(("lease", "extend", le.lease_id, le.dispatched))
        spec = rec.spec
        rec.state = "RUNNING"
        rec.start_time = time.time()
        rec.stages["leased"] = rec.start_time
        rec.node_id = le.node_id
        rec.worker_id = h.worker_id
        rec.lease = le
        h.current_task = spec.task_id
        blob = None
        if spec.fn_id not in h.known_fns:
            blob = self.state.get_function(spec.fn_id)
            h.known_fns.add(spec.fn_id)
        self._send(h, ("task", spec, blob))
        if h.conn is not None:
            rec.stamp("pushed")

    def _lease_task_finished(self, rec: TaskRecord, h) -> None:
        """A task finished (or retry-released) on a LIVE leaseholder:
        re-arm the lease and chain the next same-key task immediately —
        the completion-to-dispatch path the flamegraphs showed paying
        full placement per task."""
        le = rec.lease
        rec.lease = None
        rec.node_id = None
        le.idle_since = time.monotonic()
        if h is not None:
            h.current_task = None
        node = self.state.nodes.get(le.node_id)
        if node is not None and node.draining:
            # Drain-revoke instead of re-arm: chaining the next same-key
            # task here would keep re-busying capacity that is leaving.
            # The queued siblings re-drive through full placement onto
            # surviving nodes on the dispatch below.
            self._revoke_lease_locked(le, cause="drain")
            self._dispatch()
            return
        q = self.ready_queue.buckets.get(le.key)
        while q:
            tid = q[0]
            nrec = self.tasks.get(tid)
            if nrec is None or nrec.cancelled:
                q.popleft()
                continue
            dep_err = None
            for d in nrec.spec.deps:
                e = self.store.error_for(d)
                if e is not None:
                    dep_err = e
                    break
            if dep_err is not None:
                q.popleft()
                self._finish_with_error(nrec, dep_err, release=False)
                continue
            q.popleft()
            if not q:
                self.ready_queue.buckets.pop(le.key, None)
            self._dispatch_on_lease(le, nrec)
            return
        if q is not None and not q:
            self.ready_queue.buckets.pop(le.key, None)

    def _revoke_lease_locked(
        self, le: TaskLease, cause: str, release: bool = True,
        return_worker: bool = True,
    ) -> None:
        """Unbind a lease: journal the revocation, release its held
        resources (exactly once — the caller says whether this revoke
        still owns them), return the worker to the shared pool."""
        pool = self.task_leases.get(le.key)
        if pool is not None:
            try:
                pool.remove(le)
            except ValueError:
                pass
            if not pool:
                self.task_leases.pop(le.key, None)
        if self.lease_by_worker.get(le.worker_id) is le:
            self.lease_by_worker.pop(le.worker_id, None)
        if release:
            self.scheduler.release(le.node_id, le.resources)
        self.metrics["task_leases_revoked"] += 1
        self._journal_append(("lease", "revoke", le.lease_id, cause))
        if return_worker:
            h = self.workers.get(le.worker_id)
            if h is not None and h.state == "busy" and h.current_task is None:
                self._return_worker(h)

    def _revoke_one_idle_lease(self) -> bool:
        """Demand revocation: a different shape (or a placement group)
        can't place while idle leases pin resources — free the stalest
        one and let the caller retry.  Same-key idle leases can't reach
        here (dispatch consumes them first), so this never thrashes a
        hot stream."""
        best = None
        for pool in self.task_leases.values():
            for le in pool:
                if le.idle_since is None:
                    continue
                if best is None or le.idle_since < best.idle_since:
                    best = le
        if best is None:
            return False
        self._revoke_lease_locked(best, cause="demand")
        return True

    def _revoke_idle_leases(self, now_mono: float) -> None:
        """io-loop tick: leases idle past RAY_TPU_LEASE_IDLE_S return
        their worker + resources to the shared pool, so a burst's leases
        can't strand capacity (chaos leans on this + the crash-path
        revoke)."""
        revoked = False
        for pool in list(self.task_leases.values()):
            for le in list(pool):
                if (
                    le.idle_since is not None
                    and now_mono - le.idle_since > self._lease_idle_s
                ):
                    self._revoke_lease_locked(le, cause="idle-timeout")
                    revoked = True
        if revoked:
            self._dispatch()

    @_locked
    def _dispatch(self) -> None:
        # caller holds self.lock
        sp = self._ready_spill
        if (
            sp is not None
            and sp.count
            and len(self.tasks) <= max(self._spill_after // 2, 1000)
        ):
            # The in-memory backlog drained below the low watermark:
            # reload the next FIFO chunk of spilled overflow specs.
            for spec in sp.load(2000):
                if spec.task_id in self.tasks:
                    continue
                rec = TaskRecord(spec)
                rec.state = "READY"
                rec.stamp("queued")
                self.tasks[spec.task_id] = rec
                self.ready_queue.append(spec.task_id)
        for pg_id in list(self.pending_pgs):
            pg = self.state.placement_groups.get(pg_id)
            if pg is None or pg.state != "PENDING":
                self.pending_pgs.remove(pg_id)
                continue
            ok = self.scheduler.reserve_placement_group(pg)
            while not ok and self._revoke_one_idle_lease():
                # Idle leases were pinning the bundle capacity.
                ok = self.scheduler.reserve_placement_group(pg)
            if ok:
                self.pending_pgs.remove(pg_id)
        # Shape-bucketed dispatch (ray: ClusterTaskManager queues tasks per
        # scheduling class): probe ONE head task per shape; if it cannot
        # place, the whole bucket stays untouched this round.  Per-event
        # cost is O(shapes), not O(queued tasks) — rotating the full
        # backlog per completion was a measured 4x collapse at 4 clients
        # (the deeper the queue, the slower every completion).
        for shape in list(self.ready_queue.buckets.keys()):
            q = self.ready_queue.buckets.get(shape)
            while q:
                tid = q[0]
                rec = self.tasks.get(tid)
                if rec is None or rec.cancelled:
                    q.popleft()
                    continue
                spec = rec.spec
                # error propagation: if any dep errored, fail without running
                dep_err = None
                for d in spec.deps:
                    e = self.store.error_for(d)
                    if e is not None:
                        dep_err = e
                        break
                if dep_err is not None:
                    q.popleft()
                    self._finish_with_error(rec, dep_err, release=False)
                    continue
                if Scheduler.is_pg_task(spec):
                    sel = self.scheduler.select_pg(spec, spec.resources)
                    if sel is None:
                        if self._revoke_one_idle_lease():
                            continue  # freed pinned resources: retry head
                        break  # bucket blocked: siblings can't place either
                    node, bidx = sel
                    rec.pg = (self.scheduler._pg_for_spec(spec)[0], bidx)
                else:
                    # Lease fast path: an idle same-key leaseholder takes
                    # the task with zero placement work.
                    le = self._idle_lease_for(shape)
                    if le is not None:
                        q.popleft()
                        self._dispatch_on_lease(le, rec)
                        continue
                    try:
                        node = self.scheduler.select_node(spec)
                    except ValueError as e:
                        if self.allow_pending_infeasible or rec.allow_pending:
                            break
                        q.popleft()
                        self._finish_with_error(rec, e, release=False)
                        continue
                    if node is None or not self.scheduler.acquire(
                        node, spec.resources
                    ):
                        if self._revoke_one_idle_lease():
                            continue  # idle leases were the missing slack
                        break
                q.popleft()
                self._dispatch_placed(rec, node, shape)
            if not q:
                self.ready_queue.buckets.pop(shape, None)

    @_locked
    def _dispatch_placed(self, rec: TaskRecord, node: str, shape=None) -> None:
        # caller holds self.lock; resources for `node` already acquired
        spec = rec.spec
        tid = spec.task_id
        h = self._lease_worker(node, spec)
        rec.state = "RUNNING"
        rec.start_time = time.time()
        rec.stages["leased"] = rec.start_time
        rec.node_id = node
        rec.worker_id = h.worker_id
        h.current_task = tid
        if self._lease_eligible(spec):
            # First task of its SchedulingKey through full placement:
            # bind the worker to the key — same-key successors skip the
            # scheduler entirely (_dispatch_on_lease).
            rec.lease = self._grant_lease_locked(
                shape if shape is not None else self.ready_queue._shape_of(spec),
                h, node, spec,
            )
        if spec.is_actor_creation:
            h.state = "actor"
            h.actor_id = spec.actor_id
            ar = self.actors.get(spec.actor_id)
            if ar is not None:
                ar.worker_id = h.worker_id
                ar.placement = (
                    ("pg",) + rec.pg if rec.pg else ("node", node)
                )
        else:
            h.state = "busy"
        blob = None
        if spec.fn_id not in h.known_fns:
            blob = self.state.get_function(spec.fn_id)
            h.known_fns.add(spec.fn_id)
        kind = "create_actor" if spec.is_actor_creation else "task"
        self._send(h, (kind, spec, blob))
        if h.conn is not None:
            # A still-starting worker queues the frame in pending_sends;
            # the handshake flush stamps "pushed" then — so the lease
            # stage honestly carries the worker's whole boot time.
            rec.stamp("pushed")

    # ------------------------------------------------------------------
    # completion / failure

    def _release_for(self, rec: TaskRecord) -> None:
        if rec.lease is not None:
            # The LEASE owns the node resources: they release exactly once
            # at revoke (idle timeout, demand, worker death), never per
            # task — releasing here too would inflate the node ledger.
            rec.lease = None
            rec.node_id = None
            return
        if rec.pg is not None:
            self.scheduler.release_pg(rec.pg[0], rec.pg[1], rec.spec.resources)
            rec.pg = None
            rec.node_id = None
        elif rec.node_id:
            self.scheduler.release(rec.node_id, rec.spec.resources)
            rec.node_id = None

    def _release_actor_placement(self, ar: ActorRuntime) -> None:
        res = ar.info.creation_spec.resources
        if ar.placement is None:
            return
        if ar.placement[0] == "pg":
            self.scheduler.release_pg(ar.placement[1], ar.placement[2], res)
        else:
            self.scheduler.release(ar.placement[1], res)
        ar.placement = None

    @_locked
    def _on_task_done(self, wid: str, task_id: str, results, error_blob,
                      timing=None) -> None:
        # caller holds self.lock
        rec = self.tasks.pop(task_id, None)
        h = self.workers.get(wid)
        if rec is None:
            # Unknown/already-failed task (e.g. cancelled, actor queue
            # failed): its results are dropped, so the executor's
            # serialize-time guard borrows must still be released.
            if error_blob is None:
                for item in results:
                    for c in item[3]:
                        self._decref_local(c)
            return
        spec = rec.spec
        # Executor-side stage stamps (recv/start/end wall clock) land on
        # the head clock via the handshake-estimated per-conn offset —
        # the same correction task_events/spans get at ingest.
        if isinstance(timing, dict):
            off = self.clock_offsets.get(wid, 0.0)
            for src, dst in (
                ("recv", "received"), ("start", "running"), ("end", "exec_done"),
            ):
                v = timing.get(src)
                if isinstance(v, (int, float)):
                    rec.stages[dst] = v + off
        rec.stamp("done")
        if error_blob is not None and not (
            spec.retry_exceptions and spec.attempt < spec.max_retries
        ):
            # Only FINAL failures count — a retried attempt is not a failed
            # task (tasks_retried tracks attempts).
            self._record_task_end(rec, wid, "FAILED")
        ready_ids = []
        if error_blob is None:
            for item in results:
                oid, kind, data, contained = item
                self._store_contained(oid, contained)
                # Release the executor's serialize-time guard borrows now
                # that the stored-object borrow above holds the children
                # (see worker_proc._store_results).
                for c in contained:
                    self._decref_local(c)
                if kind == "shm":
                    self._record_sealed(wid, oid, data)
                else:
                    self._put_packed(oid, data)
                ready_ids.append(oid)
                if spec.actor_id is None:
                    self._lineage_record(oid, spec)
                    if kind != "shm":
                        # Inline bytes live ONLY in this process: journal
                        # the lineage entry so a post-restart get() can
                        # re-execute the producer instead of erroring
                        # (sealed results survive in node stores and need
                        # no journal).
                        self._inline_lineage.add(oid)
                        self._journal_append(("lineage", oid, spec))
            # Results stored + lineage recorded: the lifecycle record is
            # complete — stamp "sealed" and fold the stage durations into
            # the ring + histograms (the per-task state machine's fold).
            rec.stamp("sealed")
            self._record_task_end(rec, wid, "FINISHED")
            if spec.is_actor_creation:
                self._on_actor_alive(spec.actor_id)
        else:
            err = cloudpickle.loads(error_blob)
            if spec.retry_exceptions and spec.attempt < spec.max_retries:
                self._retry_task(rec, h)
                return
            for oid in spec.return_ids():
                self.store.put_error(oid, err)
                ready_ids.append(oid)
            if spec.is_actor_creation:
                ar = self.actors.get(spec.actor_id)
                self.state.set_actor_state(spec.actor_id, DEAD, death_cause=str(err))
                if ar:
                    self._fail_actor_queue(ar, ActorDiedError(f"creation failed: {err}"))
                    self._release_actor_placement(ar)
                    if h is not None:
                        self._send(h, ("kill",))
                        h.state = "dead"
        # release borrows
        for c in spec.contained_refs:
            self._decref_local(c)
        # free resources + worker
        if spec.actor_id is not None and not spec.is_actor_creation:
            ar = self.actors.get(spec.actor_id)
            if ar:
                ar.in_flight.pop(task_id, None)
        elif not spec.is_actor_creation:
            le = rec.lease
            if (
                le is not None
                and h is not None
                and h.state == "busy"
                and self.lease_by_worker.get(wid) is le
            ):
                # Leaseholder stays bound: chain the next same-key task
                # now, or idle within the lease window.
                self._lease_task_finished(rec, h)
            else:
                self._release_for(rec)
                if h is not None and h.state == "busy":
                    self._return_worker(h)
        for oid in ready_ids:
            self._object_ready(oid)
        if spec.is_actor_creation:
            # The creation return (always None, or the creation error) has
            # no ObjectRef holder anywhere — create_actor hands back the
            # actor ID, not a ref — so the stored bytes were orphaned at
            # refcount 0 forever.  Surfaced by the object ledger (every
            # actor left a no-live-holder suspect); freed here at the
            # source instead of exempted in the report.
            for oid in spec.return_ids():
                self.store.remove_ref(oid)
        self._dispatch()

    def _retry_task(self, rec: TaskRecord, h: Optional[WorkerHandle]) -> None:
        spec = rec.spec
        spec.attempt += 1
        self.metrics["tasks_retried"] += 1
        # A fresh attempt restarts the stage machine (stale executor/done
        # stamps from the failed attempt would disorder the telescoping);
        # the original submit time is kept so total wall stays honest.
        rec.stages = {"submit": rec.stages.get("submit", time.time())}
        if spec.actor_id is not None and not spec.is_actor_creation:
            # Relayed actor-call retry: re-push to the actor's executor
            # (the plain ready queue would lease a stateless worker and
            # run the method without the actor instance).
            ar = self.actors.get(spec.actor_id)
            info = self.state.get_actor(spec.actor_id)
            if ar is None or info is None or info.state == DEAD:
                self._finish_with_error(rec, ActorDiedError(spec.actor_id),
                                        release=False)
                return
            self.tasks[spec.task_id] = rec
            if info.state == ALIVE and ar.worker_id:
                self._push_actor_task(ar, rec)
            else:
                ar.queued.append(spec.task_id)
            return
        le = rec.lease
        if (
            le is not None
            and h is not None
            and h.state == "busy"
            and self.lease_by_worker.get(h.worker_id) is le
        ):
            # Error-retry on a live leaseholder: the lease re-arms (the
            # retried attempt likely re-dispatches right back onto it).
            self._lease_task_finished(rec, h)
        else:
            self._release_for(rec)
            if h is not None and h.state == "busy":
                self._return_worker(h)
        rec.state = "READY"
        rec.stamp("queued")
        rec.node_id = rec.worker_id = None
        self.tasks[spec.task_id] = rec
        self.ready_queue.append(spec.task_id)
        self._dispatch()

    def _finish_with_error(self, rec: TaskRecord, err: Exception, release: bool) -> None:
        spec = rec.spec
        self.tasks.pop(spec.task_id, None)
        self._record_task_end(rec, rec.worker_id, "FAILED")
        if release:
            self._release_for(rec)
        for c in spec.contained_refs:
            self._decref_local(c)
        for oid in spec.return_ids():
            self.store.put_error(oid, err)
            self._object_ready(oid)
        if spec.is_actor_creation:
            self.state.set_actor_state(spec.actor_id, DEAD, death_cause=str(err))
            ar = self.actors.get(spec.actor_id)
            if ar:
                self._fail_actor_queue(ar, ActorDiedError(str(err)))

    def _on_actor_alive(self, actor_id: str) -> None:
        ar = self.actors.get(actor_id)
        if ar is None:
            return
        ar._creation_crash_retries = 0  # fresh budget per successful start
        self.state.set_actor_state(actor_id, ALIVE, worker_id=ar.worker_id)
        while ar.queued:
            tid = ar.queued.popleft()
            rec = self.tasks.get(tid)
            if rec is not None and not rec.cancelled:
                self._push_actor_task(ar, rec)

    def _fail_actor_queue(self, ar: ActorRuntime, err: Exception) -> None:
        # (each popped record below is also logged to the task-event sink)
        doomed = list(ar.queued) + list(ar.in_flight)
        ar.queued.clear()
        ar.in_flight.clear()
        for tid in doomed:
            rec = self.tasks.pop(tid, None)
            if rec is None:
                continue
            self._record_task_end(rec, rec.worker_id, "FAILED")
            for oid in rec.spec.return_ids():
                self.store.put_error(oid, err)
                self._object_ready(oid)
            for c in rec.spec.contained_refs:
                self._decref_local(c)

    def _record_task_end(self, rec, wid, state: str) -> None:
        from ray_tpu._private import telemetry as _telemetry

        spec = rec.spec
        self.metrics["tasks_finished" if state == "FINISHED" else "tasks_failed"] += 1
        end = time.time()
        durations = _telemetry.stage_durations(rec.stages)
        self.task_events.append(
            {
                "task_id": spec.task_id,
                "name": spec.name,
                "state": state,
                "node_id": rec.node_id,
                "worker_id": wid,
                "actor_id": spec.actor_id,
                "parent_task_id": spec.parent_task_id,
                "attempt": spec.attempt,
                "end_time": end,
                "duration": (end - rec.start_time) if rec.start_time else 0.0,
                "creation": spec.is_actor_creation,
                "stages": dict(rec.stages),
                "durations": durations,
            }
        )
        self._observe_stage_durations(durations)

    def _observe_stage_durations(self, durations) -> None:
        """Fold one task's per-stage seconds into the
        task_stage_seconds{stage=...} histograms (never raises — the
        fold must not take the completion path down).  Tag resolution is
        cached per stage label: this runs for EVERY finished task (twice
        per direct task via task_events) and the per-observe merge+sort
        was a measured slice of the head's completion cost."""
        if not durations:
            return
        try:
            cache = self._stage_key_cache
            if cache is None:
                from ray_tpu._private import telemetry as _telemetry

                hist = _telemetry.task_stage_histogram()
                cache = self._stage_key_cache = (hist, {})
            hist, keys = cache
            for stage, v in durations.items():
                k = keys.get(stage)
                if k is None:
                    k = keys[stage] = hist.resolved_key({"stage": stage})
                hist.observe_resolved(k, v)
        except Exception:
            pass

    @_locked
    def _deps_locality(self, deps) -> Dict[str, int]:
        """{node_id: BYTES of dep objects local there} — feeds the
        scheduler's locality preference (dispatch path; called under
        self.lock via _dispatch).  Size-weighted, so a node holding one
        100MB argument beats a node holding three 1KB ones (ray: the
        hybrid policy's locality/load tradeoff weighs transfer cost);
        tiny deps (everything under the locality_min_bytes knob in total)
        yield no pull at all — spreading wins when the wire cost is noise."""
        from ray_tpu._private import config as _config

        scores: Dict[str, int] = {}
        for d in deps:
            size = self.object_sizes.get(d, 1)
            for n in self.object_locations.get(d, ()):
                scores[n] = scores.get(n, 0) + size
            if self.store.has_local(d):
                scores[self.head_node_id] = (
                    scores.get(self.head_node_id, 0) + size
                )
        floor = _config.get("locality_min_bytes")
        if scores and max(scores.values()) < floor:
            return {}
        return scores

    @_locked
    def _fail_task_record(
        self, rec: TaskRecord, wid: Optional[str], err: Exception,
        record_end: bool = True,
    ) -> None:
        """Caller holds self.lock.  Terminal task failure: pop + release,
        error every return id, drop borrowed refs (the shared epilogue of
        every crash/cancel/OOM/env-failure branch)."""
        spec = rec.spec
        self.tasks.pop(spec.task_id, None)
        self._release_for(rec)
        if record_end:
            self._record_task_end(rec, wid, "FAILED")
        for oid in spec.return_ids():
            self.store.put_error(oid, err)
            self._object_ready(oid)
        for c in spec.contained_refs:
            self._decref_local(c)

    @_locked
    def _retry_task_record(self, rec: TaskRecord) -> None:
        # caller holds self.lock
        self.metrics["tasks_retried"] += 1
        self._release_for(rec)
        rec.state = "READY"
        rec.stages = {"submit": rec.stages.get("submit", time.time())}
        rec.stamp("queued")
        rec.worker_id = None
        self.ready_queue.append(rec.spec.task_id)
        self._dispatch()

    @_locked
    def _on_worker_crash(self, wid: str) -> None:
        # caller holds self.lock.  Pop BOTH classification riders up front:
        # leaving them behind on duplicate notifications would leak entries
        # for the head's lifetime.
        oom = self._oom_kills.pop(wid, None)
        env_fail = self._env_failures.pop(wid, None)
        self.worker_peer_endpoints.pop(wid, None)
        # Telemetry: a dead process's gauges (queue depths) must not keep
        # contributing to the cluster aggregate (its own lock; no I/O).
        self.telemetry.forget(wid)
        self.ledger.forget(wid)
        self.profiles.forget(wid)
        # Ref borrows the dead process still held: park them as DEAD-
        # HOLDER leak suspects (attributed to this worker's node/pid by
        # `ray_tpu memory --leaks`), reclaimed after the grace so the
        # bytes don't stay pinned forever (ray: the owner releases a dead
        # borrower's references the same way).
        dead_refs = self.worker_refs.pop(wid, None)
        if dead_refs:
            from ray_tpu._private import config as _cfg_leak

            hh = self.workers.get(wid)
            self._dead_refs[wid] = {
                "refs": dead_refs,
                "node": hh.node_id if hh is not None else None,
                "pid": hh.pid if hh is not None else None,
                "t": time.time(),
                "reclaim_at": time.monotonic()
                + _cfg_leak.get("leak_reclaim_grace_s"),
            }
        self.clock_offsets.pop(wid, None)
        # Lease-dispatched tasks running ON this worker die with it; their
        # executors can never send the terminal event that would clear the
        # RUNNING entry (the caller's retry, if any, re-reports).
        for tid, e in list(self.direct_running.items()):
            if e.get("worker_id") == wid:
                self.direct_running.pop(tid, None)
        self._drop_remote_subs(wid)
        # Fences routed through this worker can never ack: fail them so the
        # caller falls back to the head path instead of hanging.
        for fid, ent in list(self._pending_fences.items()):
            if ent[2] == wid:
                self._pending_fences.pop(fid, None)
                # Restartable actor: "pending" keeps the caller relaying
                # until the new instance resolves; "dead" would pin the
                # relay path forever.
                verdict = "pending" if ent[4] else "dead"
                self._reply(ent[0], ent[1], True, (verdict, None, None, ent[4]))
        # A head-side task lease dies with its worker: revoke NOW (journal
        # + release the held resources exactly once) so the in-flight
        # task's retry below re-places through the scheduler instead of
        # binding to a ghost — chaos asserts no stranded capacity.
        tle = self.lease_by_worker.get(wid)
        if tle is not None:
            self._revoke_lease_locked(tle, cause="worker_death",
                                      return_worker=False)
        # Leases die with the worker they lease (callers see the peer conn
        # EOF and retry) and with the CALLER that held them (its workers
        # return to the pool).
        for lid, rec in list(self.peer_leases.items()):
            if rec[0] == wid:
                self._release_peer_lease_locked(lid, return_worker=False)
            elif rec[3] == wid:
                self._release_peer_lease_locked(lid, return_worker=True)
        parked = self._parked_peer_leases.pop(wid, None)
        if parked:
            for caller, req_id, lease_id in parked:
                self._release_peer_lease_locked(lease_id, return_worker=False)
                self._reply(caller, req_id, True, ("busy",))
        h = self.workers.pop(wid, None)
        if h is None or h.state == "dead":
            return  # duplicate notification (daemon report + conn EOF)
        if wid in self._expected_worker_stops:
            self._expected_worker_stops.discard(wid)
            self.events.emit(
                "INFO", "worker", "worker stopped",
                worker_id=wid, node_id=h.node_id, cause="node_removed",
            )
        else:
            self.metrics["worker_crashes"] += 1
            self.events.emit(
                "WARNING", "worker", "worker died",
                worker_id=wid, node_id=h.node_id,
                cause="oom_kill" if oom else (
                    "env_setup" if env_fail else "crash"
                ),
            )
        h.state = "dead"
        pool = self.idle_pool.get((h.node_id, h.env_key))
        if pool and wid in pool:
            pool.remove(wid)
        if h.actor_id is not None:
            self._on_actor_worker_crash(h, env_fail=env_fail)
            return
        tid = h.current_task
        if tid is None:
            return
        rec = self.tasks.get(tid)
        if rec is None:
            return
        spec = rec.spec
        if rec.cancelled:
            self._fail_task_record(
                rec, wid, TaskCancelledError(spec.name), record_end=False
            )
            return
        if env_fail is not None:
            from ray_tpu.exceptions import RuntimeEnvSetupError

            # Deterministic failure: reinstalling the same broken env on
            # retry would fail identically — no retry budget applies.
            self._fail_task_record(rec, wid, RuntimeEnvSetupError(env_fail))
            return
        if oom is not None:
            from ray_tpu._private import config as _config

            # OOM kills retry on their OWN budget (ray: task_oom_retries) —
            # a memory-pressure victim is not a task bug, and max_retries=0
            # tasks still deserve another placement.
            oom_attempts = getattr(spec, "oom_attempts", 0)
            if oom_attempts < _config.get("task_oom_retries"):
                spec.oom_attempts = oom_attempts + 1
                self._retry_task_record(rec)
                return
            rss, used, limit = oom
            self._fail_task_record(rec, wid, OutOfMemoryError(
                f"task {spec.name}'s worker was killed by the node memory "
                f"monitor (rss={rss >> 20}MiB, node usage {used >> 20}MiB "
                f"> limit {limit >> 20}MiB) after "
                f"{oom_attempts} OOM retries"
            ))
            return
        if spec.attempt < spec.max_retries:
            spec.attempt += 1
            self._retry_task_record(rec)
        else:
            self._fail_task_record(rec, wid, WorkerCrashedError(
                f"worker running task {spec.name} died unexpectedly"
            ))

    @_locked
    def _on_actor_worker_crash(
        self, h: WorkerHandle, env_fail: Optional[str] = None
    ) -> None:
        actor_id = h.actor_id
        ar = self.actors.get(actor_id)
        info = self.state.get_actor(actor_id)
        if ar is None or info is None or info.state == DEAD:
            return
        creation = ar.info.creation_spec
        if env_fail is not None:
            # Runtime-env setup failed for this actor's worker: retrying
            # would reinstall the same broken env — fail the actor NOW with
            # the setup error, not after 3 generic creation retries.
            from ray_tpu.exceptions import RuntimeEnvSetupError

            err = RuntimeEnvSetupError(env_fail)
            self._release_actor_placement(ar)
            self.state.set_actor_state(actor_id, DEAD, death_cause=env_fail)
            rec = self.tasks.pop(creation.task_id, None)
            if rec is not None:
                for oid in rec.spec.return_ids():
                    self.store.put_error(oid, err)
                    self._object_ready(oid)
            self._fail_actor_queue(ar, err)
            return
        crash_retries = getattr(ar, "_creation_crash_retries", 0)
        if (
            info.state in (PENDING_CREATION, RESTARTING)
            and crash_retries < 3
            and not ar.expected_death
            and not ar.no_restart
        ):
            # (expected_death/no_restart: a kill() during init must stay
            # dead, not resurrect through the scheduling-retry path.)
            ar._creation_crash_retries = crash_retries + 1
            # The worker died BEFORE the actor (re)initialized — a
            # scheduling/environment failure (e.g. it was placed on a node
            # whose daemon died in the same instant), not an actor death.
            # Re-schedule the creation without burning max_restarts budget,
            # matching the reference's GCS actor scheduler, which retries
            # placement and only counts ALIVE→dead transitions as restarts
            # (ray: gcs_actor_scheduler.h:111, gcs_actor_manager.h:258-266).
            self.tasks.pop(creation.task_id, None)
            self._release_actor_placement(ar)
            ar.worker_id = None
            rec = TaskRecord(creation)
            rec.state = "READY"
            rec.stamp("queued")
            self.tasks[creation.task_id] = rec
            self.ready_queue.append(creation.task_id)
            self._dispatch()
            return
        self._release_actor_placement(ar)
        err = ActorDiedError(
            f"actor {actor_id} died"
            + (" (killed)" if ar.expected_death else " unexpectedly")
        )
        can_restart = (
            not ar.no_restart
            and not ar.expected_death
            and (
                info.max_restarts == -1 or info.num_restarts < info.max_restarts
            )
        )
        # In-flight relayed calls: retry-budgeted ones re-queue onto the
        # restarted instance (same semantics as the direct path's recovery
        # re-drive; ray: max_task_retries); the rest fail ActorDiedError.
        # in_flight is insertion-ordered (push order == per-caller submit
        # order), so `requeue` comes out in submission order and the
        # extendleft below really does prepend "in order".
        requeue: List[str] = []
        for tid in list(ar.in_flight):
            rec = self.tasks.get(tid)
            if rec is None:
                continue
            if can_restart and rec.spec.attempt < rec.spec.max_retries:
                rec.spec.attempt += 1
                self.metrics["tasks_retried"] += 1
                requeue.append(tid)
                continue
            self.tasks.pop(tid, None)
            for oid in rec.spec.return_ids():
                self.store.put_error(oid, err)
                self._object_ready(oid)
            for c in rec.spec.contained_refs:
                self._decref_local(c)
        ar.in_flight.clear()
        if requeue:
            # Prepend in order: these predate anything already queued.
            ar.queued.extendleft(reversed(requeue))
        if can_restart:
            info.num_restarts += 1
            self.metrics["actor_restarts"] += 1
            self.events.emit(
                "WARNING", "actor", "actor restarting",
                actor_id=actor_id, restart=info.num_restarts,
            )
            self.state.set_actor_state(actor_id, RESTARTING)
            ar.worker_id = None
            # resubmit the creation task (restart FSM:
            # ray: gcs_actor_manager.h:258-266)
            import copy

            new_spec = copy.copy(creation)
            new_spec.task_id = ids.task_id()
            new_spec.attempt = 0
            ar.info.creation_spec = new_spec
            rec = TaskRecord(new_spec)
            rec.state = "READY"
            rec.stamp("queued")
            self.tasks[new_spec.task_id] = rec
            self.ready_queue.append(new_spec.task_id)
            self._dispatch()
        else:
            self.state.set_actor_state(actor_id, DEAD, death_cause="worker died")
            self._fail_actor_queue(ar, err)
            # The released placement may unblock queued work (e.g. a new
            # actor's creation parked on the resources this one held).
            self._dispatch()

    # ------------------------------------------------------------------
    # public API surface (driver side)

    def put(self, value: Any) -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError("ray_tpu.put() does not accept ObjectRefs")
        self.metrics["objects_put"] += 1
        oid = ids.object_id()
        contained = self.store.put(oid, value)
        size = self.store._in_shm.get(oid)
        if size:
            self.object_sizes[oid] = size  # locality scoring weight
        self._note_object(oid, "driver")
        self._obj_event(oid, "create", size)
        self._store_contained(oid, contained)
        self._object_ready(oid)
        return ObjectRef(oid)

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        for r in refs:
            if not isinstance(r, ObjectRef):
                raise TypeError(f"ray_tpu.get() takes ObjectRefs, got {type(r)}")
        oids = [r.id for r in refs]
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        # Flush-before-blocking-wait: task/kill frames this thread queued
        # (local-mode submits run on the caller's thread) must be on the
        # wire before we park on their results.
        _wire.flush_dirty()
        ready = self.store.wait(oids, len(oids), timeout)
        if len(ready) < len(oids):
            # Critical path: name the lifecycle stage each pending
            # producer is stuck in (the attribution plane's one-line
            # diagnosis for a blocked get).
            pending = [o for o in oids if o not in set(ready)]
            detail = self._blocked_get_detail(pending)
            raise GetTimeoutError(
                f"get timed out after {timeout}s"
                + (f"; critical path: {detail}" if detail else "")
            )
        values = [self._get_one_value(oid, deadline) for oid in oids]
        return values[0] if single else values

    def _get_one_value(self, oid: str, deadline: Optional[float]):
        """Fetch + deserialize one ready object; transparently reconstruct
        via lineage when its bytes are lost."""
        import time as _time

        for _ in range(3):  # bound cascading reconstructions per object
            err = self.store.error_for(oid)
            if err is not None:
                raise err
            obj = self.store.get_sealed(oid)
            if obj is None and self._fetch_remote(oid):
                obj = self.store.get_sealed(oid)
            if obj is not None:
                return obj.deserialize()
            with self.lock:
                if not self._reconstruct(oid):
                    raise ObjectLostError(oid)
            remaining = (
                None if deadline is None else max(deadline - _time.monotonic(), 0.0)
            )
            _wire.flush_dirty()  # the reconstruction dispatch just queued
            if not self.store.wait([oid], 1, remaining):
                raise GetTimeoutError(f"reconstruction of {oid} timed out")
        raise ObjectLostError(oid)

    def _fetch_remote(self, oid: str) -> bool:
        """Pull an object whose bytes live only on other nodes into the
        head store (driver-side consumption of remote results —
        ray: PullManager on the requesting raylet).  The sink's transfer
        board makes even this pull relay-servable to other nodes
        mid-flight (the head's listener serves its boards)."""
        from ray_tpu._private import object_plane

        eps = self._pull_endpoints(oid, exclude_head=True)
        if not eps:
            return False
        r = object_plane.pull_from_any(
            eps, self._authkey, oid, self.store.start_pull
        )
        return r is not None

    def wait_refs(self, refs, num_returns=1, timeout=None):
        oids = [r.id for r in refs]
        _wire.flush_dirty()  # same rule as get(): flush before parking
        ready_set = set(self.store.wait(oids, num_returns, timeout))
        ready, not_ready = [], []
        for r in refs:
            (ready if r.id in ready_set and len(ready) < num_returns else not_ready).append(r)
        return ready, not_ready

    def cancel(self, oid_or_ref, force: bool = False) -> None:
        oid = oid_or_ref.id if isinstance(oid_or_ref, ObjectRef) else oid_or_ref
        # object id "o:<task>:<i>" -> task id
        task_id = oid.split(":")[1] if oid.startswith("o:") else None
        if task_id is None:
            return
        with self.lock:
            rec = self.tasks.get(task_id)
            if rec is None:
                return
            rec.cancelled = True
            if rec.state in ("PENDING", "READY"):
                self.tasks.pop(task_id, None)
                for roid in rec.spec.return_ids():
                    self.store.put_error(roid, TaskCancelledError(rec.spec.name))
                    self._object_ready(roid)
            elif rec.state == "RUNNING" and force:
                h = self.workers.get(rec.worker_id)
                if h is not None:
                    self._send(h, ("kill",))
                    try:
                        h.proc.terminate()
                    except Exception:
                        pass

    def kill_actor(self, actor_id: str, no_restart: bool = True) -> None:
        with self.lock:
            ar = self.actors.get(actor_id)
            if ar is None:
                return
            ar.expected_death = True
            ar.no_restart = ar.no_restart or no_restart
            h = self.workers.get(ar.worker_id) if ar.worker_id else None
        if h is not None:
            self._send(h, ("kill",))
            try:
                h.proc.terminate()
            except Exception:
                pass
        else:
            with self.lock:
                info = self.state.get_actor(actor_id)
                if info and info.state != DEAD:
                    self.state.set_actor_state(actor_id, DEAD, death_cause="killed")
                    self._fail_actor_queue(ar, ActorDiedError(actor_id))
                # Cancel the still-pending creation task, else its eventual
                # dispatch would resurrect the actor to ALIVE.
                for tid, rec in list(self.tasks.items()):
                    if (
                        rec.spec.is_actor_creation
                        and rec.spec.actor_id == actor_id
                        and rec.state in ("PENDING", "READY")
                    ):
                        rec.cancelled = True
                        self.tasks.pop(tid, None)
                        for oid in rec.spec.return_ids():
                            self.store.put_error(oid, ActorDiedError(actor_id))
                            self._object_ready(oid)
                        for c in rec.spec.contained_refs:
                            self._decref_local(c)

    # -- placement groups ----------------------------------------------------

    def create_placement_group(
        self, bundles, strategy, name=None, pg_id: Optional[str] = None
    ) -> PlacementGroupInfo:
        """pg_id may be CLIENT-minted so a request retried across a head
        bounce dedupes instead of creating (and leaking the reservations
        of) a second group."""
        with self.lock:
            if pg_id is not None and pg_id in self.state.placement_groups:
                return self.state.placement_groups[pg_id]
        pg = PlacementGroupInfo(
            pg_id=pg_id or ids.placement_group_id(),
            bundles=[{k: float(v) for k, v in b.items()} for b in bundles],
            strategy=strategy,
            name=name,
        )
        with self.lock:
            self.state.register_pg(pg)  # journaled (orig_bundles captured)
            if not self.scheduler.reserve_placement_group(pg):
                self.pending_pgs.append(pg.pg_id)
        return pg

    def remove_placement_group(self, pg_id: str) -> None:
        with self.lock:
            pg = self.state.placement_groups.get(pg_id)
            if pg is not None:
                self.scheduler.remove_placement_group(pg)
                if pg_id in self.pending_pgs:
                    self.pending_pgs.remove(pg_id)

    # -- elastic re-mesh (MESH gangs; SURVEY.md §7: one host's failure
    #    tears/reshapes the whole mesh, unlike independent-worker retry) --

    def pg_info(self, pg_id: str) -> Optional[dict]:
        """Gang introspection for elastic trainers: lifecycle state plus
        the reshape bookkeeping (generation, shrunk size, scale-up cue)."""
        with self.lock:
            pg = self.state.placement_groups.get(pg_id)
            if pg is None:
                return None
            return {
                "state": pg.state,
                "generation": pg.generation,
                "size": len(pg.bundles),
                "orig_size": len(pg.orig_bundles or pg.bundles),
                "bundle_nodes": dict(pg.bundle_nodes),
                "scale_up_ready": pg.scale_up_ready,
                "lost_node": pg.lost_node,
                # Monotonic stamp of the last RESHAPING entry (system-wide
                # clock on Linux): trainers subtract it from their own
                # monotonic "noticed" time to attribute the detect stage.
                "reshaping_since": pg.reshaping_since,
            }

    def _kill_gang_actors(self, pg_id: str) -> int:
        """Caller holds self.lock.  Kill every live actor scheduled inside
        the gang: SPMD collectives span all members, so the survivors of a
        torn mesh are dead weight pinning capacity the re-plan needs —
        and killing them gives the trainer one clean gang-wide
        ActorDiedError instead of a half-alive group."""
        killed = 0
        for aid, ar in list(self.actors.items()):
            placement = ar.placement
            if not placement or placement[0] != "pg" or placement[1] != pg_id:
                continue
            info = self.state.get_actor(aid)
            if info is None or info.state == DEAD:
                continue
            killed += 1
            self.kill_actor(aid, no_restart=True)
        return killed

    def _withdraw_mesh_gangs(self, node_id: str) -> None:
        """Caller holds self.lock.  Node loss: every CREATED MESH gang the
        dead host was a member of is withdrawn as a whole — surviving
        reservations released, gang actors killed — and enters a journaled
        RESHAPING episode.  The io-loop sweep then waits for a replacement
        host up to remesh_wait_s before re-planning a smaller box."""
        from ray_tpu._private import config as _config

        for pg in list(self.state.placement_groups.values()):
            if pg.strategy != "MESH" or pg.state != "CREATED":
                continue
            if node_id not in pg.bundle_nodes.values():
                continue
            if not self.scheduler.withdraw_gang(pg, node_id):
                continue
            wait_s = float(_config.get("remesh_wait_s"))
            self.state.set_pg_state(
                pg.pg_id, "RESHAPING",
                lost_node=node_id, scale_up_ready=False,
                reshape_deadline=time.monotonic() + wait_s,
                reshaping_since=time.monotonic(),
            )
            killed = self._kill_gang_actors(pg.pg_id)
            self.events.emit(
                "WARNING", "pg",
                "MESH gang lost a member host: gang withdrawn, RESHAPING",
                pg_id=pg.pg_id, lost_node=node_id, size=len(pg.bundles),
                actors_killed=killed, wait_s=wait_s,
            )

    def _sweep_reshaping_pgs(self, now: float) -> None:
        """Advance elastic re-mesh episodes (io-loop 0.5s tick).

        Runs OFF the runtime lock: the mesh.member_death / pg.reshape
        fault points below are delay/crash-capable, and every mutation
        step below re-takes the lock and re-checks state first — a racing
        remove_placement_group wins, the sweep never resurrects it.
        """
        from ray_tpu._private import config as _config

        with self.lock:
            reshaping = [
                pg for pg in self.state.placement_groups.values()
                if pg.state == "RESHAPING"
            ]
            shrunk = [
                pg for pg in self.state.placement_groups.values()
                if (
                    pg.state == "CREATED"
                    and pg.strategy == "MESH"
                    and pg.orig_bundles
                    and len(pg.bundles) < len(pg.orig_bundles)
                    and not pg.scale_up_ready
                )
            ]
        for pg in reshaping:
            if faults.ENABLED:
                if pg.pg_id not in self._remesh_announced:
                    self._remesh_announced.add(pg.pg_id)
                    faults.point("mesh.member_death", key=pg.pg_id)
                deadline = pg.reshape_deadline
                faults.point(
                    "pg.reshape",
                    key="shrink"
                    if deadline is not None and now >= deadline
                    else "wait",
                )
            with self.lock:
                if pg.state != "RESHAPING":
                    continue
                if pg.reshape_deadline is None:
                    # Restored mid-episode after a head bounce: the wait
                    # deadline is head-local, re-arm a fresh window.
                    pg.reshape_deadline = now + float(
                        _config.get("remesh_wait_s")
                    )
                # Full size first — a replacement host may have joined.
                ok = self.scheduler.reserve_placement_group(pg)
                did_shrink = False
                if not ok and now >= pg.reshape_deadline and len(pg.bundles) > 1:
                    # Wait window expired: shrink the box by one host
                    # (journaled) and re-plan, demand-revoking idle leases
                    # when fragmentation blocks the smaller box.  Another
                    # window must elapse before shrinking further.
                    self.state.set_pg_state(
                        pg.pg_id, "RESHAPING",
                        bundles=[dict(b) for b in pg.bundles[:-1]],
                        reshape_deadline=now
                        + float(_config.get("remesh_wait_s")),
                    )
                    did_shrink = True
                    ok = self.scheduler.reserve_placement_group(pg)
                    while not ok and self._revoke_one_idle_lease():
                        ok = self.scheduler.reserve_placement_group(pg)
                if ok:
                    self._remesh_announced.discard(pg.pg_id)
                    self.events.emit(
                        "INFO", "pg",
                        "MESH gang re-meshed"
                        + (" at reduced size" if did_shrink else ""),
                        pg_id=pg.pg_id, size=len(pg.bundles),
                        orig_size=len(pg.orig_bundles or pg.bundles),
                        generation=pg.generation,
                    )
                    self._dispatch()
        for pg in shrunk:
            if self.scheduler.can_plan_full(pg):
                with self.lock:
                    if pg.state == "CREATED" and not pg.scale_up_ready:
                        self.state.set_pg_state(
                            pg.pg_id, "CREATED", scale_up_ready=True
                        )
                        self.events.emit(
                            "INFO", "pg",
                            "MESH gang can scale back to full size",
                            pg_id=pg.pg_id, size=len(pg.bundles),
                            orig_size=len(pg.orig_bundles),
                        )

    def pg_reshape(self, pg_id: str) -> bool:
        """Trainer-initiated scale-up of a shrunk MESH gang back to its
        original size: kill the gang, withdraw its reservations, and
        re-enter RESHAPING at full size.  The reservation is attempted
        inline (and by every sweep tick after); the caller polls pg_info
        until generation advances."""
        if faults.ENABLED:
            faults.point("pg.reshape", key="expand")
        from ray_tpu._private import config as _config

        with self.lock:
            pg = self.state.placement_groups.get(pg_id)
            if (
                pg is None
                or pg.state != "CREATED"
                or not pg.orig_bundles
                or len(pg.bundles) >= len(pg.orig_bundles)
            ):
                return False
            self._kill_gang_actors(pg_id)
            self.scheduler.withdraw_gang(pg, dead_node="")
            self.state.set_pg_state(
                pg_id, "RESHAPING",
                bundles=[dict(b) for b in pg.orig_bundles],
                lost_node=None, scale_up_ready=False,
                reshape_deadline=time.monotonic()
                + float(_config.get("remesh_wait_s")),
                reshaping_since=time.monotonic(),
            )
            self.events.emit(
                "INFO", "pg", "MESH gang scale-up: RESHAPING to full size",
                pg_id=pg_id, size=len(pg.bundles),
            )
            if self.scheduler.reserve_placement_group(pg):
                self._remesh_announced.discard(pg_id)
                self._dispatch()
        return True

    # -- cluster info --------------------------------------------------------

    def cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self.state.alive_nodes():
            for k, v in n.resources.items():
                out[k] = out.get(k, 0.0) + v
        return out

    def available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self.state.alive_nodes():
            for k, v in n.available.items():
                out[k] = out.get(k, 0.0) + v
        return out

    # -- virtual nodes (test fixture: ray: python/ray/cluster_utils.py:99) ---

    def add_node(
        self,
        num_cpus: float = 1.0,
        resources: Optional[Dict] = None,
        labels: Optional[Dict[str, str]] = None,
    ) -> str:
        res = {"CPU": float(num_cpus), **(resources or {})}
        nid = ids.node_id()
        self.state.register_node(
            NodeInfo(nid, dict(res), dict(res), labels=dict(labels or {}))
        )
        with self.lock:
            self._dispatch()
        return nid

    def remove_node(self, node_id: str) -> None:
        with self.lock:
            # Planned removal (autoscaler downscale / Cluster API): the
            # ensuing daemon/worker EOFs must log as routine, not failures.
            self._expected_node_removals.add(node_id)
            self.state.remove_node(node_id)
            victims = [h for h in self.workers.values() if h.node_id == node_id]
            self._expected_worker_stops.update(h.worker_id for h in victims)
            if node_id not in self.node_daemons:
                # In-process node (no daemon conn whose EOF would emit the
                # event later) — record the removal now and don't leak the
                # expectation entry.
                self._expected_node_removals.discard(node_id)
                self.events.emit("INFO", "node", "node removed", node_id=node_id)
                if node_id in self.node_lifecycle:
                    self._set_node_lifecycle(
                        node_id, "DEPARTED", reason="removed"
                    )
            self._daemon_send(node_id, ("shutdown",))
            self.node_daemons.pop(node_id, None)
            # Planned or not, a MESH gang member leaving tears the gang.
            self._withdraw_mesh_gangs(node_id)
        for h in victims:
            try:
                h.proc.terminate()
            except Exception:
                pass
        # crash handling happens via conn EOF in the io loop

    # ------------------------------------------------------------------
    # elastic capacity: the loss-proof drain protocol.  DRAINING stops new
    # leases landing (scheduler filters + lease drain-revokes), the
    # reconciler waits for running tasks, sole-copy objects evacuate over
    # the PR-10 transfer plane, and only then does the daemon depart.  A
    # node that dies MID-DRAIN falls into _on_daemon_death unchanged —
    # lineage/retry covers whatever evacuation had not yet moved.

    def start_node_drain(self, node_id: str) -> bool:
        """Enter DRAINING: journaled lifecycle flip + the volatile
        NodeInfo.draining mark, idle leases on the node drain-revoked,
        parked same-key tasks re-driven elsewhere.  Idempotent."""
        if faults.ENABLED:
            faults.point("node.drain", key=node_id)
        with self.lock:
            node = self.state.nodes.get(node_id)
            if (
                node is None
                or not node.alive
                or node.is_head
                or node_id == self.head_node_id
            ):
                return False
            if not node.draining:
                self.state.set_node_draining(node_id, True)
                self._set_node_lifecycle(node_id, "DRAINING")
                for pool in list(self.task_leases.values()):
                    for le in list(pool):
                        if (
                            le.node_id == node_id
                            and le.idle_since is not None
                        ):
                            self._revoke_lease_locked(le, cause="drain")
                self._dispatch()
        return True

    def node_busy_count(self, node_id: str) -> int:
        """Workers on node_id still holding work: running/pushed tasks
        plus resident actors.  0 = quiesced (safe to evacuate+depart)."""
        with self.lock:
            busy = 0
            for h in self.workers.values():
                if h.node_id != node_id or h.state == "dead":
                    continue
                if h.current_task is not None or h.state == "actor":
                    busy += 1
            return busy

    def sole_copy_objects(self, node_id: str) -> List[str]:
        """Objects whose ONLY sealed copy lives on node_id (no head-store
        copy, no other node in the directory) — the bytes a depart would
        lose without evacuation."""
        with self.lock:
            return [
                oid
                for oid, locs in self.object_locations.items()
                if locs == {node_id} and not self.store.has_local(oid)
            ]

    def evacuate_node_objects(
        self, node_id: str, deadline: Optional[float] = None
    ) -> dict:
        """Pull every sole-copy object off node_id into the head store
        over the transfer plane (the head is a surviving node; its store
        re-serves the bytes to any later consumer).  Runs OFF the runtime
        lock — each pull is a network transfer.  Returns the evacuation
        ledger; `remaining` > 0 means bytes were NOT saved (deadline hit
        or the node died under us) and the caller decides whether to
        depart anyway (lineage then covers the loss)."""
        moved = failed = 0
        moved_bytes = 0
        for oid in self.sole_copy_objects(node_id):
            if deadline is not None and time.monotonic() > deadline:
                break
            if faults.ENABLED:
                faults.point("node.evacuate", key=oid)
            ok = False
            try:
                ok = self._fetch_remote(oid)
            except Exception:
                ok = False
            if ok and self.store.has_local(oid):
                moved += 1
                moved_bytes += self.object_sizes.get(oid, 0)
            else:
                failed += 1
        remaining = len(self.sole_copy_objects(node_id))
        if moved or failed or remaining:
            self.events.emit(
                "INFO" if remaining == 0 else "WARNING",
                "autoscale", "node evacuation",
                node_id=node_id, moved=moved, moved_bytes=moved_bytes,
                failed=failed, remaining=remaining,
            )
        return {
            "moved": moved,
            "moved_bytes": moved_bytes,
            "failed": failed,
            "remaining": remaining,
        }

    def depart_node(self, node_id: str) -> None:
        """Final drain step: planned removal (remove_node) + the terminal
        DEPARTED lifecycle record.  Workers still running tasks here die
        as EXPECTED stops — their in-flight tasks re-drive on their retry
        budget, same as any worker death."""
        if faults.ENABLED:
            faults.point("node.depart", key=node_id)
        self.remove_node(node_id)
        with self.lock:
            if node_id in self.node_lifecycle:
                self._set_node_lifecycle(
                    node_id, "DEPARTED", reason="removed"
                )

    # ------------------------------------------------------------------

    def shutdown(self) -> None:
        if self._shutdown:
            return
        self._shutdown = True
        from ray_tpu.util import tracing

        # Lifecycle spans (util/tracing.py, always recorded): the inside
        # twin of a caller's clock around `ray_tpu.shutdown()`, one child per
        # stage that can take over 0.1 s.
        with tracing.span(
            "runtime::shutdown", parent={"trace_id": self.trace_id}, lifecycle=True
        ):
            self._shutdown_stages(tracing)
        global _runtime
        _runtime = None

    def _shutdown_stages(self, tracing) -> None:
        atexit.unregister(self.shutdown)
        set_ref_hooks(None, None)
        with tracing.span("runtime::shutdown::services", lifecycle=True):
            if self._autoscaler is not None:
                try:
                    self._autoscaler.stop()
                except Exception:
                    pass
            if getattr(self, "_snapshot_storage", None) is not None:
                self._snapshot_storage.close()
            if getattr(self, "_journal", None) is not None:
                self._journal.close()
            if getattr(self, "_ready_spill", None) is not None:
                self._ready_spill.close()
            if getattr(self, "_mem_monitor", None) is not None:
                self._mem_monitor.stop()
        # Final log drain: crash output written moments ago must reach the
        # ring buffers/stdout before the session dies.
        with tracing.span("runtime::shutdown::log_drain", lifecycle=True):
            try:
                self._log_monitor.flush()
                self._log_monitor.stop()
            except Exception:
                pass
            try:
                if _wire.stats_enabled():
                    # Final per-process counters into the event log (workers'
                    # snapshots were folded in live via their wire_stats
                    # reports — see _handle_msg).
                    self.events.emit(
                        "INFO", "wire", "head wire stats", **_wire.stats()
                    )
                self.events.emit("INFO", "runtime", "session shutting down")
                self.events.close()
            except Exception:
                pass
        with tracing.span("runtime::shutdown::signal_processes", lifecycle=True):
            for nid in list(self.node_daemons):
                self._daemon_send(nid, ("shutdown",))
            for proc in self._daemon_procs.values():
                try:
                    proc.terminate()
                except OSError:
                    pass
            if self._zygote_proc is not None:
                try:
                    self._zygote_proc.terminate()
                except OSError:
                    pass
            for h in list(self.workers.values()):
                try:
                    if h.conn is not None:
                        h.conn.send(("kill",))
                except OSError:
                    pass
                try:
                    h.proc.terminate()
                except Exception:
                    pass
            # The kill/shutdown frames above are queued on batching conns:
            # push them out before the fds die with the process.
            _wire.flush_dirty()
            try:
                self.listener.close()
            except OSError:
                pass
        with tracing.span("runtime::shutdown::workers_exit", lifecycle=True):
            deadline = time.monotonic() + 2.0
            for h in list(self.workers.values()):
                remaining = max(0.0, deadline - time.monotonic())
                try:
                    h.proc.join(remaining)
                except Exception:
                    pass
            # A worker that held TPU chips releases them while it EXITS (a
            # reset per chip, its pinned host memory): seconds on a four-chip
            # host, during which the next process to open the chips fails
            # with "Device or resource busy".  So shutdown returns when this
            # host's workers are gone, not when they were told to go.
            deadline = time.monotonic() + 30.0
            for h in list(self.workers.values()):
                if isinstance(h.proc, (_PopenHandle, _ZygoteProcHandle)):
                    while not _exited(h.proc.pid) and time.monotonic() < deadline:
                        time.sleep(0.05)
        with tracing.span("runtime::shutdown::store", lifecycle=True):
            self.store.destroy()


_PARKED = object()
_runtime: Optional[Runtime] = None


def get_runtime() -> Runtime:
    if _runtime is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _runtime


def is_initialized() -> bool:
    return _runtime is not None


def init_runtime(**kwargs) -> Runtime:
    global _runtime
    if _runtime is not None:
        return _runtime
    _runtime = Runtime(**kwargs)
    return _runtime


def shutdown_runtime() -> None:
    global _runtime
    if _runtime is not None:
        rt = _runtime
        _runtime = None
        rt.shutdown()
