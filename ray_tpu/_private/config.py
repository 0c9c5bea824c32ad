"""Runtime config knobs: one table, env-var overridable.

ray: src/ray/common/ray_config_def.h (the RAY_CONFIG X-macro table — every
runtime knob declared once, overridable via RAY_<name> env vars) +
python/ray/_private/ray_constants.py.  Same shape here: each knob is a row
with a default and docstring; `RAY_TPU_<NAME>` env vars override at first
access; `_system_config` overrides at init beat both.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict

_DEFS: Dict[str, tuple] = {
    # name: (default, type, doc)
    "scheduler_spread_threshold": (
        0.5, float,
        "hybrid policy: head-node utilization above which tasks spill to "
        "the least-utilized remote node (ray: RAY_scheduler_spread_threshold)",
    ),
    "max_direct_call_object_size": (
        100 * 1024, int,
        "results >= this many bytes go to the shm store; smaller inline "
        "over the control conn (ray: max_direct_call_object_size)",
    ),
    "object_store_memory": (
        0, int,
        "shm store capacity in bytes; 0 = 30% of the shm filesystem's free "
        "space at init (ray: object_store_memory)",
    ),
    "lineage_max_entries": (
        10000, int,
        "max producer TaskSpecs retained for object reconstruction",
    ),
    "lineage_max_bytes": (
        64 * 1024 * 1024, int,
        "max bytes of retained args blobs in the lineage table "
        "(ray: max_lineage_bytes spirit, task_manager.h:97)",
    ),
    "task_events_max": (
        2000, int,
        "ring-buffer size of the finished-task event sink "
        "(ray: task_events_max_num_task_in_gcs)",
    ),
    "worker_prestart_count": (
        8, int,
        "warm worker-pool size prestarted at init (capped by node CPUs; "
        "ray: worker pool prestart)",
    ),
    "use_zygote": (
        1, int,
        "1 = spawn local workers by forking the pre-warmed zygote "
        "(~2ms); 0 = exec a fresh interpreter per worker (zygote.py)",
    ),
    "worker_handshake_timeout_s": (
        60.0, float,
        "a spawned worker that hasn't connected within this window dies "
        "via its own watchdog",
    ),
    "spill_storage_uri": (
        "", str,
        "external spill target URI (file:// native; s3://gs:// via fsspec "
        "when installed); empty = session-local spill directory "
        "(ray: external_storage.py:185)",
    ),
    "native_store": (
        1, int,
        "1 = use the C++ shm arena when it builds; 0 = file-per-object",
    ),
    "bind_host": (
        "127.0.0.1", str,
        "driver listener bind address; 0.0.0.0 exposes it to node daemons "
        "on other machines",
    ),
    "object_transfer_chunk_bytes": (
        8 * 1024 * 1024, int,
        "chunk size for cross-node object pulls "
        "(ray: object_manager_default_chunk_size)",
    ),
    "gcs_storage_backend": (
        "file", str,
        "control-plane snapshot backend: 'file' (atomic single file) or "
        "'sqlite' (WAL-journaled, crash-safe) "
        "(ray: gcs store_client in-memory vs redis backends)",
    ),
    "gcs_journal": (
        1, int,
        "1 = append-only mutation journal between snapshot ticks (actor "
        "register/restart/death, named bindings, job transitions, inline-"
        "result lineage), replayed over the snapshot at head restart; "
        "0 = snapshot-only durability (up to one tick of mutations lost) "
        "(ray: the GCS writes each table mutation through its store "
        "client instead of snapshotting)",
    ),
    "gcs_journal_fsync": (
        0, int,
        "journal append durability: 0 = write+flush only (survives "
        "process SIGKILL via the page cache — the chaos-soak envelope), "
        "1 = fsync every append (survives host power loss), N>1 = fsync "
        "every N-th append (bounded-loss middle ground)",
    ),
    "gcs_journal_compact_bytes": (
        4 * 1024 * 1024, int,
        "journal size that forces an immediate snapshot (which folds the "
        "journal in and resets it) instead of waiting for the next tick",
    ),
    "snapshot_inflight_max_blob_bytes": (
        256 * 1024, int,
        "in-flight tasks with args blobs over this size are not persisted "
        "for head-restart re-drive (their argument objects would not "
        "survive the head's store anyway)",
    ),
    "snapshot_inflight_max_tasks": (
        10000, int,
        "cap on in-flight task specs persisted per snapshot tick",
    ),
    "locality_min_bytes": (
        1024 * 1024, int,
        "dependency-locality scoring floor: tasks whose LARGEST per-node "
        "local dep footprint is under this many bytes schedule by load "
        "alone (pulling tiny args costs less than imbalance)",
    ),
    "serve_proxy_max_connections": (
        2048, int,
        "max concurrent HTTP connections one serve proxy holds open; "
        "connections beyond the bound are refused at accept "
        "(ray: uvicorn's backlog/limit-concurrency role)",
    ),
    "serve_proxy_threads": (
        32, int,
        "executor threads one serve proxy uses to resolve replica "
        "responses; bounds active requests while idle keep-alive "
        "connections cost only a coroutine",
    ),
    "object_transfer_max_concurrency": (
        8, int,
        "max concurrent outbound transfers an object server runs; excess "
        "fetches queue (ray: object_manager_max_bytes_in_flight spirit)",
    ),
    "object_transfer_timeout_s": (
        120.0, float,
        "bound on every blocking step of a cross-node object pull "
        "(connect, header, each chunk) — a wedged server fails the fetch "
        "instead of hanging the get (ray: pull retry timer spirit)",
    ),
    "relay_pipeline": (
        1, int,
        "1 = broadcast pulls get a pipelined transfer plan: in-flight "
        "pullers re-serve landed chunks mid-transfer (chain/tree "
        "broadcast, ray: push_manager.h chunk pipelining); 0 = classic "
        "staggered whole-object rounds (grants capped at sealed copies)",
    ),
    "relay_fanout": (
        2, int,
        "max concurrent downstream pullers one feed (sealed source OR "
        "in-flight relay) serves in a transfer plan; each admitted "
        "puller immediately becomes a feed itself, so admission capacity "
        "grows with the tree instead of with completed rounds",
    ),
    "relay_stall_timeout_s": (
        10.0, float,
        "relay liveness bound, both sides: a relay server whose upstream "
        "watermark stops advancing closes the conn after this long, and "
        "a receiver whose relay feed goes silent fails the fetch and "
        "falls back to a sealed source (re-plan, not wedge)",
    ),
    "node_ip": (
        "127.0.0.1", str,
        "address this node's object server advertises to other nodes "
        "(set RAY_TPU_NODE_IP per host in real multi-host deployments)",
    ),
    "reconnect_window_s": (
        0.0, float,
        "how long daemons/workers retry connecting after losing the head "
        "conn before giving up and exiting; 0 = die on EOF (classic mode). "
        "The standalone head sets this for its cluster so a head restart "
        "is survivable (ray: gcs_rpc_server_reconnect_timeout_s)",
    ),
    "log_to_driver": (
        1, int,
        "1 = echo worker stdout/stderr lines (prefixed) to the driver/head "
        "process stdout as they arrive; 0 = files + ring buffers only "
        "(ray: ray.init(log_to_driver=...))",
    ),
    "worker_log_ring_lines": (
        2000, int,
        "per-worker ring buffer of recent log lines kept for the logs "
        "CLI / dashboard endpoint",
    ),
    "health_check_period_ms": (
        1000, int,
        "how often node daemons send liveness heartbeats to the head "
        "(ray: health_check_period_ms, gcs_health_check_manager.h:39)",
    ),
    "health_check_timeout_ms": (
        10000, int,
        "no heartbeat for this long => the node is declared dead even "
        "with its TCP conn still open (hung daemon / half-open conn); "
        "0 disables timeout-based death (EOF only)",
    ),
    "memory_monitor_refresh_ms": (
        250, int,
        "how often each node daemon checks memory pressure; 0 disables "
        "the OOM monitor (ray: memory_monitor_refresh_ms)",
    ),
    "memory_usage_threshold": (
        0.95, float,
        "usage fraction above which the daemon kills a worker "
        "(ray: memory_usage_threshold)",
    ),
    "memory_limit_bytes": (
        0, int,
        "per-node worker-group RSS budget; 0 = account whole-system "
        "memory from /proc/meminfo instead (the deployment default)",
    ),
    "task_oom_retries": (
        3, int,
        "extra retry budget for tasks whose worker was OOM-killed, "
        "separate from max_retries (ray: task_oom_retries)",
    ),
    "oom_worker_killing_policy": (
        "largest", str,
        "victim choice under memory pressure: 'largest' RSS (finds the "
        "actual hog — prestarted idle workers are never bigger) or "
        "'newest' spawned (ray: worker_killing_policy.h)",
    ),
    "wire_batch_bytes": (
        64 * 1024, int,
        "control-plane frame coalescing: pending bytes at which a "
        "BatchingConn flushes (one physical write per batch); 0 disables "
        "batching entirely (every frame is its own write — the unbatched "
        "comparison baseline; ray: gRPC stream buffering plays this role)",
    ),
    "wire_guard": (
        1, int,
        "1 = bounds-check native frame bodies before marshal.loads "
        "(every declared string length / container count must fit the "
        "bytes present, cumulative allocation capped at O(body)) — a "
        "corrupted or hostile 11-byte body can otherwise make the "
        "decoder pre-allocate gigabytes; costs a few µs per native "
        "frame; 0 trusts the fabric and decodes unguarded",
    ),
    "wire_flush_us": (
        200, int,
        "linger bound on a pending control-frame batch: the background "
        "flusher sweeps dirty conns after this many microseconds, so "
        "fire-and-forget frames never wait longer than ~this (blocking "
        "paths flush explicitly and never wait at all)",
    ),
    "wire_native": (
        1, int,
        "1 = encode the hot control-frame kinds (task push, done, refop, "
        "metrics/refs/prof pushes) with the struct-framed "
        "native codec (wire_native.py: marshal data tuples, no pickle, "
        "~14x cheaper per TaskSpec); 0 = pickle every frame (the v2 "
        "behavior).  Negotiated by the protocol-version fence; kinds "
        "without a native codec fall back to pickle per frame either way",
    ),
    "lease_pipeline_depth": (
        0, int,
        "caller-side direct transport: unacked tasks one worker lease "
        "pipelines before another worker is leased; 0 = auto "
        "(max(4, 64/cpus) — deep pipelining onto few executors wins on "
        "small hosts, fan-out wins on many-core; resolved at process "
        "start)",
    ),
    "lease_max_per_key": (
        0, int,
        "caller-side direct transport: max worker leases one scheduling "
        "key holds; 0 = auto (min(8, cpus), floor 1; resolved at process "
        "start)",
    ),
    "task_lease_idle_s": (
        2.0, float,
        "head-side lease reuse: how long a worker leased to a scheduling "
        "key (fn + resource shape + strategy) stays bound after its last "
        "same-key task before the lease is revoked and the worker "
        "returns to the shared pool (ray: "
        "worker_lease_timeout_milliseconds + direct_task_transport.h:40 "
        "lease reuse keyed by SchedulingKey)",
    ),
    "gcs_journal_flush_us": (
        500, int,
        "journal group-commit linger: mutation entries accumulate for up "
        "to this many microseconds (or _BATCH_BYTES) and flush as ONE "
        "buffered write — the BatchingConn size/linger discipline applied "
        "to the journal file.  0 = write-per-append (the pre-batching "
        "behavior); a SIGKILL can lose at most the unflushed window, the "
        "same contract wire linger has",
    ),
    "gcs_journal_batch_bytes": (
        64 * 1024, int,
        "journal group-commit size trigger: pending entry bytes at which "
        "the batch flushes immediately instead of waiting for the linger",
    ),
    "ready_queue_spill_after": (
        100000, int,
        "head ready-queue backlog (tasks) beyond which newly-submitted "
        "dependency-free plain tasks spill their specs to a disk segment "
        "next to the GCS snapshot instead of living in head memory; "
        "reloaded in dispatch-order chunks as the backlog drains.  Bounds "
        "head RSS under a 1M-task backlog (the reference absorbs the same "
        "backlog through its distributed raylet queues); 0 disables "
        "spilling",
    ),
    "wire_stats": (
        0, int,
        "1 = expose per-process wire counters (logical frames, physical "
        "writes, bytes, flush-reason histogram) through the state API / "
        "dashboard, emit them as a cluster event at shutdown, and have "
        "workers report theirs to the head (counting itself is always on)",
    ),
    "fault_spec": (
        "", str,
        "deterministic fault-injection plan (faults.py grammar: "
        "'<point>:<action>[@sel,...];...'); empty = injection disabled "
        "(zero-overhead fast path; ray: RayConfig testing knobs like "
        "testing_asio_delay_us)",
    ),
    "fault_seed": (
        0, int,
        "seed for the fault plan's prob= selectors — the same spec+seed "
        "replays the same injection schedule (print it on failure, rerun "
        "to reproduce)",
    ),
    "metrics_push_ms": (
        1000, int,
        "how often every process (workers, daemons, attached drivers, the "
        "head itself) snapshots its util/metrics registry + wire counters "
        "and ships it to the head as a droppable oneway riding the v2 "
        "batch frames; 0 disables the push (ray: "
        "metrics_report_interval_ms, the OpenCensus export tick)",
    ),
    "telemetry_ring_samples": (
        360, int,
        "head-side bound on each aggregated metric's time series ring "
        "(samples retained at the push period — 360 x 1s = 6 minutes; "
        "ray: the GcsTaskManager ring-storage idiom applied to metrics)",
    ),
    "flight_ring_size": (
        512, int,
        "per-process flight-recorder ring: recent telemetry events "
        "(spans, metric-push deltas, fault injections, cluster events) "
        "retained in memory for a crash dump",
    ),
    "flight_dir": (
        "", str,
        "directory flight-recorder rings dump to (per-pid JSONL files) on "
        "crash, lock-watchdog report, or fault-plane kill; empty disables "
        "dumping (the ring still records)",
    ),
    "refs_push": (
        1, int,
        "1 = every worker/driver ships its live ObjectRef table (oid, "
        "count, creation site) to the head's object ledger each telemetry "
        "tick as a droppable refs_push oneway (requires metrics_push_ms "
        "> 0); 0 disables the ref-table leg only (ray: the per-worker "
        "ReferenceCounter tables `ray memory` joins, reference_count.h:61)",
    ),
    "ref_callsite": (
        0, int,
        "1 = capture the creation site (first non-ray_tpu stack frame) of "
        "every ObjectRef into the live-ref table, enabling `ray_tpu memory "
        "--group-by callsite`; off by default — a frame walk per ref on "
        "the hot path (ray: RAY_record_ref_creation_sites)",
    ),
    "leak_reclaim_grace_s": (
        3.0, float,
        "how long a crashed process's outstanding ref borrows stay as "
        "attributed LEAK SUSPECTS in the object ledger before the head "
        "reclaims them (decref + free); the window in which `ray_tpu "
        "memory --leaks` can attribute leaked bytes to the dead holder's "
        "node/pid",
    ),
    "leak_orphan_reclaim_s": (
        20.0, float,
        "how long a NO-LIVE-HOLDER leak suspect (located ready bytes at "
        "refcount 0 that no live process's ref table claims) must stay "
        "flagged across ledger ticks before the head frees it (0 = never "
        "auto-free).  Covers the head-bounce retention gap: a re-driven "
        "task's result seals at refcount 0 on the restarted head, and a "
        "driver that already dropped its ref can never free it — each "
        "reclaim is a WARNING event, visible, not papered over",
    ),
    "leak_age_s": (
        10.0, float,
        "minimum object age before located bytes with refcount 0 and no "
        "live holder count as a leak suspect (younger objects are in the "
        "legitimate seal-to-first-addref window)",
    ),
    "object_events_max": (
        4096, int,
        "bound on the head's object lifecycle event ring (create/seal/"
        "transfer/spill/restore/free records merged into the chrome "
        "timeline)",
    ),
    "zygote_fork_grace_s": (
        20.0, float,
        "how long a zygote-forked worker handle with no pid attribution "
        "yet reads alive before the reaper declares the fork lost and "
        "reschedules its lease",
    ),
    "actor_adopt_grace_s": (
        5.0, float,
        "after a head restart, how long restored detached/named actors "
        "wait for their live worker to reconnect (state preserved) before "
        "being respawned from their creation spec (state reset)",
    ),
    "prof_hz": (
        0.0, float,
        "sampling-profiler autostart rate: every process starts its "
        "sys._current_frames() sampler at this many Hz at entry "
        "(profiler.py; the chaos soak's always-hot mode).  0 = off — the "
        "zero-overhead default; `ray_tpu profile` still starts sampling "
        "cluster-wide on demand via a pubsub broadcast "
        "(ray: the dashboard's py-spy attach plays this role)",
    ),
    "timeline_last_s": (
        0.0, float,
        "default window for the chrome-trace timeline export: only "
        "events/spans newer than this many seconds are emitted (0 = "
        "everything the rings hold); `ray_tpu timeline --last/--since` "
        "override per call",
    ),
    "remesh_wait_s": (
        30.0, float,
        "elastic MESH gangs: after a member host dies, how long the "
        "reshape sweep waits for a replacement host before re-planning a "
        "smaller contiguous box at N-1 (wait-vs-shrink policy; 0 = shrink "
        "immediately)",
    ),
    "autoscale_enabled": (
        0, int,
        "1 = the head attaches the demand-driven autoscaler "
        "(_private/autoscaler.py) at boot: a reconcile loop grows the "
        "node fleet toward unmet demand and drains idle nodes back to "
        "the floor; infeasible tasks PARK instead of erroring while it "
        "is on (the fleet may grow to fit them)",
    ),
    "autoscale_interval_s": (
        0.5, float,
        "autoscaler reconcile period: how often demand is compared "
        "against the fleet (each tick runs OFF the runtime lock)",
    ),
    "autoscale_min_nodes": (
        0, int,
        "autoscaler floor: provider-managed worker nodes are never "
        "drained below this count (the head node is not counted)",
    ),
    "autoscale_max_nodes": (
        4, int,
        "autoscaler ceiling: at most this many provider-managed worker "
        "nodes exist at once, however deep the unmet demand",
    ),
    "autoscale_up_wait_s": (
        1.0, float,
        "launch hysteresis: demand must stay unmet this long before a "
        "node launch — a burst the current fleet absorbs within the "
        "window never scales up",
    ),
    "autoscale_idle_s": (
        10.0, float,
        "drain hysteresis: a provider-managed node must sit fully idle "
        "(no running tasks, no actors, no held leases) this long before "
        "the autoscaler starts draining it",
    ),
    "autoscale_launch_timeout_s": (
        30.0, float,
        "a REQUESTED/STARTING node that has not registered within this "
        "window is declared failed: its process is terminated and the "
        "slot retried",
    ),
    "autoscale_drain_timeout_s": (
        30.0, float,
        "drain patience: how long a DRAINING node may wait for its "
        "running tasks to finish before the daemon departs anyway (the "
        "in-flight tasks then re-drive on their retry budget, exactly "
        "like a node death)",
    ),
}

# Back-compat env names from before the knob table existed, plus the
# short spellings the docs use for the fast-path knobs.
_ENV_ALIASES: Dict[str, tuple] = {
    "lineage_max_entries": ("RAY_TPU_LINEAGE_MAX",),
    "lineage_max_bytes": ("RAY_TPU_LINEAGE_MAX_BYTES",),
    "task_lease_idle_s": ("RAY_TPU_LEASE_IDLE_S",),
    "gcs_journal_flush_us": ("RAY_TPU_JOURNAL_FLUSH_US",),
    "gcs_journal_batch_bytes": ("RAY_TPU_JOURNAL_BATCH_BYTES",),
}

# Process-wiring environment variables: NOT knobs.  These carry bootstrap
# plumbing between processes (spawn-time identity, fds, endpoints) or are
# read before the config table can be imported (early-boot toggles), so
# they are accessed directly via os.environ rather than config.get().
# Declared here so the knob-registry lint can tell a deliberate wiring
# access from a typo'd knob name (which silently no-ops).  Adding an env
# var that is neither a knob nor declared here fails the lint.
WIRING_ENV: Dict[str, str] = {
    # spawn-time identity / topology (parent -> child)
    "RAY_TPU_DRIVER_HOST": "head endpoint host handed to spawned processes",
    "RAY_TPU_DRIVER_PORT": "head endpoint port handed to spawned processes",
    "RAY_TPU_AUTHKEY": "hex cluster authkey handed to spawned processes",
    "RAY_TPU_SESSION": "session id handed to spawned processes",
    "RAY_TPU_WORKER_ID": "this worker's id (set by the spawning daemon)",
    "RAY_TPU_NODE_ID": "this node's id (set by the spawning daemon)",
    "RAY_TPU_NODE_CONFIG": "JSON node spec for a starting node daemon",
    "RAY_TPU_HEAD_CONFIG": "JSON head spec for `ray_tpu head` boot",
    "RAY_TPU_PEER_HOST": "host the worker's direct-call listener binds",
    "RAY_TPU_HOST_IP": "this host's routable IP (parallel bootstrap)",
    "RAY_TPU_STORE_DIR": "shm store directory handed to spawned processes",
    "RAY_TPU_RUNTIME_ENV": "JSON runtime_env applied at worker boot",
    "RAY_TPU_ENV_VARS": "JSON extra env vars applied at worker boot",
    # inherited descriptors (SCM_RIGHTS / fork plumbing)
    "RAY_TPU_ZYGOTE_FD": "inherited zygote control-pipe fd number",
    "RAY_TPU_ARENA_FD": "inherited shm arena fd number",
    # early-boot / dev toggles read before config import is safe
    "RAY_TPU_TRACE": "1 = record per-task and per-step spans (util/tracing.py)",
    "RAY_TPU_DEBUG_LOCKS": "1 = slow-lock diagnostics in the runtime",
    "RAY_TPU_FAULTHANDLER": "1 = arm faulthandler in spawned workers",
    "RAY_TPU_PDEATHSIG": "0 = skip parent-death signal on Linux children",
    "RAY_TPU_CHIPS": "override detected accelerator chip count",
    "RAY_TPU_LOCK_WATCHDOG": "1 = swap hot locks for instrumented wrappers",
    "RAY_TPU_LOCK_HOLD_S": "lock-watchdog long-hold threshold (seconds)",
    "RAY_TPU_LOCK_WATCHDOG_DIR": "per-pid lock-watchdog report directory",
    # cache locations
    "RAY_TPU_NATIVE_CACHE": "build cache dir for the native arena module",
    "RAY_TPU_PKG_CACHE": "download cache dir for runtime_env packages",
    # bench plumbing
    "RAY_TPU_PERF_PERSIST": "keep ray_perf scratch dirs for inspection",
}

_lock = threading.Lock()
_values: Dict[str, Any] = {}
_frozen_overrides: Dict[str, Any] = {}


def set_system_config(overrides: Dict[str, Any]) -> None:
    """Programmatic overrides (ray: ray.init(_system_config=...)); applied
    before first access wins over env vars."""
    unknown = set(overrides) - set(_DEFS)
    if unknown:
        raise ValueError(f"unknown config keys {sorted(unknown)}; valid: {sorted(_DEFS)}")
    coerced = {}
    for k, v in overrides.items():
        typ = _DEFS[k][1]
        try:
            coerced[k] = typ(v)
        except (TypeError, ValueError) as e:
            # fail HERE at the init() call site, not later inside Runtime
            raise ValueError(f"config {k!r} expects {typ.__name__}, got {v!r}") from e
    with _lock:
        _frozen_overrides.update(coerced)
        for k, v in coerced.items():
            _values.pop(k, None)  # recompute on next access
            # Children (workers/daemons) inherit os.environ, not this
            # in-process table: export the env form so worker-side knobs
            # (handshake timeout, inline threshold, native store) actually
            # take effect there.
            os.environ[f"RAY_TPU_{k.upper()}"] = str(v)


def get(name: str):
    """Resolve a knob: _system_config > RAY_TPU_<NAME> env > default."""
    try:
        default, typ, _doc = _DEFS[name]
    except KeyError:
        raise KeyError(f"unknown config {name!r}; valid: {sorted(_DEFS)}")
    # Lock-free fast path (GIL-atomic dict read): get() sits on hot paths
    # like per-result inline_threshold checks.
    try:
        return _values[name]
    except KeyError:
        pass
    with _lock:
        if name in _values:
            return _values[name]
        if name in _frozen_overrides:
            val = _frozen_overrides[name]
        else:
            env = os.environ.get(f"RAY_TPU_{name.upper()}")
            if env is None:
                for alias in _ENV_ALIASES.get(name, ()):
                    env = os.environ.get(alias)
                    if env is not None:
                        break
            if env is not None:
                try:
                    val = typ(env)
                except ValueError:
                    val = default
            else:
                val = default
        _values[name] = val
        return val


def describe() -> Dict[str, Dict[str, Any]]:
    """Every knob with default, current value, and doc (ray: the config
    dump the dashboard shows)."""
    return {
        name: {"default": d, "value": get(name), "doc": doc}
        for name, (d, _t, doc) in _DEFS.items()
    }


def _reset_for_tests() -> None:
    with _lock:
        _values.clear()
        _frozen_overrides.clear()
