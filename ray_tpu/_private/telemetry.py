"""Cluster telemetry plane: pushed metrics, head-side aggregation, and a
per-process crash flight recorder.

ray: the reference's observability layer is three pipelines — per-worker
TaskEventBuffer batches task state transitions into a GCS-side ring buffer
(gcs_task_manager.h:61), OpenCensus stats export to Prometheus through the
metrics agent (metrics_agent.py:375), and per-component event files
(src/ray/util/event.h).  This module is that layer for this build:

  * PUSH — every process snapshots its util/metrics registry plus its
    wire counters on a period (RAY_TPU_METRICS_PUSH_MS) and ships the
    snapshot to the head as a DROPPABLE oneway riding the v2 batch
    frames: telemetry never competes with ownership traffic (seals,
    refops) for the reconnect backlog, and a dead conn just loses a tick;
  * SINK — the head keeps the latest snapshot per process and folds them
    into bounded ring-buffer time series (the GcsTaskManager ring-storage
    idiom applied to metrics), exposed through util/state.py, the
    dashboard's Prometheus endpoint, and the `ray_tpu metrics` /
    `ray_tpu status` CLI verbs;
  * FLIGHT RECORDER — a bounded in-process ring of recent telemetry
    events (spans, metric-push deltas, fault injections, cluster events)
    in EVERY process, dumped to per-pid JSONL files under
    RAY_TPU_FLIGHT_DIR on an uncaught exception, a lock-watchdog report,
    or a fault-plane `crash` kill — so a chaos-soak death is diagnosable
    from what the process saw in its last seconds, without a replay.

The ring always records (a deque append per event, at flush/tick
granularity — not per task); only the DUMP is gated on the dir knob.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

_ring_lock = threading.Lock()
_ring: Optional[deque] = None
_ring_pid = os.getpid()
_proc_tag = "main"
_installed = False
_dump_seq = 0


def _get_ring() -> deque:
    """Ring, lazily sized from config (and re-created after a fork: the
    parent's entries describe the parent's life, not this process's)."""
    global _ring, _ring_pid
    with _ring_lock:
        if _ring is None or _ring_pid != os.getpid():
            from ray_tpu._private import config as _config

            _ring = deque(maxlen=max(_config.get("flight_ring_size"), 16))
            _ring_pid = os.getpid()
        return _ring


def note(kind: str, **fields: Any) -> None:
    """Record one flight-recorder event.  Never raises — observability
    must not take the process down."""
    try:
        ev = {"t": time.time(), "kind": kind}
        ev.update(fields)
        ring = _get_ring()
        with _ring_lock:
            ring.append(ev)
    except Exception:
        pass


def flight_dir() -> str:
    from ray_tpu._private import config as _config

    return _config.get("flight_dir")


def flight_dump(reason: str) -> Optional[str]:
    """Dump the ring to a per-pid JSONL file under the flight dir (one
    file per process, appended: a process that trips twice keeps both
    dumps).  Returns the path, or None when dumping is disabled/fails.
    Called from crash paths — must never raise and must stay signal-lean
    (plain open/write, no locks beyond the ring's)."""
    global _dump_seq
    d = flight_dir()
    if not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        with _ring_lock:
            events = list(_ring or ())
        # The profiler's top stacks ride every dump: a chaos-killed
        # process records not just what it did but where its time went
        # (None when nothing was sampled — dumping must never block on
        # or require the profiler).
        prof = None
        try:
            from ray_tpu._private import profiler as _profiler

            prof = _profiler.flight_snapshot()
        except Exception:
            prof = None
        _dump_seq += 1
        path = os.path.join(d, f"flight-{os.getpid()}.jsonl")
        with open(path, "a") as f:
            f.write(
                json.dumps(
                    {
                        "kind": "dump",
                        "reason": reason,
                        "pid": os.getpid(),
                        "proc": _proc_tag,
                        "t": time.time(),
                        "seq": _dump_seq,
                        "events": len(events),
                        "prof_stacks": len(prof) if prof else 0,
                    }
                )
                + "\n"
            )
            for ev in events:
                f.write(json.dumps(ev, default=str) + "\n")
            if prof:
                f.write(
                    json.dumps(
                        {
                            "kind": "prof_snapshot",
                            "t": time.time(),
                            "stacks": [[s, n] for s, n in prof],
                        }
                    )
                    + "\n"
                )
        return path
    except Exception:
        return None


def collect_dumps(d: str) -> List[Dict[str, Any]]:
    """Every dump header written by any process into dir `d` (the soak
    harness attaches these to failing reports)."""
    out: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for fn in names:
        if not (fn.startswith("flight-") and fn.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(d, fn)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "dump":
                        rec["file"] = fn
                        out.append(rec)
        except OSError:
            pass
    return out


def install(tag: Optional[str] = None) -> None:
    """Arm the flight recorder's dump triggers in this process:

      * sys.excepthook / threading.excepthook — an uncaught exception
        dumps before the default handler prints it;
      * faults.point `crash` — the pre-SIGKILL hook dumps the ring at the
        exact hazard site the fault plane killed (the chaos soak's
        worker/daemon/head deaths become diagnosable);
      * lock_watchdog reports — an order inversion or long hold dumps the
        ring alongside the watchdog's own report file.

    Idempotent; cheap enough to call at every process entry."""
    global _installed, _proc_tag
    if tag:
        _proc_tag = tag
    # Sampling-profiler autostart (RAY_TPU_PROF_HZ > 0): every process
    # entry funnels through install(), so the always-hot mode covers
    # head, workers and daemons with one knob.  Re-checked
    # per call — forked children re-install under their own tag and the
    # parent's sampler thread did not survive the fork.
    try:
        from ray_tpu._private import profiler as _profiler

        _profiler.maybe_autostart()
    except Exception:
        pass
    if _installed:
        return
    _installed = True

    from ray_tpu._private import faults, lock_watchdog

    faults.set_crash_hook(
        lambda point_name: flight_dump(f"fault-crash:{point_name}")
    )
    lock_watchdog.set_report_hook(
        lambda report: flight_dump("lock-watchdog")
    )

    prev_except = sys.excepthook

    def _excepthook(etype, value, tb):
        note("uncaught", error=f"{etype.__name__}: {value}")
        flight_dump(f"uncaught:{etype.__name__}")
        prev_except(etype, value, tb)

    sys.excepthook = _excepthook

    prev_thread = threading.excepthook

    def _thread_excepthook(args):
        note(
            "uncaught-thread",
            error=f"{args.exc_type.__name__}: {args.exc_value}",
            thread=getattr(args.thread, "name", "?"),
        )
        flight_dump(f"uncaught-thread:{args.exc_type.__name__}")
        prev_thread(args)

    threading.excepthook = _thread_excepthook


# ---------------------------------------------------------------------------
# per-process metric snapshots (the push payload)

_last_push_wire: Dict[str, int] = {}


def snapshot_process(extra: Optional[Dict[str, float]] = None) -> Dict:
    """One process's telemetry snapshot: the full util/metrics registry
    (histograms carry boundaries for head-side rendering), this process's
    wire counters, and any caller-supplied internal gauges (head queue
    depths, journal counters...).  Shipped verbatim as the metrics_push
    payload — pickle carries the tag-tuple keys fine."""
    from ray_tpu._private import wire as _wire
    from ray_tpu.util import metrics as _metrics

    snap = {
        "pid": os.getpid(),
        "proc": _proc_tag,
        "t": time.time(),
        "metrics": _metrics.collect(),
        "wire": _wire.stats(),
    }
    if extra:
        snap["internal"] = dict(extra)
    # Flight-ring the push DELTA (bytes/frames moved since the last one):
    # a crash dump then shows the process's recent control-plane activity.
    try:
        w = snap["wire"]
        global _last_push_wire
        note(
            "metrics_push",
            frames=w["logical_frames"] - _last_push_wire.get("logical_frames", 0),
            writes=w["physical_writes"] - _last_push_wire.get("physical_writes", 0),
            bytes=w["bytes_written"] - _last_push_wire.get("bytes_written", 0),
            metrics=len(snap["metrics"]),
        )
        _last_push_wire = dict(w)
    except Exception:
        pass
    return snap


# ---------------------------------------------------------------------------
# head-side sink: latest snapshot per process + ring-buffer time series

def _flat_key(name: str, tag_key: Tuple) -> str:
    if not tag_key:
        return name
    tags = ",".join(f"{k}={v}" for k, v in tag_key)
    return f"{name}{{{tags}}}"


class TelemetrySink:
    """Aggregates pushed per-process snapshots on the head.

    `processes` holds the LATEST snapshot per sender (worker id, driver
    id, daemon:<node>, "head"); `series` holds bounded (t, value) rings
    per aggregated scalar, appended by sample() at the head's push tick.
    Counters and histogram buckets SUM across processes; gauges sum too
    (queue depths add up — the per-process value stays readable in
    `processes`)."""

    def __init__(self, ring_samples: int = 360):
        self._lock = threading.Lock()
        self.processes: Dict[str, Dict] = {}
        self.series: Dict[str, deque] = {}
        self._ring_samples = max(ring_samples, 4)

    def ingest(self, key: str, snap: Dict) -> None:
        if not isinstance(snap, dict):
            return
        with self._lock:
            # Bounded: a pathological sender churn (worker ids are fresh
            # per spawn) must not grow the map forever.
            while len(self.processes) >= 4096:
                self.processes.pop(next(iter(self.processes)))
            self.processes[key] = snap

    def forget(self, key: str) -> None:
        with self._lock:
            self.processes.pop(key, None)

    def aggregate(self) -> Dict[str, Dict]:
        """Merge the latest snapshots: metric name -> {type, description,
        boundaries?, data: {tag_key: merged value}} — the same shape one
        process's collect() has, so renderers handle both."""
        with self._lock:
            snaps = list(self.processes.values())
        out: Dict[str, Dict] = {}
        for snap in snaps:
            for name, rec in (snap.get("metrics") or {}).items():
                cur = out.get(name)
                if cur is None:
                    cur = out[name] = {
                        "type": rec.get("type"),
                        "description": rec.get("description", ""),
                        "data": {},
                    }
                    if "boundaries" in rec:
                        cur["boundaries"] = rec["boundaries"]
                elif cur.get("type") != rec.get("type"):
                    continue  # name collision across processes: first wins
                for k, v in (rec.get("data") or {}).items():
                    prev = cur["data"].get(k)
                    if prev is None:
                        cur["data"][k] = (
                            dict(v) if isinstance(v, dict) else v
                        )
                    elif isinstance(v, dict):  # histogram series
                        if len(prev.get("buckets", ())) == len(v.get("buckets", ())):
                            prev["buckets"] = [
                                a + b for a, b in zip(prev["buckets"], v["buckets"])
                            ]
                            prev["sum"] = prev.get("sum", 0.0) + v.get("sum", 0.0)
                            prev["count"] = prev.get("count", 0) + v.get("count", 0)
                    else:
                        cur["data"][k] = prev + v
        return out

    def scalars(self) -> Dict[str, float]:
        """Flattened aggregate: one number per (metric, tag set).  The
        series rings and the CLI read this."""
        out: Dict[str, float] = {}
        for name, rec in self.aggregate().items():
            for k, v in rec["data"].items():
                if isinstance(v, dict):
                    out[_flat_key(name + "_count", k)] = float(v.get("count", 0))
                    out[_flat_key(name + "_sum", k)] = float(v.get("sum", 0.0))
                else:
                    out[_flat_key(name, k)] = float(v)
        return out

    def internal_totals(self) -> Dict[str, float]:
        """Cluster-wide sums of the per-process `internal` gauges (head
        queue depths, journal counters) and wire counters."""
        out: Dict[str, float] = {}
        with self._lock:
            snaps = list(self.processes.values())
        for snap in snaps:
            for k, v in (snap.get("internal") or {}).items():
                out[k] = out.get(k, 0.0) + float(v)
            for k, v in (snap.get("wire") or {}).items():
                out[f"wire_{k}"] = out.get(f"wire_{k}", 0.0) + float(v)
        return out

    def sample(self, extra: Optional[Dict[str, float]] = None) -> None:
        """Fold the current aggregate into the time-series rings (one
        sample per metric per head push tick)."""
        now = time.time()
        values = self.scalars()
        values.update(self.internal_totals())
        if extra:
            values.update(extra)
        with self._lock:
            for k, v in values.items():
                ring = self.series.get(k)
                if ring is None:
                    ring = self.series[k] = deque(maxlen=self._ring_samples)
                ring.append((now, v))

    def series_snapshot(
        self, name: Optional[str] = None
    ) -> Dict[str, List[Tuple[float, float]]]:
        with self._lock:
            if name is not None:
                return {name: list(self.series.get(name, ()))}
            return {k: list(v) for k, v in self.series.items()}

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            procs = {
                key: {
                    "pid": s.get("pid"),
                    "proc": s.get("proc"),
                    "age_s": round(time.time() - s.get("t", 0.0), 3),
                    "metrics": len(s.get("metrics") or ()),
                    # Per-process internal gauges ride along (head queue
                    # depths): `ray_tpu status` reads them per process,
                    # not just as cluster sums.
                    **(
                        {"internal": dict(s["internal"])}
                        if isinstance(s.get("internal"), dict)
                        else {}
                    ),
                }
                for key, s in self.processes.items()
            }
            n_series = len(self.series)
        return {
            "processes": procs,
            "series_tracked": n_series,
            "aggregate": self.scalars(),
            "internal": self.internal_totals(),
        }


def prometheus_cluster_text(
    sink: TelemetrySink, extra_gauges: Optional[Dict[str, float]] = None
) -> str:
    """Prometheus text exposition of the CLUSTER aggregate: every pushed
    process registry merged (counters/buckets summed), plus runtime-level
    gauges — the head's /metrics endpoint body (ray: the metrics agent
    re-exports every worker's OpenCensus views the same way)."""
    from ray_tpu.util.metrics import (
        _prom_help,
        _prom_histogram_lines,
        _prom_labels,
        _prom_name,
    )

    agg = sink.aggregate()
    lines: List[str] = []
    for name, rec in sorted(agg.items()):
        pname = _prom_name(name)
        mtype = rec.get("type")
        if mtype == "Counter":
            lines.append(f"# HELP {pname}_total {_prom_help(rec['description'])}")
            lines.append(f"# TYPE {pname}_total counter")
            for k, v in sorted(rec["data"].items()):
                lines.append(f"{pname}_total{_prom_labels(k)} {v}")
        elif mtype == "Gauge":
            lines.append(f"# HELP {pname} {_prom_help(rec['description'])}")
            lines.append(f"# TYPE {pname} gauge")
            for k, v in sorted(rec["data"].items()):
                lines.append(f"{pname}{_prom_labels(k)} {v}")
        elif mtype == "Histogram" and rec.get("boundaries"):
            lines.append(f"# HELP {pname} {_prom_help(rec['description'])}")
            lines.append(f"# TYPE {pname} histogram")
            for k, d in sorted(rec["data"].items()):
                if isinstance(d, dict):
                    lines.extend(
                        _prom_histogram_lines(pname, k, rec["boundaries"], d)
                    )
    for name, value in sorted((extra_gauges or {}).items()):
        pname = _prom_name(f"ray_tpu_{name}")
        lines.append(f"# TYPE {pname} gauge")
        lines.append(f"{pname} {value}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# task lifecycle attribution: the per-task state machine's pure core
#
# ray: gcs_task_manager.h keeps per-task state-transition records (the
# task_events ring here); PAPERS.md's Dapper lineage argues the useful
# unit is the STAGE-ATTRIBUTED record, not aggregate counters.  Each
# TaskRecord carries wall-clock stamps for the stages below (head clock;
# executor stamps land via the done message, clock-offset-corrected);
# stage_durations() telescopes them into per-stage seconds, so the sum
# of durations equals last-stamp minus first-stamp by construction —
# the ≥95%-accounted acceptance property.

# Stamp order (a task flows left to right; absent stamps are skipped):
#   submit     submit_task entry (head)
#   queued     dependencies met, joined the ready queue (head)
#   leased     worker handle acquired by the dispatcher (head)
#   pushed     task frame written to a live conn (head)
#   received   executor dequeued the frame (worker, corrected)
#   running    executor began user code (worker, corrected)
#   exec_done  user code returned (worker, corrected)
#   done       done message landed on the head (head)
#   sealed     results stored + lineage recorded (head)
STAGE_ORDER = (
    "submit", "queued", "leased", "pushed", "received", "running",
    "exec_done", "done", "sealed",
)

# Duration labels: time spent BETWEEN stamp X and the next present stamp
# is attributed to the stage named here (what the task was waiting on).
STAGE_LABELS = {
    "submit": "pending",        # dependency wait
    "queued": "queued",         # scheduler queue
    "leased": "lease",          # worker acquisition (spawn on a cold pool)
    "pushed": "wire",           # frame flight + executor pickup
    "received": "exec_queue",   # executor-side queue behind earlier tasks
    "running": "running",       # user code
    "exec_done": "return",      # result flight back (batch linger + decode)
    "done": "seal",             # head-side store + lineage bookkeeping
}


def stage_durations(stages: Dict[str, float]) -> Dict[str, float]:
    """Telescoped per-stage seconds from a stamp dict (pure).  Negative
    gaps (clock-offset estimation error across processes) clamp to 0 —
    the clamped time reappears in the next head-side stage, so the total
    stays within the offset error of wall time."""
    present = [
        (s, stages[s])
        for s in STAGE_ORDER
        if isinstance(stages.get(s), (int, float))
    ]
    out: Dict[str, float] = {}
    for (s0, t0), (_s1, t1) in zip(present, present[1:]):
        out[STAGE_LABELS.get(s0, s0)] = round(max(t1 - t0, 0.0), 6)
    return out


def stage_wall_seconds(stages: Dict[str, float]) -> float:
    """First-to-last stamped wall time (the denominator of the
    accounted-fraction acceptance check)."""
    ts = [
        stages[s] for s in STAGE_ORDER
        if isinstance(stages.get(s), (int, float))
    ]
    return max(ts[-1] - ts[0], 0.0) if len(ts) >= 2 else 0.0


_STAGE_HIST = None


def task_stage_histogram():
    """`task_stage_seconds{stage=...}` — the head observes every finished
    task's per-stage durations here; the cluster aggregate renders it on
    /metrics.  Lazy: only the process folding task records registers it."""
    global _STAGE_HIST
    if _STAGE_HIST is None:
        from ray_tpu.util.metrics import Histogram

        _STAGE_HIST = Histogram(
            "task_stage_seconds",
            "per-task time spent in each lifecycle stage "
            "(submit→queued→leased→pushed→running→done→sealed machine)",
            boundaries=[0.0005, 0.002, 0.01, 0.05, 0.2, 1.0, 5.0, 30.0],
            tag_keys=("stage",),
        )
    return _STAGE_HIST


_REMESH_HIST = None


def remesh_histogram():
    """`remesh_seconds{stage=...}` — elastic-SPMD recovery wall clock
    attributed per stage (detect → teardown → replan → respawn → resume,
    plus total).  The trainer driver observes one sample per stage per
    re-mesh episode; the chaos soak asserts the breakdown lands.  Lazy,
    like task_stage_histogram: only a process that actually re-meshes
    registers it.  Boundaries are seconds-scale: recovery is dominated by
    the replacement-wait policy and worker respawn, not micro latencies."""
    global _REMESH_HIST
    if _REMESH_HIST is None:
        from ray_tpu.util.metrics import Histogram

        _REMESH_HIST = Histogram(
            "remesh_seconds",
            "elastic MESH gang recovery time per stage "
            "(detect/teardown/replan/respawn/resume/total)",
            boundaries=[0.05, 0.2, 0.5, 1.0, 2.0, 5.0, 15.0, 60.0, 300.0],
            tag_keys=("stage",),
        )
    return _REMESH_HIST


_AUTOSCALE_HIST = None


def autoscale_histogram():
    """`autoscale_seconds{stage=...}` — elastic-capacity transition wall
    clock attributed per node-lifecycle edge (launch = REQUESTED→ACTIVE,
    drain_wait = DRAINING→quiesced, evacuate = quiesced→objects-safe,
    depart = DRAINING→DEPARTED, plus total for a full drain).  The
    head-side reconciler observes one sample per transition per node;
    the autoscale chaos soak asserts the breakdown lands.  Lazy like
    remesh_histogram — only a head that actually autoscales registers
    it.  Seconds-scale boundaries: launches are dominated by daemon
    boot, drains by task completion and evacuation."""
    global _AUTOSCALE_HIST
    if _AUTOSCALE_HIST is None:
        from ray_tpu.util.metrics import Histogram

        _AUTOSCALE_HIST = Histogram(
            "autoscale_seconds",
            "elastic-capacity node transition time per stage "
            "(launch/drain_wait/evacuate/depart/total)",
            boundaries=[0.05, 0.2, 0.5, 1.0, 2.0, 5.0, 15.0, 60.0, 300.0],
            tag_keys=("stage",),
        )
    return _AUTOSCALE_HIST


def summarize_task_events(
    events: List[Dict[str, Any]],
    live: Optional[List[Dict[str, Any]]] = None,
    slow: int = 10,
) -> Dict[str, Any]:
    """Fold task records into the `ray_tpu tasks --summary` body (pure):
    per-stage totals + percentiles, the accounted-vs-wall fraction, state
    counts, and the N slowest tasks with their stage breakdowns."""
    per_stage: Dict[str, List[float]] = {}
    states: Dict[str, int] = {}
    wall_total = 0.0
    accounted_total = 0.0
    rows: List[Dict[str, Any]] = []
    for e in events:
        states[e.get("state", "?")] = states.get(e.get("state", "?"), 0) + 1
        durs = e.get("durations") or {}
        stages = e.get("stages") or {}
        wall = stage_wall_seconds(stages) or float(e.get("duration") or 0.0)
        acc = sum(durs.values())
        wall_total += wall
        accounted_total += acc
        for k, v in durs.items():
            per_stage.setdefault(k, []).append(float(v))
        rows.append(
            {
                "task_id": e.get("task_id"),
                "name": e.get("name"),
                "state": e.get("state"),
                "wall_s": round(wall, 6),
                "durations": durs,
                "creation": bool(e.get("creation")),
                "critical_stage": (
                    max(durs, key=durs.get) if durs else None
                ),
            }
        )
    for t in live or ():
        states[t.get("state", "?")] = states.get(t.get("state", "?"), 0) + 1

    def _pct(xs: List[float], q: float) -> float:
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(int(q * (len(xs) - 1) + 0.5), len(xs) - 1)]

    stage_stats = {
        k: {
            "count": len(v),
            "total_s": round(sum(v), 6),
            "mean_s": round(sum(v) / len(v), 6),
            "p50_s": round(_pct(v, 0.50), 6),
            "p95_s": round(_pct(v, 0.95), 6),
            "p99_s": round(_pct(v, 0.99), 6),
        }
        for k, v in sorted(per_stage.items())
    }
    rows.sort(key=lambda r: -r["wall_s"])
    return {
        "tasks": len(events),
        "states": states,
        "stages": stage_stats,
        "wall_s_total": round(wall_total, 6),
        "accounted_s_total": round(accounted_total, 6),
        "accounted_fraction": (
            round(accounted_total / wall_total, 4) if wall_total else None
        ),
        "slow": rows[: max(slow, 0)],
    }


# ---------------------------------------------------------------------------
# object/memory introspection plane: bytes-per-copy counters + the ledger

def _copy_counters():
    """Process-wide bytes-per-copy counters, created lazily (module import
    runs before config/metric setup in some entrypoints).  Every byte-
    moving path of the object plane increments these: put/seal (create a
    sealed copy), pull (a transfer-plane copy from a sealed source),
    relay (a transfer-plane copy served out of an in-flight pull's board
    — pipelined broadcast), spill/restore (disk round trips), promote
    (inline bytes uploaded to the head), arena_map (a same-node zero-copy
    map of a sealed arena buffer: copies tick, bytes stay ZERO — the
    counted proof reads don't copy).  The copy-coverage lint pass holds
    every byte-moving function in store/object_plane/arena to this
    counter (or a reviewed allowlist entry).  ray_perf's put/broadcast
    shapes report bytes-per-copy off the deltas; the cluster aggregate
    sums every process's counts via the metrics push."""
    global _OBJ_COPIES, _OBJ_COPY_BYTES
    if _OBJ_COPIES is None:
        from ray_tpu.util.metrics import Counter

        _OBJ_COPIES = Counter(
            "object_copies",
            "sealed-copy operations by object-plane path",
            tag_keys=("path",),
        )
        _OBJ_COPY_BYTES = Counter(
            "object_copy_bytes",
            "bytes moved per object-plane copy path",
            tag_keys=("path",),
        )
    return _OBJ_COPIES, _OBJ_COPY_BYTES


_OBJ_COPIES = None
_OBJ_COPY_BYTES = None


def count_copy(path: str, nbytes: int) -> None:
    """Record one object-plane copy of nbytes via `path` (put/seal/pull/
    relay/spill/restore/promote/arena_map).  Never raises — called from
    store/transfer hot paths, sometimes under their locks."""
    try:
        copies, by = _copy_counters()
        copies.inc(tags={"path": path})
        if nbytes:
            by.inc(nbytes, tags={"path": path})
    except Exception:
        pass


def copy_counter_snapshot() -> Dict[str, Dict[str, float]]:
    """{path: {copies, bytes}} from this process's counters (ray_perf
    reads deltas of this around a timed shape)."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        copies, by = _copy_counters()
        for k, v in copies.snapshot().items():
            path = dict(k).get("path", "?")
            out.setdefault(path, {"copies": 0.0, "bytes": 0.0})["copies"] = v
        for k, v in by.snapshot().items():
            path = dict(k).get("path", "?")
            out.setdefault(path, {"copies": 0.0, "bytes": 0.0})["bytes"] = v
    except Exception:
        pass
    return out


_LEDGER_GAUGES = None


def ledger_gauges():
    """Prometheus-facing gauges the head sets from its ledger tick:
    per-node store/spilled bytes and per-node leak-suspect bytes.  Lazy —
    only the process that sets them registers them."""
    global _LEDGER_GAUGES
    if _LEDGER_GAUGES is None:
        from ray_tpu.util.metrics import Gauge

        _LEDGER_GAUGES = (
            Gauge(
                "object_ledger_node_bytes",
                "sealed object bytes per node and tier (store/spilled), "
                "from the head's object-ledger join",
                tag_keys=("node", "tier"),
            ),
            Gauge(
                "object_ledger_leak_suspect_bytes",
                "bytes attributed to object-ledger leak suspects, by the "
                "holding (or owning) node",
                tag_keys=("node",),
            ),
        )
    return _LEDGER_GAUGES


class ObjectLedger:
    """Head-side sink for pushed per-process live-ref tables (refs_push),
    the worker leg of cluster memory introspection.  Mirrors TelemetrySink:
    latest snapshot per sender, forgotten when the process dies.  The
    authoritative owner-side join (store tables + object directory + conn-
    tracked borrows) happens in build_memory_records — this class only
    carries what remote processes report about themselves (in-process
    counts, owned flags, creation sites)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.tables: Dict[str, Dict] = {}

    def ingest(self, key: str, snap: Dict) -> None:
        if not isinstance(snap, dict):
            return
        with self._lock:
            while len(self.tables) >= 4096:
                self.tables.pop(next(iter(self.tables)))
            self.tables[key] = snap

    def forget(self, key: str) -> None:
        with self._lock:
            self.tables.pop(key, None)

    def snapshot(self) -> Dict[str, Dict]:
        with self._lock:
            return dict(self.tables)


def build_memory_records(
    store_table: Dict[str, Tuple[str, Optional[int]]],
    refcounts: Dict[str, int],
    ready: Dict[str, bool],
    locations: Dict[str, List[str]],
    sizes: Dict[str, int],
    meta: Dict[str, Tuple[float, str]],
    conn_refs: Dict[str, Dict[str, int]],
    pushed_tables: Dict[str, Dict],
    dead_refs: Dict[str, Dict],
    proc_info: Dict[str, Tuple[Optional[str], Optional[int]]],
    now: float,
    leak_age_s: float,
) -> List[Dict[str, Any]]:
    """Join the owner's view of every object with the holder-side ref
    tables into per-object ledger records (pure — unit-testable without a
    cluster).

      store_table    oid -> (location, size|None) from OwnerStore (head
                     bytes: memory/shm/spilled/error)
      refcounts      owner-side refcount per oid
      locations      oid -> [node ids] holding sealed remote copies
      sizes          oid -> packed size (survives spill)
      meta           oid -> (created_ts, creator proc label)
      conn_refs      holder key -> {oid: outstanding conn-tracked borrows}
                     (workers via refop tracking, drivers via driver_refs,
                     "head" for the head process's own live-ref table)
      pushed_tables  holder key -> refs_push snapshot ({"refs": {oid:
                     [count, site]}, ...}) — enrichment (sites, owned)
      dead_refs      crashed holder key -> {"refs", "node", "pid", ...}:
                     borrows awaiting reclaim — their objects are the
                     DEAD-HOLDER leak suspects
      proc_info      holder key -> (node, pid) for live holders

    Leak rules (SURVEY §2.1's debugging story):
      * dead-holder — bytes still held by a crashed process's unreclaimed
        borrows (clears when the reclaim sweep drops them);
      * no-live-holder — located bytes, refcount 0, no holder anywhere,
        older than leak_age_s (outside the seal-to-first-addref window).
    """
    oids = set(store_table) | set(locations) | set(refcounts)
    pushed_refs: Dict[str, Dict] = {}
    for key, snap in pushed_tables.items():
        refs = snap.get("refs") if isinstance(snap, dict) else None
        if refs:
            pushed_refs[key] = refs
            oids.update(refs)
    for rec in dead_refs.values():
        oids.update(rec.get("refs", ()))

    records: List[Dict[str, Any]] = []
    for oid in oids:
        loc, size = store_table.get(oid, (None, None))
        if size is None:
            size = sizes.get(oid)
        copies = list(locations.get(oid, ()))
        if loc in ("memory", "shm", "spilled"):
            copies = ["head"] + copies
        if loc is None:
            loc = "remote" if locations.get(oid) else "worker-local"
        holders: List[Dict[str, Any]] = []
        for key, table in conn_refs.items():
            n = table.get(oid)
            if not n:
                continue
            node, pid = proc_info.get(key, (None, None))
            pushed = pushed_refs.get(key, {}).get(oid)
            holders.append(
                {
                    "holder": key,
                    "node": node,
                    "pid": pid,
                    "count": n,
                    "site": pushed[1] if pushed else None,
                    "owned": bool(pushed[2]) if pushed and len(pushed) > 2 else False,
                    "pinned": bool(pushed[3]) if pushed and len(pushed) > 3 else False,
                    "dead": False,
                }
            )
        seen = {h["holder"] for h in holders}
        for key, refs in pushed_refs.items():
            # Processes whose borrows are not conn-tracked (e.g. owned
            # direct-call results that never escaped) still show as
            # holders via their pushed table.
            if key in seen or oid not in refs:
                continue
            node, pid = proc_info.get(key, (None, None))
            rec = refs[oid]
            holders.append(
                {
                    "holder": key,
                    "node": node,
                    "pid": pid,
                    "count": rec[0],
                    "site": rec[1],
                    "owned": bool(rec[2]) if len(rec) > 2 else False,
                    "pinned": bool(rec[3]) if len(rec) > 3 else False,
                    "dead": False,
                }
            )
        leak = None
        for key, rec in dead_refs.items():
            n = rec.get("refs", {}).get(oid)
            if n:
                holders.append(
                    {
                        "holder": key,
                        "node": rec.get("node"),
                        "pid": rec.get("pid"),
                        "count": n,
                        "site": None,
                        "owned": False,
                        "pinned": False,
                        "dead": True,
                    }
                )
                # Only a suspect while the owner still accounts the
                # object (bytes or count) — a freed oid lingering in the
                # dead set until the sweep is not a leak.
                if (
                    refcounts.get(oid, 0) > 0
                    or oid in store_table
                    or locations.get(oid)
                ):
                    leak = "dead-holder"
        created, creator = meta.get(oid, (None, None))
        age = round(now - created, 3) if created else None
        has_bytes = loc in ("memory", "shm", "spilled") or bool(
            locations.get(oid)
        )
        if (
            leak is None
            and has_bytes
            and refcounts.get(oid, 0) == 0
            and not holders
            and ready.get(oid, False)
            and (age is None or age > leak_age_s)
        ):
            leak = "no-live-holder"
        records.append(
            {
                "object_id": oid,
                "location": loc,
                "size_bytes": size,
                "copies": copies,
                "refcount": refcounts.get(oid, 0),
                "ready": bool(ready.get(oid, False)),
                "holders": holders,
                "holder_count": sum(h["count"] for h in holders),
                "age_s": age,
                "creator": creator,
                "site": next(
                    (h["site"] for h in holders if h["site"]), None
                ),
                "leak": leak,
            }
        )
    records.sort(key=lambda r: -(r["size_bytes"] or 0))
    return records


def summarize_memory_records(
    records: List[Dict[str, Any]],
    group_by: Optional[str] = None,
    top: int = 20,
) -> Dict[str, Any]:
    """Aggregations over ledger records: per-node bytes, top-N objects,
    leak suspects, optional group-by (node|owner|callsite) — the body of
    `ray_tpu memory`, util/state.memory_summary and /api/memory."""
    nodes: Dict[str, Dict[str, float]] = {}
    total = 0
    spilled = 0
    for r in records:
        size = r["size_bytes"] or 0
        total += size
        for node in r["copies"] or (
            [h["node"] or "?" for h in r["holders"]][:1] or ["?"]
        ):
            rec = nodes.setdefault(
                node, {"store_bytes": 0, "spilled_bytes": 0, "objects": 0}
            )
            rec["objects"] += 1
            if r["location"] == "spilled" and node == "head":
                rec["spilled_bytes"] += size
                spilled += size
            else:
                rec["store_bytes"] += size
    leaks = [r for r in records if r["leak"]]
    out: Dict[str, Any] = {
        "objects": len(records),
        "bytes_total": total,
        "spilled_bytes": spilled,
        "nodes": nodes,
        "top": records[: max(top, 0)],
        "leak_suspects": len(leaks),
        "leak_suspect_bytes": sum(r["size_bytes"] or 0 for r in leaks),
        "leaks": leaks,
    }
    if group_by:
        groups: Dict[str, Dict[str, float]] = {}

        def keys_for(r) -> List[str]:
            if group_by == "node":
                return [str(k) for k in (r["copies"] or ["?"])]
            if group_by == "owner":
                return [str(r["creator"] or "?")]
            if group_by == "callsite":
                sites = {h["site"] for h in r["holders"] if h["site"]}
                if r["site"]:
                    sites.add(r["site"])
                return [str(s) for s in (sites or {"?"})]
            raise ValueError(
                f"unknown group_by {group_by!r} (node|owner|callsite)"
            )

        for r in records:
            for k in keys_for(r):
                g = groups.setdefault(k, {"objects": 0, "bytes": 0})
                g["objects"] += 1
                g["bytes"] += r["size_bytes"] or 0
        out["groups"] = dict(
            sorted(groups.items(), key=lambda kv: -kv[1]["bytes"])
        )
    return out


def _reset_for_tests() -> None:
    global _ring, _last_push_wire
    with _ring_lock:
        _ring = None
    _last_push_wire = {}
