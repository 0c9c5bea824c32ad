"""Chaos soak: a schedule-driven, minutes-scale fault sweep with a seed.

ray: release/nightly_tests/setup_chaos.py runs Ray's long-running chaos
suites with a NodeKillerActor; the CI-scale tests/test_chaos.py here kills
at wall-clock random and cannot replay a failure.  This harness drives the
deterministic fault plane (ray_tpu/_private/faults.py) instead: every kill
and every delay comes from a named, seeded RAY_TPU_FAULT_SPEC clause, so a
failing run prints its seed and the exact spec to rerun.

The soak boots a SPLIT cluster (standalone head subprocess + one external
node daemon + one RELAY node under a scoped spec) and keeps five
workloads running while the spec fires:

  * task chains (produce -> fold, lineage + retries) — every round's
    results must be exactly right;
  * a NAMED restartable actor under max_task_retries — every reply must
    match;
  * an ANONYMOUS restartable actor whose worker is killed in the SAME
    window as a head kill (the overlap ISSUE 5's journaled GCS exists
    for) — the driver's handle must be re-resolved and serving again,
    and the ledger proves a restart happened;
  * serve HTTP traffic against a 2-replica deployment (replicas are
    killed in the head-kill window too) — every logical request must
    eventually succeed;
  * pipelined BROADCASTS (ISSUE 12): fresh multi-chunk objects land on
    several nodes per round through relay transfer plans while the
    relay node's daemon is crash-killed MID-RELAY — every sum must stay
    exact and nothing may leak.

The default schedule (seeded, per-process deterministic):
  * workers crash at their result-send hazard (wire.send of done/pdone
    frames, every N-th matching frame) — the juiciest window: did the
    result land before the death?;
  * the node daemon crashes at its t=18s (store loss -> lineage
    reconstruction) and is relaunched as a fresh node;
  * the head SIGKILLs itself mid-snapshot at its t=30s and is relaunched
    into the same session (restore + live-worker adoption);
  * a small probabilistic delay on every control frame keeps ordering
    races warm.

Afterwards the harness drains to a quiescent state (fault spec stripped
from relaunches), runs a clean verification round, and checks the ledger:
no lost results, no reply mismatches, per-task execution counts within
retry budgets, zero lost serve requests.  The report lands in
CHAOS_r01.json (or --out).

A SECOND scenario (--trainer, ISSUE 16) proves elastic SPMD end to end:
a MESH-gang DataParallelTrainer runs checkpointed steps across two
mesh_coord-labeled gang hosts while the harness SIGKILLs one gang daemon
mid-step.  The gang must re-mesh at N-1 within the RAY_TPU_REMESH_WAIT_S
window, resume from the latest checkpoint with bounded lost steps, scale
back to N when a replacement host (same coordinate) joins, and finish
with every step reported exactly once — with the per-stage recovery
breakdown (detect/teardown/replan/respawn/resume) in the remesh_seconds
histogram.  Report lands in CHAOS_r11.json.

Usage:
    python scripts/chaos_soak.py --duration 75 --seed 7 \
        [--spec '<fault spec>'] [--out CHAOS_r01.json] [--no-serve]
    python scripts/chaos_soak.py --trainer [--out CHAOS_r11.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402

# Per-process deterministic kill schedule + latency noise:
#   * match=^done (anchored) kills RELAYED executors — chain task workers
#     and the soak actor's worker — at their result-send hazard, but not
#     direct-path repliers (pdone does not match);
#   * the old replica-kill/head-bounce EXCLUSION is LIFTED: the journaled
#     GCS (ISSUE 5) persists ANONYMOUS actor records, so the schedule now
#     deliberately overlaps them — the AnonSoak worker and each serve
#     Replica crash at their t=29 (their clocks start at worker spawn,
#     so these land during/right after the head's own t=30 death): an
#     actor that dies while the head is down must be re-resolved from the
#     restored record and restarted on its budget;
#   * each head incarnation dies TWICE over: SIGKILL mid-journal-append
#     at its t=24 (torn-tail hazard — replay must recover the complete
#     prefix) and, if it gets there, mid-snapshot at its t=30;
#   * only the FIRST daemon (soak-d1) dies — its store loss must heal via
#     lineage before the head kills land;
#   * wire.flush clauses exercise the BATCH hazard window: a worker dies
#     mid-flush with a coalesced run of frames in flight (the receiver
#     sees a torn stream — EOF or a truncated batch decode_frames rejects
#     whole, never a partial dispatch), and a small probabilistic delay
#     stretches flush windows to keep batch/ordering races warm.
#   * ISSUE 12 (RELAY_SPEC below, scoped to the relay node):
#     transfer.chunk_relay crash-kills the relay daemon MID-RELAY of a
#     live broadcast (serving chunks of a pull still in flight on its
#     node) after its 8th relayed chunk — the downstream puller must fall
#     back to a sealed source (or re-plan via the owner) and the
#     broadcast workload must lose nothing; the 256KB soak chunk size
#     keeps every broadcast multi-chunk so the hazard window stays wide.
DEFAULT_SPEC = (
    "wire.send:crash@proc=worker,match=^done,after=40,every=53,times=2;"
    "wire.send:delay=0.002@prob=0.02;"
    "wire.flush:crash@proc=worker,match=^done,after=30,every=41,times=1;"
    "wire.flush:delay=0.002@prob=0.02;"
    "wire.send:crash@proc=daemon:soak-d1,at=18,times=1;"
    "wire.send:crash@proc=actor:AnonSoak,at=29,times=1;"
    "wire.send:crash@proc=actor:Replica,at=29,times=1;"
    "gcs.journal_append:crash@proc=head,at=24,times=1;"
    "gcs.save:crash@proc=head,at=30,times=1"
)

# The RELAY node runs a SCOPED spec: just the mid-relay daemon kill (+ the
# ambient wire delay).  Its workers inherit this spec too — deliberately
# WITHOUT the worker/actor kill clauses: a relay node carrying the full
# schedule re-arms the per-process actor kills on every respawned worker
# it hosts, which turns post-storm placement onto that node into an
# infinite kill loop (observed: replicas/actors re-killed every ~30s
# through the whole drain).  The relay hazard this node exists for lives
# in the DAEMON process, so that is what the clause targets.
RELAY_SPEC = (
    "transfer.chunk_relay:crash@proc=daemon,after=8,times=1;"
    "wire.send:delay=0.002@prob=0.02"
)

TASK_RETRIES = 25
ACTOR_RETRIES = 25
CHAIN_WIDTH = 8
# Driver-level re-drives per logical operation.  A head kill erases the
# control-plane record of COMPLETED-but-unfetched results that lived only
# in the head process; the supported recovery envelope is snapshot
# re-drive (in-flight tasks) + surviving node copies + actor adoption.  A
# logical op that still cannot produce its (correct) answer after this
# many fresh submissions counts as LOST and fails the soak — and every
# re-drive is counted in the report, so the at-most-once windows are
# measured, not papered over.
REDRIVES = 3
# shm-sized payloads (>= max_direct_call_object_size): sealed segments
# live on tmpfs node stores and survive head bounces; inline results die
# with the head process.
ARR = 1 << 14


def _append(path: str, line: str) -> None:
    # O_APPEND single-line writes are atomic across the node's processes.
    with open(path, "a") as f:
        f.write(line + "\n")


@ray_tpu.remote(max_retries=TASK_RETRIES)
def produce(i, r, log_path):
    _append(log_path, f"produce:{r}:{i}")
    return np.full((ARR,), i, dtype=np.int64)


@ray_tpu.remote(max_retries=TASK_RETRIES)
def wave_work(i, delay, log_path):
    """Demand wave for the autoscale scenario: 1-CPU sleepers sized so the
    queue outlives the up-wait hysteresis and the fleet provably grows."""
    _append(log_path, f"wave:{i}")
    time.sleep(delay)
    return i


@ray_tpu.remote(max_retries=TASK_RETRIES)
def fold(a, j, r, log_path):
    _append(log_path, f"fold:{r}:{j}")
    return np.full((ARR,), int(a.sum()) + j, dtype=np.int64)


# Broadcast payload: ~4MB of int64 => 16 relay chunks at the soak's 256KB
# transfer chunk size, so a mid-relay kill has a wide window to land in.
BCAST_N = (4 << 20) // 8


@ray_tpu.remote(max_retries=TASK_RETRIES, scheduling_strategy="SPREAD")
def bcast_land(x, r, i, log_path):
    _append(log_path, f"bcast:{r}:{i}")
    return int(x.sum())


@ray_tpu.remote(max_restarts=100, max_task_retries=ACTOR_RETRIES)
class SoakActor:
    def __init__(self, log_path):
        self.log_path = log_path

    def echo(self, i):
        _append(self.log_path, f"actor:{i}")
        return i


@ray_tpu.remote(max_restarts=100, max_task_retries=ACTOR_RETRIES)
class AnonSoak:
    """ANONYMOUS restartable actor — the record class that used to die
    with the head.  Its spec clause kills the hosting worker at its t=29,
    overlapping the head's own deaths: recovery requires the restarted
    head to re-resolve the actor from persisted GCS state (journal) and
    restart it on its budget.  __init__ logs so the ledger can PROVE a
    restart happened (anoninit count >= 2)."""

    def __init__(self, log_path):
        self.log_path = log_path
        _append(log_path, "anoninit:0")

    def echo(self, i):
        _append(self.log_path, f"anon:{i}")
        return i


def _launch_daemon(head_json: str, node_id: str, num_cpus: int,
                   spec_override: Optional[str] = None,
                   resources: Optional[Dict[str, float]] = None,
                   labels: Optional[Dict[str, str]] = None):
    """spec_override scopes the fault plan THIS daemon (and every worker
    it spawns) runs under; empty string = no faults; None = inherit the
    ambient os.environ spec (the classic soak daemons).  labels carry the
    mesh_coord topology tags the elastic-trainer scenario's gang hosts
    need."""
    with open(head_json) as f:
        info = json.load(f)
    env = os.environ.copy()
    if spec_override is not None:
        if spec_override:
            env["RAY_TPU_FAULT_SPEC"] = spec_override
        else:
            env.pop("RAY_TPU_FAULT_SPEC", None)
    env.update(
        {
            "RAY_TPU_DRIVER_HOST": info["host"],
            "RAY_TPU_DRIVER_PORT": str(info["port"]),
            "RAY_TPU_AUTHKEY": info["authkey"],
            "RAY_TPU_NODE_CONFIG": json.dumps(
                {
                    "node_id": node_id,
                    "session": info["session"],
                    "num_cpus": num_cpus,
                    "resources": resources or {},
                    "labels": labels or {},
                }
            ),
            "PYTHONPATH": os.pathsep.join(dict.fromkeys([REPO_ROOT] + sys.path)),
        }
    )
    return subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_daemon"],
        env=env,
        close_fds=True,
    )


class _Workload(threading.Thread):
    """Base: loops `step` until stop; remembers the first hard failure."""

    t0 = 0.0  # stamped by run_soak before start()

    def __init__(self, name, stop):
        super().__init__(daemon=True, name=name)
        self.stop_evt = stop
        self.failure: Optional[str] = None
        self.iterations = 0
        self.redrives = 0

    def note(self, msg):
        print(
            f"[soak t={time.monotonic() - self.t0:6.1f}s] [{self.name}] {msg}",
            flush=True,
        )

    def run(self):
        while not self.stop_evt.is_set():
            try:
                self.step()
                self.iterations += 1
            except Exception as e:  # noqa: BLE001 — a soak failure is data
                import traceback

                self.failure = (
                    f"(iteration {self.iterations}, "
                    f"t={time.monotonic() - self.t0:.1f}s) "
                    f"{type(e).__name__}: {e}"
                )
                self.note(self.failure + "\n" + traceback.format_exc())
                return

    def eventually(self, make_refs, check, timeout=60.0):
        """Submit-fresh-and-get with a bounded, COUNTED re-drive on the
        two outcomes a head kill can legitimately inflict on this client
        (a parked get that will never resolve, a loudly-lost object).
        Wrong VALUES never retry — they fail the soak immediately."""
        from ray_tpu.exceptions import GetTimeoutError, ObjectLostError

        last = None
        for attempt in range(1 + REDRIVES):
            if attempt:
                self.redrives += 1
                self.note(
                    f"re-drive {attempt}/{REDRIVES} of iteration "
                    f"{self.iterations} after {last!r}"
                )
            try:
                outs = ray_tpu.get(make_refs(), timeout=timeout)
            except (GetTimeoutError, ObjectLostError) as e:
                last = e
                continue
            check(outs)
            return
        raise AssertionError(
            f"logical op LOST after {REDRIVES} re-drives: {last!r}"
        )


class _ChainLoad(_Workload):
    def __init__(self, stop, log_path):
        super().__init__("soak-chains", stop)
        self.log_path = log_path

    def step(self):
        r = self.iterations

        def make_refs():
            return [
                fold.remote(
                    produce.remote(i, r, self.log_path), i, r, self.log_path
                )
                for i in range(CHAIN_WIDTH)
            ]

        def check(outs):
            for i, a in enumerate(outs):
                expect = i * ARR + i
                if a.shape != (ARR,) or int(a[0]) != expect or int(a.sum()) != expect * ARR:
                    raise AssertionError(
                        f"chain round {r} lane {i}: wrong result (CORRUPT)"
                    )

        self.eventually(make_refs, check)


class _ActorLoad(_Workload):
    def __init__(self, stop, log_path):
        super().__init__("soak-actor", stop)
        self.actor = SoakActor.options(name="soak_actor").remote(log_path)

    def step(self):
        i = self.iterations

        def check(outs):
            if outs != [i]:
                raise AssertionError(
                    f"actor echo({i}) returned {outs[0]} (CORRUPT reply)"
                )

        self.eventually(lambda: [self.actor.echo.remote(i)], check)
        # Shared-box pacing.  This also sets the actor-worker churn rate:
        # the kill clause fires on done-frame COUNTS, so an unpaced echo
        # hammer would recycle the actor's worker every ~1s and the
        # one-box cluster would spend itself respawning processes.
        time.sleep(0.1)


class _AnonLoad(_Workload):
    """Drives the ANONYMOUS actor through the overlapping replica-kill +
    head-kill window.  The driver keeps calling the SAME handle — after
    the overlap, the handle only works again if the restarted head
    re-resolved the anonymous record (pre-ISSUE-5 this was impossible:
    the record died with the head)."""

    def __init__(self, stop, log_path):
        super().__init__("soak-anon", stop)
        self.actor = AnonSoak.remote(log_path)

    def step(self):
        i = self.iterations

        def check(outs):
            if outs != [i]:
                raise AssertionError(
                    f"anon echo({i}) returned {outs[0]} (CORRUPT reply)"
                )

        self.eventually(lambda: [self.actor.echo.remote(i)], check)
        time.sleep(0.1)  # same shared-box pacing as the named actor load


class _BroadcastLoad(_Workload):
    """ISSUE 12: a live pipelined broadcast under the storm.  Each round
    puts a FRESH multi-chunk object (head store) and lands it on several
    nodes at once via SPREAD — the owner hands out relay transfer plans,
    in-flight pullers re-serve chunks, and the spec's
    transfer.chunk_relay clause crash-kills a daemon MID-RELAY.  Every
    round's sums must be exactly right (a torn or short relay would
    corrupt them), and the re-drive budget covers head/daemon deaths.
    The put rides inside make_refs so a re-drive after a head bounce
    re-seals fresh bytes instead of chasing a dead object id."""

    WIDTH = 3  # landing tasks per round (SPREAD across the node set)

    def __init__(self, stop, log_path):
        super().__init__("soak-bcast", stop)
        self.log_path = log_path

    def step(self):
        r = self.iterations
        fill = r % 251 + 1
        arr = np.full(BCAST_N, fill, dtype=np.int64)
        expect = fill * BCAST_N

        def make_refs():
            ref = ray_tpu.put(arr)
            return [
                bcast_land.remote(ref, r, i, self.log_path)
                for i in range(self.WIDTH)
            ]

        def check(outs):
            for i, got in enumerate(outs):
                if got != expect:
                    raise AssertionError(
                        f"broadcast round {r} lane {i}: {got} != {expect} "
                        "(CORRUPT relay)"
                    )

        self.eventually(make_refs, check)
        time.sleep(0.3)  # shared-box pacing; frees land between rounds


class _ServeLoad(_Workload):
    """One logical request per step; each retries (with address
    re-discovery — a restarted proxy binds a fresh port) until it succeeds
    or the per-request budget lapses (then it is LOST — the soak fails)."""

    def __init__(self, stop, addr, addr_fn):
        super().__init__("soak-serve", stop)
        self.addr = addr
        self.addr_fn = addr_fn
        self.ok = 0
        self.retried = 0
        self.lost = 0

    def step(self):
        import urllib.request

        deadline = time.monotonic() + 60
        attempt = 0
        while True:
            attempt += 1
            try:
                req = urllib.request.Request(
                    self.addr + "/soak", data=b"{}", method="POST"
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    body = json.loads(resp.read())
                assert body["result"] == {"ok": True}
                self.ok += 1
                if attempt > 1:
                    self.retried += 1
                # Light pacing: the soak shares one box with the whole
                # cluster; an unpaced HTTP hammer starves the processes
                # it is testing.
                time.sleep(0.05)
                return
            except Exception:
                if time.monotonic() > deadline:
                    self.lost += 1
                    raise AssertionError(
                        f"serve request lost after {attempt} attempts"
                    )
                time.sleep(1.0)
                try:
                    self.addr = self.addr_fn() or self.addr
                except Exception:
                    pass  # control plane mid-bounce: retry the old address


def _collect_flight(report: Dict, flight_dir: str) -> int:
    """Fold the flight-recorder dump headers into the report; returns the
    dump count."""
    from ray_tpu._private import telemetry

    dumps = telemetry.collect_dumps(flight_dir)
    by_reason: Dict[str, int] = {}
    for d in dumps:
        key = d.get("reason", "?")
        by_reason[key] = by_reason.get(key, 0) + 1
    report["flight_recorder"] = {
        "dir": flight_dir,
        "dumps": len(dumps),
        "by_reason": by_reason,
        "processes": sorted({d.get("proc", "?") for d in dumps}),
    }
    return len(dumps)


def _count_log(path: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    try:
        with open(path) as f:
            for ln in f:
                ln = ln.strip()
                if ln:
                    counts[ln] = counts.get(ln, 0) + 1
    except FileNotFoundError:
        pass
    return counts


def run_soak(
    duration: float = 75.0,
    seed: int = 7,
    spec: str = DEFAULT_SPEC,
    out: Optional[str] = None,
    use_serve: bool = True,
    num_cpus: int = 4,
    watch_locks: bool = True,
) -> Dict:
    from ray_tpu._private import faults, lock_watchdog
    from ray_tpu._private.head import launch_head_subprocess

    faults.configure(spec, seed)  # fail LOUDLY on a typo'd plan, up front
    faults.disable()  # the driver itself stays clean; children get the env

    workdir = tempfile.mkdtemp(prefix=f"chaos-soak-{seed}-")
    log_path = os.path.join(workdir, "executions.log")
    # Unique per run: session names key the shared /tmp log + store dirs,
    # and a reused name would interleave a previous soak's state.
    session = f"chaos{seed}x{os.getpid():x}"
    saved_env = {
        k: os.environ.get(k)
        for k in (
            "RAY_TPU_FAULT_SPEC",
            "RAY_TPU_FAULT_SEED",
            "RAY_TPU_RECONNECT_WINDOW_S",
            "RAY_TPU_LOCK_WATCHDOG",
            "RAY_TPU_LOCK_WATCHDOG_DIR",
            "RAY_TPU_LOCK_HOLD_S",
            "RAY_TPU_TRACE",
            "RAY_TPU_FLIGHT_DIR",
            "RAY_TPU_METRICS_PUSH_MS",
            "RAY_TPU_PROF_HZ",
            "RAY_TPU_OBJECT_TRANSFER_CHUNK_BYTES",
            "RAY_TPU_RELAY_FANOUT",
        )
    }
    os.environ["RAY_TPU_FAULT_SPEC"] = spec
    os.environ["RAY_TPU_FAULT_SEED"] = str(seed)
    os.environ["RAY_TPU_RECONNECT_WINDOW_S"] = "45"
    # ISSUE 12: small transfer chunks keep every broadcast multi-chunk, so
    # mid-relay kill windows stay wide and relays genuinely pipeline.
    os.environ.setdefault("RAY_TPU_OBJECT_TRANSFER_CHUNK_BYTES", "262144")
    # relay_fanout=1 makes every multi-node pull a CHAIN (the 2nd puller
    # feeds off the 1st's in-flight board), so relays form with just two
    # daemon nodes — the shared 1-vCPU box can't afford the node count a
    # bushier tree would need to exercise the relay path.
    os.environ.setdefault("RAY_TPU_RELAY_FANOUT", "1")
    # FULL telemetry plane on across every process of the soak cluster
    # (ISSUE 6 acceptance: the soak passes with push + spans + flight
    # recorder enabled, and every fault-plane kill leaves a flight dump
    # behind — failures become diagnosable without a replay).
    flight_dir = os.path.join(workdir, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_FLIGHT_DIR"] = flight_dir
    os.environ.setdefault("RAY_TPU_METRICS_PUSH_MS", "1000")
    # ISSUE 10: the sampling profiler runs HOT through the whole soak in
    # every process (head, workers, daemon autostart via
    # telemetry.install) — head kills must not wedge it, and every
    # crash dump carries the victim's last collapsed-stack snapshot.
    os.environ.setdefault("RAY_TPU_PROF_HZ", "25")
    watchdog_dir = os.path.join(workdir, "watchdog")
    if watch_locks:
        # Lock watchdog on across EVERY process of the soak cluster
        # (children inherit the env; the driver flips its already-imported
        # module gate directly).  Reports land in watchdog_dir per pid and
        # any report fails the soak — order inversions and long holds must
        # not ride along under chaos.  Hold threshold is looser than the
        # 1s default: a 4-CPU CI box under storm-level GIL contention
        # stretches legitimate dispatch holds.
        os.makedirs(watchdog_dir, exist_ok=True)
        os.environ["RAY_TPU_LOCK_WATCHDOG"] = "1"
        os.environ["RAY_TPU_LOCK_WATCHDOG_DIR"] = watchdog_dir
        os.environ.setdefault("RAY_TPU_LOCK_HOLD_S", "2.0")
        lock_watchdog._enable_for_tests(True)

    report: Dict = {
        "seed": seed,
        "spec": spec,
        "duration_s": duration,
        "kills": {"head": 0, "daemon": 0},
        "lock_watchdog": {"enabled": watch_locks, "reports": []},
        "result": "FAIL",
    }
    head = daemon = None
    relay_daemons: Dict[str, subprocess.Popen] = {}
    serve_mod = None
    stop = threading.Event()
    loads = []
    try:
        head, head_json = launch_head_subprocess(
            workdir, num_cpus=num_cpus, session=session
        )
        daemon = _launch_daemon(head_json, "soak-d1", num_cpus)
        # One extra RELAY node (ISSUE 12): the broadcast workload's
        # SPREAD landings pull one head-store object onto both daemon
        # nodes at once; with relay_fanout=1 the second puller MUST
        # chain off the first's in-flight board — and the
        # transfer.chunk_relay clause kills whichever daemon is serving
        # a mid-flight relay.
        relay_daemons.update(
            {"soak-b1": _launch_daemon(head_json, "soak-b1", 2,
                                       spec_override=RELAY_SPEC)}
        )
        relay_gen = {"soak-b1": 1}
        report["kills"]["relay_daemon"] = 0

        def check_relay_daemons(draining: bool) -> None:
            for slot, proc in list(relay_daemons.items()):
                if proc.poll() is None:
                    continue
                report["kills"]["relay_daemon"] += 1
                relay_gen[slot] += 1
                nid = f"{slot}g{relay_gen[slot]}"
                note(
                    f"relay daemon {slot} died (kill "
                    f"#{report['kills']['relay_daemon']}); relaunching as {nid}"
                )
                relay_daemons[slot] = _launch_daemon(
                    head_json, nid, 2,
                    spec_override="" if draining else RELAY_SPEC,
                )

        ray_tpu.init(address=head_json)

        if use_serve:
            from ray_tpu import serve as serve_mod

            serve_mod.start(http_options={"host": "127.0.0.1", "port": 0})

            @serve_mod.deployment(
                name="soak",
                num_replicas=2,
                ray_actor_options={"max_restarts": 100},
            )
            def soak_dep(body=None):
                return {"ok": True}

            serve_mod.run(soak_dep.bind())
            addr = serve_mod.get_http_address()

        loads = [
            _ChainLoad(stop, log_path),
            _ActorLoad(stop, log_path),
            _AnonLoad(stop, log_path),
            _BroadcastLoad(stop, log_path),
        ]
        if use_serve:
            loads.append(_ServeLoad(stop, addr, serve_mod.get_http_address))

        # ---- supervise the schedule window: the SPEC does the killing;
        # the harness only resurrects control-plane processes.
        t0 = time.monotonic()
        _Workload.t0 = t0
        for w in loads:
            w.start()

        def note(msg):
            print(f"[soak t={time.monotonic() - t0:6.1f}s] {msg}", flush=True)

        daemon_n = 1
        while time.monotonic() - t0 < duration:
            time.sleep(0.5)
            draining = time.monotonic() - t0 > duration - 10
            if head.poll() is not None:
                report["kills"]["head"] += 1
                if draining:
                    # Quiescence: relaunches near/after the end come up
                    # with the fault plan stripped.
                    os.environ.pop("RAY_TPU_FAULT_SPEC", None)
                note(f"head died (kill #{report['kills']['head']}); relaunching")
                head, _ = launch_head_subprocess(
                    workdir, num_cpus=num_cpus, session=session
                )
                note("head relaunched")
            if daemon.poll() is not None:
                report["kills"]["daemon"] += 1
                daemon_n += 1
                if draining:
                    os.environ.pop("RAY_TPU_FAULT_SPEC", None)
                note(f"daemon died (kill #{report['kills']['daemon']}); "
                     f"relaunching as soak-d{daemon_n}")
                daemon = _launch_daemon(head_json, f"soak-d{daemon_n}", num_cpus)
            check_relay_daemons(draining)
            dead = [w for w in loads if w.failure]
            if dead:
                note(f"workload failure: {[(w.name, w.failure) for w in dead]}")
                break

        # ---- drain: stop the storm but KEEP SUPERVISING — surviving
        # processes still carry live clauses (each head incarnation crashes
        # at its own t=30), and a death with nobody resurrecting it would
        # strand the workloads' final operations.  Relaunches from here on
        # come up with the fault plan stripped.
        os.environ.pop("RAY_TPU_FAULT_SPEC", None)
        stop.set()
        drain_deadline = time.monotonic() + 300
        while (
            any(w.is_alive() for w in loads)
            and time.monotonic() < drain_deadline
        ):
            time.sleep(0.5)
            if head.poll() is not None:
                report["kills"]["head"] += 1
                note("head died during drain; relaunching clean")
                head, _ = launch_head_subprocess(
                    workdir, num_cpus=num_cpus, session=session
                )
            if daemon.poll() is not None:
                report["kills"]["daemon"] += 1
                daemon_n += 1
                note(f"daemon died during drain; relaunching as soak-d{daemon_n}")
                daemon = _launch_daemon(head_json, f"soak-d{daemon_n}", num_cpus)
            check_relay_daemons(True)
        for w in loads:
            w.join(timeout=10)
            if w.is_alive():
                raise AssertionError(f"[{w.name}] never drained (wedged op)")
        for w in loads:
            if w.failure:
                raise AssertionError(f"[{w.name}] {w.failure}")
        if head.poll() is not None:
            head, _ = launch_head_subprocess(
                workdir, num_cpus=num_cpus, session=session
            )
        # A clean round on the post-storm cluster: convergence, not luck.
        final = ray_tpu.get(
            [
                fold.remote(produce.remote(i, "final", log_path), i, "final",
                            log_path)
                for i in range(CHAIN_WIDTH)
            ],
            timeout=240,
        )
        for i, a in enumerate(final):
            assert int(a[0]) == i * ARR + i, (
                "post-storm cluster did not converge to correct results"
            )

        # ---- memory introspection: the object ledger must CONVERGE to
        # zero leak suspects after every kill the storm threw (worker
        # crashes mid-hold leave dead-holder suspects; the reclaim sweep
        # must clear them and free the bytes).  Polled: reclaim grace +
        # final refs_push ticks need a beat to land.
        from ray_tpu.util import state as state_api

        mem = None
        # Budget: worst-case orphan path is leak_age (10s) + orphan grace
        # (20s) + push/tick lag before a drain-era orphan is reclaimed.
        mem_deadline = time.monotonic() + 90
        while time.monotonic() < mem_deadline:
            try:
                mem = state_api.memory_summary(top=0)
            except Exception:
                time.sleep(1.0)
                continue
            if mem["leak_suspects"] == 0:
                break
            time.sleep(1.0)
        report["memory"] = {
            "leak_suspects": mem["leak_suspects"] if mem else None,
            "leak_suspect_bytes": mem["leak_suspect_bytes"] if mem else None,
            "objects": mem["objects"] if mem else None,
            "bytes_total": mem["bytes_total"] if mem else None,
            "nodes": mem["nodes"] if mem else None,
        }
        assert mem is not None, "memory_summary unreachable after the storm"
        assert mem["leak_suspects"] == 0, (
            f"object ledger did not converge: {mem['leak_suspects']} leak "
            f"suspects holding {mem['leak_suspect_bytes']} bytes after "
            f"drain: {[r['object_id'] for r in mem['leaks']][:10]}"
        )

        # ---- lease revocation (ISSUE 11): the match=^done crash clause
        # kills workers at their result-send hazard — each victim was an
        # executing LEASEHOLDER (head-side when its task relayed,
        # caller-side when direct), so the storm exercises the
        # crash-revocation path throughout.  The POST-storm incarnation's
        # counters start clean, so drive a small RELAYED burst (SPREAD is
        # direct-ineligible — it must take the head's queued path and
        # grant head-side leases) and then require convergence: every
        # lease revoked or idle-reaped with its resources back in the
        # pool.  A stranded lease would starve the cluster quietly.
        @ray_tpu.remote(max_retries=5, scheduling_strategy="SPREAD")
        def lease_probe(i):
            return i

        probe_out = ray_tpu.get(
            [lease_probe.remote(i) for i in range(16)], timeout=120
        )
        assert probe_out == list(range(16))
        lease_state = None
        lease_deadline = time.monotonic() + 60
        while time.monotonic() < lease_deadline:
            try:
                internal = state_api.telemetry_summary()["internal"]
            except Exception:
                time.sleep(1.0)
                continue
            lease_state = {
                "granted": internal.get("task_leases_granted"),
                "revoked": internal.get("task_leases_revoked"),
                "lease_dispatches": internal.get("lease_dispatches"),
                "live_at_quiesce": internal.get("head_task_leases"),
            }
            if lease_state["live_at_quiesce"] == 0.0:
                break
            time.sleep(1.0)
        report["task_leases"] = lease_state
        assert lease_state is not None, "telemetry unreachable at quiesce"
        assert lease_state["granted"], "storm never exercised a task lease"
        assert lease_state["live_at_quiesce"] == 0.0, (
            f"task leases stranded after the storm: {lease_state}"
        )

        # ---- the ledger: executions within retry budgets, kills fired.
        counts = _count_log(log_path)
        head_kills = report["kills"]["head"]
        # At-least-once bound: system retries per submission, times the
        # driver's counted re-drives, plus the snapshot re-drive a head
        # restart performs.
        budget = (TASK_RETRIES + 1) * (1 + REDRIVES) + head_kills
        over = {k: c for k, c in counts.items() if c > budget}
        assert not over, f"execution counts beyond retry budgets: {over}"
        dup_execs = sum(c - 1 for c in counts.values() if c > 1)
        chains = next(w for w in loads if w.name == "soak-chains")
        actor = next(w for w in loads if w.name == "soak-actor")
        anon = next(w for w in loads if w.name == "soak-anon")
        bcast = next(w for w in loads if w.name == "soak-bcast")
        anon_inits = counts.get("anoninit:0", 0)
        report.update(
            {
                "chain_rounds": chains.iterations,
                "chain_results_checked": chains.iterations * CHAIN_WIDTH,
                "chain_redrives": chains.redrives,
                "actor_calls": actor.iterations,
                "actor_redrives": actor.redrives,
                "anon_actor_calls": anon.iterations,
                "anon_actor_redrives": anon.redrives,
                "anon_actor_restarts": max(anon_inits - 1, 0),
                "broadcast_rounds": bcast.iterations,
                "broadcast_results_checked": bcast.iterations
                * _BroadcastLoad.WIDTH,
                "broadcast_redrives": bcast.redrives,
                "distinct_executions": len(counts),
                "duplicate_executions": dup_execs,
                "execution_budget": budget,
            }
        )
        if use_serve:
            sv = next(w for w in loads if w.name == "soak-serve")
            report["serve"] = {
                "ok": sv.ok, "retried": sv.retried, "lost": sv.lost,
            }
            assert sv.lost == 0, f"{sv.lost} serve requests lost"
        assert chains.iterations >= 3, "soak too short: <3 chain rounds ran"
        assert actor.iterations >= 10, "soak too short: <10 actor calls ran"
        assert head_kills >= 1, "schedule never killed the head"
        assert report["kills"]["daemon"] >= 1, "schedule never killed a daemon"
        assert dup_execs >= 1, (
            "no task was ever re-executed: worker kill clauses never fired"
        )
        # ISSUE 5 acceptance: the anonymous actor was killed (at=29, in
        # the head-kill window), RESTARTED from the restored record
        # (>= 2 inits), and its handle kept serving to the drained end
        # (anon workload finished with zero failures above).  Pre-journal,
        # this workload could not survive the overlap at all.
        assert anon_inits >= 2, (
            "anonymous actor never restarted — the AnonSoak kill clause "
            "never fired or the record did not survive the head bounce"
        )
        assert anon.iterations >= 10, "soak too short: <10 anon-actor calls ran"
        if watch_locks:
            wd = lock_watchdog.collect_dir_reports(watchdog_dir)
            wd.extend(f"driver: {r}" for r in lock_watchdog.reports())
            report["lock_watchdog"]["reports"] = wd
            assert not wd, f"lock watchdog reports under chaos: {wd}"
        # Flight recorder: every fault-plane crash dumped its ring.  The
        # schedule provably killed processes (asserted above), so dumps
        # MUST exist — a zero here means the recorder regressed.
        dumps = _collect_flight(report, flight_dir)
        assert dumps, (
            "fault-plane kills fired but produced no flight-recorder dumps"
        )
        # ISSUE 12 acceptance: the broadcast workload ran through the
        # storm with every sum exact, AND the transfer.chunk_relay clause
        # provably crash-killed a daemon MID-RELAY of a live broadcast
        # (its flight dump names the point) — the downstream pullers fell
        # back to sealed sources / re-planned with zero lost results, and
        # the ledger's leak sweep (asserted above) covered the broadcast
        # objects too.
        from ray_tpu._private import telemetry as _telemetry

        relay_kill_dumps = [
            d
            for d in _telemetry.collect_dumps(flight_dir)
            if "transfer.chunk_relay" in str(d.get("reason", ""))
        ]
        report["relay_kills_mid_broadcast"] = len(relay_kill_dumps)
        assert bcast.iterations >= 3, "soak too short: <3 broadcast rounds ran"
        assert relay_kill_dumps, (
            "transfer.chunk_relay kill clause never fired — no daemon was "
            "mid-relay during the storm (is the pipelined broadcast "
            "actually on?)"
        )
        # ISSUE 10 acceptance: the profiler sampled through the chaos —
        # crash dumps carry collapsed-stack snapshots (prof_stacks > 0 in
        # the dump header), so a killed process records where its time
        # went, not just what it did.
        all_dumps = _telemetry.collect_dumps(flight_dir)
        prof_dumps = [d for d in all_dumps if d.get("prof_stacks", 0) > 0]
        report["profiler"] = {
            "hz": float(os.environ.get("RAY_TPU_PROF_HZ", "0")),
            "dumps_with_prof_snapshot": len(prof_dumps),
            "dumps_total": len(all_dumps),
        }
        assert prof_dumps, (
            "profiler ran hot through the soak but no flight dump carries "
            "a collapsed-stack snapshot (prof_stacks == 0 everywhere)"
        )
        report["result"] = "PASS"
        return report
    except BaseException:
        # Attach the flight-recorder dumps to the failing report: what
        # each killed/crashed process saw in its last seconds, without a
        # replay (the dump files stay under the kept session dir).
        try:
            _collect_flight(report, flight_dir)
        except Exception:
            pass
        print(
            "\n=== CHAOS SOAK FAILED — replay with:\n"
            f"    python scripts/chaos_soak.py --seed {seed} "
            f"--duration {duration} --spec '{spec}'\n"
            f"    (session dir kept at {workdir}; flight-recorder dumps "
            f"under {flight_dir})",
            file=sys.stderr,
            flush=True,
        )
        raise
    finally:
        stop.set()
        if serve_mod is not None:
            try:
                serve_mod.shutdown()
            except Exception:
                pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        for proc in (daemon, head, *relay_daemons.values()):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if watch_locks:
            lock_watchdog._enable_for_tests(
                os.environ.get("RAY_TPU_LOCK_WATCHDOG") == "1"
            )
        if out and report.get("result"):
            with open(out, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
                f.write("\n")


# ---------------------------------------------------------------------------
# Elastic-trainer scenario (ISSUE 16): gang re-mesh under a host SIGKILL.
# ---------------------------------------------------------------------------


def _elastic_train_fn(config):
    """Elastic SPMD soak loop: one checkpointed step at a time.  World
    size is whatever gang the driver respawned us into (2 -> 1 -> 2 over
    the scenario); every step reports WITH a checkpoint, so a re-mesh
    loses at most the in-flight step plus the undrained report window."""
    import time as _t

    from ray_tpu.train import session

    ckpt = session.get_checkpoint()
    start = int(ckpt["step"]) + 1 if ckpt else 0
    rank = session.get_world_rank()
    world = session.get_world_size()
    for s in range(start, int(config["steps"])):
        _append(config["log_path"], f"trainstep:{rank}/{world}:{s}")
        _t.sleep(float(config["step_s"]))
        session.report({"step": s, "world": world}, checkpoint={"step": s})


class _TrainerLoad(threading.Thread):
    """Runs fit() off the supervisor thread; remembers result/failure."""

    def __init__(self, steps: int, step_s: float, log_path: str):
        super().__init__(daemon=True, name="soak-trainer")
        self.steps = steps
        self.step_s = step_s
        self.log_path = log_path
        self.result = None
        self.failure: Optional[str] = None

    def run(self):
        try:
            from ray_tpu.air.config import (
                FailureConfig,
                RunConfig,
                ScalingConfig,
            )
            from ray_tpu.train.backend import BackendConfig
            from ray_tpu.train.data_parallel_trainer import DataParallelTrainer

            trainer = DataParallelTrainer(
                _elastic_train_fn,
                train_loop_config={
                    "steps": self.steps,
                    "step_s": self.step_s,
                    "log_path": self.log_path,
                },
                # Plain backend: the elasticity under test is the gang +
                # worker-group machinery, not jax multiprocess (which the
                # CPU backend cannot run anyway).
                backend_config=BackendConfig(),
                scaling_config=ScalingConfig(
                    num_workers=2,
                    resources_per_worker={"CPU": 1.0, "gang": 1.0},
                    placement_strategy="MESH",
                ),
                run_config=RunConfig(failure_config=FailureConfig(max_failures=2)),
            )
            self.result = trainer.fit()
            if self.result.error is not None:
                self.failure = f"fit() returned error: {self.result.error}"
        except BaseException as e:  # noqa: BLE001 — a soak failure is data
            import traceback

            self.failure = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"


def _train_step_counts(log_path: str) -> Dict[tuple, int]:
    """{(rank, world, step): executions} from the trainstep ledger."""
    out: Dict[tuple, int] = {}
    for line, n in _count_log(log_path).items():
        if not line.startswith("trainstep:"):
            continue
        rw, step = line.split(":")[1:3]
        rank, world = rw.split("/")
        out[(int(rank), int(world), int(step))] = n
    return out


def _steps_at_world(counts: Dict[tuple, int], world: int) -> set:
    return {step for (_r, w, step) in counts if w == world}


def run_trainer_soak(
    seed: int = 11,
    out: Optional[str] = None,
    num_cpus: int = 2,
    watch_locks: bool = True,
    steps: int = 140,
    step_s: float = 0.2,
    wait_s: float = 4.0,
) -> Dict:
    """The elastic SPMD gang-re-mesh scenario (report: CHAOS_r11.json).

    Timeline: trainer runs on a 2-host MESH gang -> the harness SIGKILLs
    gang host B mid-step -> the head withdraws the gang, waits wait_s for
    a replacement, then re-plans a 1-host box -> the trainer resumes from
    the latest checkpoint at world size 1 -> the harness launches a
    replacement host at B's coordinate -> the sweep flags scale-up, the
    trainer re-meshes back to world size 2 and finishes every step."""
    from ray_tpu._private import lock_watchdog
    from ray_tpu._private.head import launch_head_subprocess
    from ray_tpu.util import tracing

    workdir = tempfile.mkdtemp(prefix=f"chaos-trainer-{seed}-")
    log_path = os.path.join(workdir, "executions.log")
    session = f"remesh{seed}x{os.getpid():x}"
    saved_env = {
        k: os.environ.get(k)
        for k in (
            "RAY_TPU_FAULT_SPEC",
            "RAY_TPU_REMESH_WAIT_S",
            "RAY_TPU_TRACE",
            "RAY_TPU_FLIGHT_DIR",
            "RAY_TPU_LOCK_WATCHDOG",
            "RAY_TPU_LOCK_WATCHDOG_DIR",
            "RAY_TPU_LOCK_HOLD_S",
            "RAY_TPU_METRICS_PUSH_MS",
        )
    }
    # No ambient fault storm: the chaos here is the host SIGKILL itself
    # (plus full telemetry/watchdog planes, which must stay clean).
    os.environ.pop("RAY_TPU_FAULT_SPEC", None)
    os.environ["RAY_TPU_REMESH_WAIT_S"] = str(wait_s)
    flight_dir = os.path.join(workdir, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_FLIGHT_DIR"] = flight_dir
    os.environ.setdefault("RAY_TPU_METRICS_PUSH_MS", "1000")
    tracing.enable_tracing()  # driver process: spans for the remesh stages
    watchdog_dir = os.path.join(workdir, "watchdog")
    if watch_locks:
        os.makedirs(watchdog_dir, exist_ok=True)
        os.environ["RAY_TPU_LOCK_WATCHDOG"] = "1"
        os.environ["RAY_TPU_LOCK_WATCHDOG_DIR"] = watchdog_dir
        os.environ.setdefault("RAY_TPU_LOCK_HOLD_S", "2.0")
        lock_watchdog._enable_for_tests(True)

    report: Dict = {
        "seed": seed,
        "scenario": "elastic-trainer",
        "steps": steps,
        "step_s": step_s,
        "remesh_wait_s": wait_s,
        "kills": {"gang_daemon": 0},
        "lock_watchdog": {"enabled": watch_locks, "reports": []},
        "result": "FAIL",
    }
    head = gang_a = gang_b = None
    import ray_tpu

    try:
        head, head_json = launch_head_subprocess(
            workdir, num_cpus=num_cpus, session=session
        )
        # Two gang hosts on a 1-D mesh (coordinates "0" and "1"); the
        # custom "gang" resource pins train workers onto them.
        gang_a = _launch_daemon(head_json, "gang-a", 2, spec_override="",
                                resources={"gang": 1.0},
                                labels={"mesh_coord": "0"})
        gang_b = _launch_daemon(head_json, "gang-b", 2, spec_override="",
                                resources={"gang": 1.0},
                                labels={"mesh_coord": "1"})
        ray_tpu.init(address=head_json)

        t0 = time.monotonic()

        def note(msg):
            print(f"[remesh t={time.monotonic() - t0:6.1f}s] {msg}",
                  flush=True)

        def wait_for(cond, what, deadline_s):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if trainer.failure:
                    raise AssertionError(f"trainer failed: {trainer.failure}")
                if cond():
                    return time.monotonic() - t0
                time.sleep(0.25)
            raise AssertionError(f"timed out after {deadline_s}s waiting "
                                 f"for {what}")

        trainer = _TrainerLoad(steps, step_s, log_path)
        trainer.start()

        # Phase 1: the full gang trains.
        wait_for(
            lambda: len(_steps_at_world(_train_step_counts(log_path), 2)) >= 10,
            "10 steps at world size 2", 120,
        )
        # Phase 2: SIGKILL gang host B mid-step (its PDEATHSIG-armed
        # train worker dies with it — a whole-host loss, not a clean
        # actor exit).
        note("SIGKILL gang-b daemon (host loss mid-step)")
        gang_b.kill()
        report["kills"]["gang_daemon"] += 1
        t_kill = time.monotonic() - t0
        # Phase 3: the gang must re-form at N-1 and RESUME training.
        steps_before_kill = _steps_at_world(_train_step_counts(log_path), 2)
        t_world1 = wait_for(
            lambda: len(_steps_at_world(_train_step_counts(log_path), 1)) >= 3,
            "training to resume at world size 1",
            wait_s + 60,
        )
        note(f"re-meshed at N-1, training resumed ({t_world1 - t_kill:.1f}s "
             "after the kill)")
        # Phase 4: a replacement host joins at B's coordinate; the sweep
        # flags scale-up and the trainer re-meshes back to full size.
        gang_b = _launch_daemon(head_json, "gang-b2", 2, spec_override="",
                                resources={"gang": 1.0},
                                labels={"mesh_coord": "1"})
        t_relaunch = time.monotonic() - t0
        note("replacement host gang-b2 launched at mesh_coord 1")
        t_world2 = wait_for(
            lambda: bool(
                _steps_at_world(_train_step_counts(log_path), 2)
                - steps_before_kill
            ),
            "training to scale back to world size 2", 90,
        )
        note(f"scaled back to N ({t_world2 - t_relaunch:.1f}s after the "
             "replacement joined)")
        # Phase 5: run to completion.
        trainer.join(timeout=steps * step_s + 240)
        assert not trainer.is_alive(), "trainer never finished (wedged)"
        assert trainer.failure is None, f"trainer failed: {trainer.failure}"
        result = trainer.result
        t_done = time.monotonic() - t0
        report["timeline"] = {
            "kill_at_s": round(t_kill, 2),
            "world1_resumed_at_s": round(t_world1, 2),
            "replacement_at_s": round(t_relaunch, 2),
            "world2_resumed_at_s": round(t_world2, 2),
            "done_at_s": round(t_done, 2),
            "shrink_recovery_s": round(t_world1 - t_kill, 2),
            "scale_up_recovery_s": round(t_world2 - t_relaunch, 2),
        }

        # ---- zero lost results: every step reported exactly once, in
        # order, across the whole elastic history.
        got = [m["step"] for m in result.metrics_history]
        assert got == list(range(steps)), (
            f"step history wrong: {len(got)} reports, "
            f"missing={sorted(set(range(steps)) - set(got))[:10]}, "
            f"dups={sorted({s for s in got if got.count(s) > 1})[:10]}"
        )
        # ---- the gang provably shrank and recovered: world sizes form
        # exactly the 2 -> 1 -> 2 envelope.
        worlds = [m["world"] for m in result.metrics_history]
        segments = [w for i, w in enumerate(worlds)
                    if i == 0 or worlds[i - 1] != w]
        assert segments == [2, 1, 2], (
            f"world-size history {segments} != [2, 1, 2]"
        )
        report["world_segments"] = segments
        # ---- bounded lost steps: re-executed (checkpointed-past) work
        # per re-mesh is at most the in-flight step + the undrained
        # report window per rank; across two episodes a generous cap
        # still proves checkpoint resume did its job.
        counts = _train_step_counts(log_path)
        by_rank_step: Dict[tuple, int] = {}
        for (rank, _w, step), n in counts.items():
            by_rank_step[(rank, step)] = by_rank_step.get((rank, step), 0) + n
        lost = sum(n - 1 for n in by_rank_step.values() if n > 1)
        report["lost_steps_reexecuted"] = lost
        assert lost <= 24, (
            f"{lost} steps re-executed — checkpoint resume is not bounding "
            "lost work"
        )
        # ---- recovery attribution: every stage of both episodes landed
        # in the remesh_seconds histogram (driver-side — fit() ran here).
        from ray_tpu._private import telemetry

        snap = telemetry.remesh_histogram().snapshot()
        stages = {dict(k).get("stage"): v for k, v in snap.items()}
        report["remesh_stages"] = {
            s: {"count": v["count"], "sum_s": round(v["sum"], 3)}
            for s, v in sorted(stages.items())
        }
        for stage in ("detect", "teardown", "replan", "respawn", "resume",
                      "total"):
            assert stages.get(stage, {}).get("count", 0) >= 2, (
                f"remesh stage {stage!r} missing from the histogram: "
                f"{report['remesh_stages']} (expected one sample per "
                "episode, 2 episodes)"
            )
        # Every episode's end-to-end recovery fits the 60s deadline (the
        # histogram's >60s buckets stay empty).
        h = telemetry.remesh_histogram()
        over_idx = h.boundaries.index(60.0)
        total_buckets = stages["total"]["buckets"]
        assert sum(total_buckets[over_idx + 1:]) == 0, (
            f"a re-mesh took >60s: total buckets {total_buckets} over "
            f"boundaries {h.boundaries}"
        )
        # ---- the ledger converges: no leaked objects from the killed
        # host's in-flight work.
        from ray_tpu.util import state as state_api

        mem = None
        mem_deadline = time.monotonic() + 90
        while time.monotonic() < mem_deadline:
            try:
                mem = state_api.memory_summary(top=0)
            except Exception:
                time.sleep(1.0)
                continue
            if mem["leak_suspects"] == 0:
                break
            time.sleep(1.0)
        report["memory"] = {
            "leak_suspects": mem["leak_suspects"] if mem else None,
            "objects": mem["objects"] if mem else None,
        }
        assert mem is not None and mem["leak_suspects"] == 0, (
            f"object ledger did not converge after the host kill: {mem}"
        )
        if watch_locks:
            wd = lock_watchdog.collect_dir_reports(watchdog_dir)
            wd.extend(f"driver: {r}" for r in lock_watchdog.reports())
            report["lock_watchdog"]["reports"] = wd
            assert not wd, f"lock watchdog reports under re-mesh: {wd}"
        report["result"] = "PASS"
        return report
    except BaseException:
        print(
            "\n=== ELASTIC-TRAINER SOAK FAILED — replay with:\n"
            f"    python scripts/chaos_soak.py --trainer --seed {seed}\n"
            f"    (session dir kept at {workdir})",
            file=sys.stderr,
            flush=True,
        )
        raise
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        for proc in (gang_a, gang_b, head):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if watch_locks:
            lock_watchdog._enable_for_tests(
                os.environ.get("RAY_TPU_LOCK_WATCHDOG") == "1"
            )
        if out and report.get("result"):
            with open(out, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
                f.write("\n")


def run_autoscale_soak(
    seed: int = 12,
    out: Optional[str] = None,
    watch_locks: bool = True,
) -> Dict:
    """The elastic-capacity scenario (report: CHAOS_r12.json).

    Timeline: the head boots with the demand-driven autoscaler ON
    (min=1/max=4, LocalProcessProvider) -> serve replicas + a 1-CPU task
    wave push demand and the fleet grows to max -> sole-copy shm objects
    are pinned onto two autoscaled nodes -> node A is drained and its
    daemon SIGKILLed MID-EVACUATION (the spec delays every evacuation
    pull, widening the window) -> the death path + lineage re-derive A's
    results -> node B is drained and the HEAD is SIGKILLed mid-drain ->
    the relaunched head replays every journaled lifecycle transition,
    the resumed reconciler finishes B's evacuation with a clean ledger
    (zero lost bytes: B's producers run exactly once) -> the idle fleet
    drains itself back to the floor.  PASS requires zero lost results,
    zero lost sole-copy bytes, a converged object ledger, and a silent
    lock watchdog."""
    from ray_tpu._private import faults, lock_watchdog
    from ray_tpu._private.head import launch_head_subprocess
    from ray_tpu.util import state as state_api
    from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

    # The only spec clause: stretch each evacuation pull so the daemon
    # SIGKILL and the head SIGKILL both land INSIDE the evacuation loop.
    spec = "node.evacuate:delay=0.3"
    faults.configure(spec, seed)
    faults.disable()  # driver stays clean; the head enables from env

    workdir = tempfile.mkdtemp(prefix=f"chaos-autoscale-{seed}-")
    log_path = os.path.join(workdir, "executions.log")
    session = f"elastic{seed}x{os.getpid():x}"
    saved_env = {
        k: os.environ.get(k)
        for k in (
            "RAY_TPU_FAULT_SPEC",
            "RAY_TPU_FAULT_SEED",
            "RAY_TPU_RECONNECT_WINDOW_S",
            "RAY_TPU_TRACE",
            "RAY_TPU_FLIGHT_DIR",
            "RAY_TPU_LOCK_WATCHDOG",
            "RAY_TPU_LOCK_WATCHDOG_DIR",
            "RAY_TPU_LOCK_HOLD_S",
            "RAY_TPU_METRICS_PUSH_MS",
            "RAY_TPU_AUTOSCALE_ENABLED",
            "RAY_TPU_AUTOSCALE_INTERVAL_S",
            "RAY_TPU_AUTOSCALE_MIN_NODES",
            "RAY_TPU_AUTOSCALE_MAX_NODES",
            "RAY_TPU_AUTOSCALE_UP_WAIT_S",
            "RAY_TPU_AUTOSCALE_IDLE_S",
            "RAY_TPU_AUTOSCALE_LAUNCH_TIMEOUT_S",
            "RAY_TPU_AUTOSCALE_DRAIN_TIMEOUT_S",
        )
    }
    os.environ["RAY_TPU_FAULT_SPEC"] = spec
    os.environ["RAY_TPU_FAULT_SEED"] = str(seed)
    os.environ["RAY_TPU_RECONNECT_WINDOW_S"] = "45"
    # Elastic knobs: every head incarnation (launch_head_subprocess copies
    # os.environ) runs the embedded reconciler with the same aggressive
    # cadence, so the post-bounce head resumes B's drain on its own.
    os.environ["RAY_TPU_AUTOSCALE_ENABLED"] = "1"
    os.environ["RAY_TPU_AUTOSCALE_INTERVAL_S"] = "0.25"
    os.environ["RAY_TPU_AUTOSCALE_MIN_NODES"] = "1"
    os.environ["RAY_TPU_AUTOSCALE_MAX_NODES"] = "4"
    os.environ["RAY_TPU_AUTOSCALE_UP_WAIT_S"] = "0.5"
    # Long enough that autonomous idle-drain never races the scripted
    # chaos on A/B, short enough that wind-down fits the soak budget.
    os.environ["RAY_TPU_AUTOSCALE_IDLE_S"] = "15"
    os.environ["RAY_TPU_AUTOSCALE_LAUNCH_TIMEOUT_S"] = "20"
    os.environ["RAY_TPU_AUTOSCALE_DRAIN_TIMEOUT_S"] = "6"
    flight_dir = os.path.join(workdir, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_FLIGHT_DIR"] = flight_dir
    os.environ.setdefault("RAY_TPU_METRICS_PUSH_MS", "1000")
    watchdog_dir = os.path.join(workdir, "watchdog")
    if watch_locks:
        os.makedirs(watchdog_dir, exist_ok=True)
        os.environ["RAY_TPU_LOCK_WATCHDOG"] = "1"
        os.environ["RAY_TPU_LOCK_WATCHDOG_DIR"] = watchdog_dir
        os.environ.setdefault("RAY_TPU_LOCK_HOLD_S", "2.0")
        lock_watchdog._enable_for_tests(True)

    report: Dict = {
        "seed": seed,
        "scenario": "elastic-autoscale",
        "spec": spec,
        "kills": {"head": 0, "daemon": 0},
        "lock_watchdog": {"enabled": watch_locks, "reports": []},
        "result": "FAIL",
    }
    RANK = {
        "REQUESTED": 0, "STARTING": 1, "ACTIVE": 2,
        "DRAINING": 3, "DEPARTED": 4,
    }
    PINS = 4
    head = None
    daemon_pids: Dict[str, int] = {}
    import ray_tpu

    try:
        head, head_json = launch_head_subprocess(
            workdir, num_cpus=2, session=session
        )
        ray_tpu.init(address=head_json)
        t0 = time.monotonic()

        def note(msg):
            print(f"[elastic t={time.monotonic() - t0:6.1f}s] {msg}",
                  flush=True)

        def _req(op, payload=None):
            from ray_tpu._private.worker_proc import get_worker_runtime

            return get_worker_runtime().request(op, payload)

        def lifecycle() -> Dict[str, Dict]:
            try:
                return _req("node_lifecycle")
            except Exception:
                return {}  # head mid-bounce: answer again next poll

        def managed(*states) -> Dict[str, Dict]:
            return {
                nid: rec
                for nid, rec in lifecycle().items()
                if rec.get("src") == "autoscaler"
                and (not states or rec.get("state") in states)
            }

        def wait_for(cond, what, deadline_s):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                try:
                    # Conditions poll THROUGH head bounces: a dropped
                    # request is "not yet", never a verdict.
                    if cond():
                        return time.monotonic() - t0
                except Exception:
                    pass
                time.sleep(0.25)
            raise AssertionError(
                f"timed out after {deadline_s}s waiting for {what}"
            )

        def _counts(prefix: str) -> Dict[str, int]:
            c: Dict[str, int] = {}
            try:
                with open(log_path) as f:
                    for line in f:
                        line = line.strip()
                        if line.startswith(prefix + ":"):
                            c[line] = c.get(line, 0) + 1
            except FileNotFoundError:
                pass
            return c

        def _note_pids():
            for row in state_api.list_nodes():
                if row.get("daemon_pid"):
                    daemon_pids[row["node_id"]] = row["daemon_pid"]

        # ---- phase 1: the floor launch (min_nodes=1, zero demand).
        t_floor = wait_for(
            lambda: len(managed("ACTIVE")) >= 1, "the floor node", 30
        )
        note("floor node ACTIVE")

        # ---- phase 2: demand wave.  Serve replica targets land in the
        # demand summary; a 1-CPU task wave outlives the up-wait window
        # and the reconciler grows the fleet to max.
        from ray_tpu import serve as serve_mod

        serve_mod.start(http_options={"host": "127.0.0.1", "port": 0})

        @serve_mod.deployment(
            name="elastic",
            num_replicas=2,
            ray_actor_options={"max_restarts": 100},
        )
        def elastic_dep(body=None):
            return {"ok": True}

        serve_mod.run(elastic_dep.bind())
        wait_for(
            lambda: "elastic" in state_api.demand_summary()["serve_targets"],
            "serve replica targets in the demand summary", 30,
        )
        note("serve targets visible in demand summary")

        wave_refs = [wave_work.remote(i, 1.5, log_path) for i in range(24)]
        t_max = wait_for(
            lambda: len(managed("ACTIVE")) >= 4,
            "the fleet to reach max_nodes=4", 90,
        )
        note(f"fleet at max ({t_max - t_floor:.1f}s after the floor)")
        _note_pids()
        wave_out = ray_tpu.get(wave_refs, timeout=240)
        assert sorted(wave_out) == list(range(24)), (
            f"lost wave results: {sorted(wave_out)}"
        )
        del wave_refs, wave_out
        serve_mod.shutdown()  # replicas off the fleet before the chaos

        # ---- phase 3: pin sole-copy shm objects onto two autoscaled
        # nodes (soft affinity; ARR int64 payloads are store-sealed).
        fleet = sorted(managed("ACTIVE"))
        assert len(fleet) >= 3, f"fleet shrank early: {fleet}"
        node_a, node_b = fleet[0], fleet[1]

        def _fleet_idle():
            # Serve teardown + wave lease expiry are asynchronous; pins
            # only target a node reliably once its CPU is back in the pool.
            rws = {r["node_id"]: r for r in state_api.list_nodes()}
            return all(
                rws[nid]["available"].get("CPU")
                == rws[nid]["resources"].get("CPU")
                for nid in fleet
            )

        wait_for(_fleet_idle, "the fleet to go idle before pinning", 30)

        def _pin(nid, tag):
            # SERIAL submissions: the target has 1 CPU, and soft affinity
            # spills a busy node's overflow elsewhere — one in flight at
            # a time keeps every pin (and its lease reuse) on the target.
            strat = NodeAffinitySchedulingStrategy(nid, soft=True)
            refs = []
            for i in range(PINS):
                r = produce.options(scheduling_strategy=strat).remote(
                    i, tag, log_path
                )
                ready, _ = ray_tpu.wait(
                    [r], timeout=60,
                    fetch_local=False,  # a driver fetch breaks sole-copy-ness
                )
                assert ready, f"pin {tag}:{i} did not finish"
                refs.append(r)
            return refs

        pin_a = _pin(node_a, "pinA")
        pin_b = _pin(node_b, "pinB")
        rows = {r["node_id"]: r for r in state_api.list_nodes()}
        for nid in (node_a, node_b):
            assert rows[nid]["store_bytes"] >= PINS * ARR * 8, (
                f"pins did not land on {nid}: {rows[nid]}"
            )
        _note_pids()

        # ---- phase 4: drain A, SIGKILL its daemon mid-evacuation.  The
        # drain must fall back to the DEATH path: lineage re-derives A's
        # sole copies on the survivors.
        pid_a = rows[node_a]["daemon_pid"]
        assert pid_a, f"no daemon pid for {node_a}"
        assert _req("node_drain", node_a) is True
        wait_for(
            lambda: lifecycle().get(node_a, {}).get("state")
            in ("DRAINING", "DEPARTED"),
            "A's drain to journal", 10,
        )
        time.sleep(0.7)  # quiesce beat + first delayed evacuation pulls
        note(f"SIGKILL {node_a} daemon mid-evacuation")
        os.kill(pid_a, signal.SIGKILL)
        report["kills"]["daemon"] += 1
        wait_for(
            lambda: lifecycle().get(node_a, {}).get("state") == "DEPARTED",
            "A to close DEPARTED via the death path", 30,
        )
        rec_a = lifecycle()[node_a]
        assert rec_a.get("reason") == "died", rec_a
        out_a = ray_tpu.get(pin_a, timeout=120)
        for i, arr in enumerate(out_a):
            assert arr.shape == (ARR,) and int(arr[0]) == i, (
                f"pinA[{i}] wrong after mid-evacuation kill"
            )
        report["pin_a_exec_counts"] = _counts("produce:pinA")
        note("A's results re-derived via lineage after the kill")
        del pin_a, out_a

        # ---- phase 5: drain B, SIGKILL the HEAD mid-drain.  The
        # relaunched head must replay every journaled transition and the
        # resumed reconciler must finish B's evacuation losslessly.
        pre = lifecycle()
        assert pre, "lifecycle table empty before the bounce"
        assert _req("node_drain", node_b) is True
        wait_for(
            lambda: lifecycle().get(node_b, {}).get("state") == "DRAINING",
            "B's drain to journal", 10,
        )
        time.sleep(0.6)  # land inside B's delayed evacuation loop
        note("SIGKILL head mid-drain (bounce mid-reconcile)")
        head.kill()
        try:
            head.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        report["kills"]["head"] += 1
        head, _ = launch_head_subprocess(workdir, num_cpus=2, session=session)
        note("head relaunched; waiting for lifecycle replay")
        wait_for(
            lambda: lifecycle().get(node_b, {}).get("state")
            in ("DRAINING", "DEPARTED"),
            "the restored lifecycle table", 60,
        )
        post = lifecycle()
        for nid, rec in pre.items():
            assert nid in post, f"journaled node {nid} lost in the bounce"
            assert RANK[post[nid]["state"]] >= RANK[rec["state"]], (
                f"{nid} regressed across the bounce: "
                f"{rec['state']} -> {post[nid]['state']}"
            )
            if rec.get("src"):
                assert post[nid].get("src") == rec["src"], (nid, post[nid])
        assert post[node_a].get("reason") == "died", post[node_a]
        report["lifecycle_replayed"] = {
            nid: post[nid]["state"] for nid in sorted(pre)
        }
        t_b = wait_for(
            lambda: lifecycle().get(node_b, {}).get("state") == "DEPARTED",
            "the resumed reconciler to finish B's drain", 60,
        )
        rec_b = lifecycle()[node_b]
        assert rec_b.get("reason") == "removed", (
            f"B's drain did not finish cleanly: {rec_b}"
        )
        note(f"B drained clean by the post-bounce reconciler (t={t_b:.1f}s)")
        # The evacuation ledger on the NEW head: B's final pass must
        # report remaining=0 (zero lost sole-copy bytes) and have moved
        # at least one object post-bounce.
        evs = [
            e
            for e in state_api.list_cluster_events(
                limit=200, source="autoscale"
            )
            if e.get("message") == "node evacuation"
            and e.get("node_id") == node_b
        ]
        assert evs, "no evacuation ledger events for B on the new head"
        assert evs[-1].get("remaining") == 0, f"lost bytes on B: {evs[-1]}"
        moved = sum(e.get("moved", 0) for e in evs)
        assert moved >= 1, f"nothing evacuated post-bounce: {evs}"
        report["evacuation"] = {
            "events": len(evs),
            "moved": moved,
            "moved_bytes": sum(e.get("moved_bytes", 0) for e in evs),
            "failed": sum(e.get("failed", 0) for e in evs),
        }
        # Zero lost bytes, PROVEN: B's results come back correct and its
        # producers ran exactly ONCE — the bytes moved, nothing re-ran.
        out_b = ray_tpu.get(pin_b, timeout=120)
        for i, arr in enumerate(out_b):
            assert arr.shape == (ARR,) and int(arr[0]) == i, (
                f"pinB[{i}] wrong after the drained depart"
            )
        cb = _counts("produce:pinB")
        assert len(cb) == PINS and all(v == 1 for v in cb.values()), (
            f"B's producers re-ran — evacuation lost bytes: {cb}"
        )
        report["pin_b_exec_counts"] = cb
        note("B's sole copies survived: values intact, zero re-executions")
        del pin_b, out_b

        # ---- phase 6: wind-down.  With demand gone the reconciler
        # idle-drains the surplus back to the floor on its own.
        t_down = wait_for(
            lambda: len(
                managed("REQUESTED", "STARTING", "ACTIVE", "DRAINING")
            ) <= 1,
            "the fleet to drain back to the floor", 120,
        )
        assert len(managed("ACTIVE")) == 1
        note(f"fleet back at the floor (t={t_down:.1f}s)")
        report["timeline"] = {
            "floor_at_s": round(t_floor, 2),
            "max_fleet_at_s": round(t_max, 2),
            "b_drained_at_s": round(t_b, 2),
            "floor_again_at_s": round(t_down, 2),
        }

        # ---- the stage histogram made it to the pushed-metrics plane.
        def _hist_count():
            agg = state_api.telemetry_summary()["aggregate"]
            return sum(
                v for k, v in agg.items()
                if k.startswith("autoscale_seconds_count")
            )

        wait_for(
            lambda: _hist_count() >= 1,
            "autoscale_seconds samples on the metrics plane", 30,
        )
        report["autoscale_seconds_samples"] = _hist_count()

        # ---- the object ledger converges after both kills.
        mem = None
        mem_deadline = time.monotonic() + 90
        while time.monotonic() < mem_deadline:
            try:
                mem = state_api.memory_summary(top=0)
            except Exception:
                time.sleep(1.0)
                continue
            if mem["leak_suspects"] == 0:
                break
            time.sleep(1.0)
        report["memory"] = {
            "leak_suspects": mem["leak_suspects"] if mem else None,
            "objects": mem["objects"] if mem else None,
        }
        assert mem is not None and mem["leak_suspects"] == 0, (
            f"object ledger did not converge after the chaos: {mem}"
        )

        # ---- every lifecycle state the soak produced is a known state.
        final = lifecycle()
        bad = {
            nid: rec for nid, rec in final.items()
            if rec.get("state") not in RANK
        }
        assert not bad, f"unknown lifecycle states: {bad}"
        report["final_lifecycle"] = {
            nid: {"state": rec["state"], "reason": rec.get("reason")}
            for nid, rec in sorted(final.items())
        }

        if watch_locks:
            wd = lock_watchdog.collect_dir_reports(watchdog_dir)
            wd.extend(f"driver: {r}" for r in lock_watchdog.reports())
            report["lock_watchdog"]["reports"] = wd
            assert not wd, f"lock watchdog reports under autoscale: {wd}"
        report["result"] = "PASS"
        return report
    except BaseException:
        print(
            "\n=== ELASTIC-AUTOSCALE SOAK FAILED — replay with:\n"
            f"    python scripts/chaos_soak.py --autoscale --seed {seed}\n"
            f"    (session dir kept at {workdir})",
            file=sys.stderr,
            flush=True,
        )
        raise
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        if head is not None and head.poll() is None:
            head.terminate()
            try:
                head.wait(timeout=10)
            except subprocess.TimeoutExpired:
                head.kill()
        # Autoscaled daemons are children of (possibly SIGKILLed) head
        # incarnations — reap any stragglers so the box stays clean.
        for nid, pid in daemon_pids.items():
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if watch_locks:
            lock_watchdog._enable_for_tests(
                os.environ.get("RAY_TPU_LOCK_WATCHDOG") == "1"
            )
        if out and report.get("result"):
            with open(out, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
                f.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--duration", type=float, default=75.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--spec", default=DEFAULT_SPEC)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-serve", action="store_true")
    ap.add_argument("--num-cpus", type=int, default=4)
    ap.add_argument("--no-lock-watchdog", action="store_true")
    ap.add_argument(
        "--trainer", action="store_true",
        help="run the elastic SPMD gang re-mesh scenario instead "
             "(report: CHAOS_r11.json)",
    )
    ap.add_argument(
        "--autoscale", action="store_true",
        help="run the elastic-capacity autoscaler scenario instead "
             "(report: CHAOS_r12.json)",
    )
    args = ap.parse_args(argv)
    if args.autoscale:
        report = run_autoscale_soak(
            seed=args.seed if args.seed != 7 else 12,
            out=args.out or "CHAOS_r12.json",
            watch_locks=not args.no_lock_watchdog,
        )
        print(json.dumps(report, indent=1, sort_keys=True))
        return 0
    if args.trainer:
        report = run_trainer_soak(
            seed=args.seed if args.seed != 7 else 11,
            out=args.out or "CHAOS_r11.json",
            num_cpus=args.num_cpus,
            watch_locks=not args.no_lock_watchdog,
        )
        print(json.dumps(report, indent=1, sort_keys=True))
        return 0
    report = run_soak(
        duration=args.duration,
        seed=args.seed,
        spec=args.spec,
        out=args.out,
        use_serve=not args.no_serve,
        num_cpus=args.num_cpus,
        watch_locks=not args.no_lock_watchdog,
    )
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
