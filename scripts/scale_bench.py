"""Scale-envelope benchmarks (ray: release/benchmarks/ many_tasks /
many_actors / many_pgs + scalability/single_node.json shapes).

Reproduces the reference's release-qualification shapes at single-host CI
scale and records throughputs with honest hardware caveats (the reference
ran these on 64-node AWS clusters; this host is usually 1 vCPU):

  many_actors      N actors created + first call acked, then killed
  many_tasks       M tasks queued at once, drained through the pool
  many_pgs         P placement groups created (ready) then removed
  many_objects     K driver puts, then one bulk get of all K
  broadcast        100MB object pulled by 3 isolated-store daemon nodes

Run: python scripts/scale_bench.py [--actors 1000] [--tasks 10000]
     [--pgs 200] [--objects 10000] [--output BENCH_scale.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A count of host-side operations: never opens an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _rss_gb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) / 1024 / 1024
    except OSError:
        pass
    return 0.0


def bench_many_actors(n: int, wave: int) -> dict:
    import ray_tpu

    @ray_tpu.remote(num_cpus=0.001)
    class Tiny:
        def ping(self):
            return 1

    t0 = time.monotonic()
    peak_live = 0
    created = 0
    handles = []
    for start in range(0, n, wave):
        batch = [Tiny.remote() for _ in range(min(wave, n - start))]
        ray_tpu.get([a.ping.remote() for a in batch], timeout=600)
        created += len(batch)
        handles.extend(batch)
        peak_live = max(peak_live, len(handles))
    dt = time.monotonic() - t0
    t1 = time.monotonic()
    for a in handles:
        ray_tpu.kill(a)
    kill_dt = time.monotonic() - t1
    return {
        "actors_created": created,
        "actors_per_s": round(created / dt, 1),
        "peak_live_actors": peak_live,
        "kill_s": round(kill_dt, 1),
    }


def bench_many_tasks(m: int) -> dict:
    import ray_tpu

    @ray_tpu.remote(num_cpus=0.5)
    def noop(i):
        return i

    t0 = time.monotonic()
    refs = [noop.remote(i) for i in range(m)]
    submit_dt = time.monotonic() - t0
    out = ray_tpu.get(refs, timeout=1200)
    total_dt = time.monotonic() - t0
    assert out[-1] == m - 1 and len(out) == m
    return {
        "tasks_queued": m,
        "submit_per_s": round(m / submit_dt, 1),
        "drain_per_s": round(m / total_dt, 1),
    }


def bench_backlog(n: int, spill_after: int) -> dict:
    """Absorb an n-task backlog on one head with BOUNDED RSS (reference:
    '1M queued tasks on one node', SURVEY.md §6 stress_tests).

    Methodology: measure steady-state head RSS after a small warmup,
    submit n dependency-free noop tasks as fast as the submit path goes
    (specs beyond ready_queue_spill_after overflow to the disk segment —
    runtime._ReadySpill), sample RSS throughout the drain, and prove
    completion by counter delta: every 1000th task carries num_returns=1
    and its value is asserted; the rest run with num_returns=0 (zero
    result objects — the backlog stresses the QUEUE, not the store).
    Zero lost results == tasks_finished advanced by exactly n and every
    sampled return value is correct."""
    import ray_tpu
    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()

    @ray_tpu.remote(num_cpus=0.5, max_retries=5)
    def nought():
        return None

    @ray_tpu.remote(num_cpus=0.5, max_retries=5)
    def probe(i):
        return i

    # Warmup: workers booted, pools warm, THEN the steady-state floor.
    ray_tpu.get([probe.remote(i) for i in range(200)], timeout=300)
    time.sleep(1.0)
    steady_gb = _rss_gb()
    base_finished = rt.metrics["tasks_finished"] + rt.metrics["tasks_failed"]

    peak_gb = steady_gb
    probes = []
    t0 = time.monotonic()
    for i in range(n):
        if i % 1000 == 999:
            probes.append((i, probe.remote(i)))
            peak_gb = max(peak_gb, _rss_gb())
        else:
            nought.options(num_returns=0).remote()
    submit_dt = time.monotonic() - t0
    spill = rt._ready_spill
    spilled_peak = spill.appended if spill is not None else 0
    backlog_peak = len(rt.tasks) + (spill.count if spill is not None else 0)

    # Drain, sampling RSS once a second.
    deadline = time.monotonic() + 3600
    while time.monotonic() < deadline:
        done = (
            rt.metrics["tasks_finished"] + rt.metrics["tasks_failed"]
            - base_finished
        )
        peak_gb = max(peak_gb, _rss_gb())
        if done >= n:
            break
        time.sleep(1.0)
    total_dt = time.monotonic() - t0
    finished = rt.metrics["tasks_finished"] - base_finished
    failed = rt.metrics["tasks_failed"]
    vals = ray_tpu.get([r for _i, r in probes], timeout=600)
    assert vals == [i for i, _r in probes], "probe results corrupted"
    return {
        "backlog_tasks": n,
        "spill_after": spill_after,
        "submit_per_s": round(n / submit_dt, 1),
        "drain_per_s": round(n / total_dt, 1),
        "specs_spilled": spilled_peak,
        "backlog_peak": backlog_peak,
        "tasks_finished": finished,
        "tasks_failed": failed,
        "lost_results": n - finished - failed,
        "probes_verified": len(probes),
        "steady_rss_gb": round(steady_gb, 3),
        "peak_rss_gb": round(peak_gb, 3),
        "rss_ratio": round(peak_gb / steady_gb, 2) if steady_gb else None,
    }


def bench_many_pgs(p: int) -> dict:
    import ray_tpu

    t0 = time.monotonic()
    pgs = [
        ray_tpu.util.placement_group([{"CPU": 0.001}], strategy="PACK")
        for _ in range(p)
    ]
    for pg in pgs:
        pg.wait(timeout_seconds=120)
    create_dt = time.monotonic() - t0
    t1 = time.monotonic()
    for pg in pgs:
        ray_tpu.util.remove_placement_group(pg)
    remove_dt = time.monotonic() - t1
    return {
        "pgs": p,
        "pgs_per_s": round(p / create_dt, 1),
        "remove_per_s": round(p / remove_dt, 1),
    }


def bench_many_objects(k: int) -> dict:
    import ray_tpu

    t0 = time.monotonic()
    refs = [ray_tpu.put(i) for i in range(k)]
    put_dt = time.monotonic() - t0
    t1 = time.monotonic()
    vals = ray_tpu.get(refs, timeout=600)
    get_dt = time.monotonic() - t1
    assert vals[k - 1] == k - 1
    return {
        "objects": k,
        "puts_per_s": round(k / put_dt, 1),
        "gets_per_s": round(k / get_dt, 1),
    }


def bench_actor_churn(
    n_live: int, waves: int, wave_size: int, traffic_actors: int = 4
) -> dict:
    """ROADMAP item 2's churn scenario: create/kill waves against a live
    actor pool WHILE background traffic keeps calling survivors — the
    many_actors shape measures a quiet cluster, this one measures
    creation under load.  Creation latency is attributed PER STAGE from
    the new task-lifecycle records (`util/state.task_summary`): the
    report says whether a slow wave spent its time queued, leasing
    (worker spawn), or running __init__ — the evidence the actors/s hunt
    starts from, instead of one opaque wall number."""
    import threading

    import ray_tpu
    from ray_tpu.util import state as state_api

    @ray_tpu.remote(num_cpus=0.001)
    class Churn:
        def ping(self):
            return 1

    # Steady pool + background traffic over it.
    pool = [Churn.remote() for _ in range(n_live)]
    ray_tpu.get([a.ping.remote() for a in pool], timeout=600)
    stop = threading.Event()
    traffic_calls = [0]

    def _traffic():
        i = 0
        while not stop.is_set():
            batch = [
                pool[(i + j) % len(pool)].ping.remote()
                for j in range(traffic_actors)
            ]
            try:
                ray_tpu.get(batch, timeout=120)
            except Exception:
                pass  # a killed actor mid-wave: traffic keeps going
            traffic_calls[0] += len(batch)
            i += traffic_actors

    t = threading.Thread(target=_traffic, daemon=True)
    t.start()

    wave_lat: list = []
    t0 = time.monotonic()
    for _w in range(waves):
        w0 = time.monotonic()
        fresh = [Churn.remote() for _ in range(wave_size)]
        ray_tpu.get([a.ping.remote() for a in fresh], timeout=600)
        wave_lat.append(time.monotonic() - w0)
        # Kill the oldest wave-size actors; the fresh ones replace them.
        victims, pool = pool[:wave_size], pool[wave_size:] + fresh
    churn_dt = time.monotonic() - t0
    stop.set()
    t.join(timeout=30)

    # Per-stage creation latency from the attribution plane: only
    # actor-creation records (event["creation"]) from this run's window.
    summary = state_api.task_summary(slow=2000)
    creations = [r for r in summary["slow"] if r.get("creation")]
    stage_tot: dict = {}
    for r in creations:
        for k, v in (r["durations"] or {}).items():
            stage_tot.setdefault(k, []).append(v)
    per_stage = {
        k: {
            "mean_s": round(sum(v) / len(v), 6),
            "p95_s": round(sorted(v)[int(0.95 * (len(v) - 1))], 6),
            "n": len(v),
        }
        for k, v in sorted(stage_tot.items())
    }
    for a in pool:
        ray_tpu.kill(a)
    created = waves * wave_size
    return {
        "live_pool": n_live,
        "waves": waves,
        "wave_size": wave_size,
        "created_under_load": created,
        "churn_creations_per_s": round(created / churn_dt, 1),
        "wave_latency_s": [round(x, 3) for x in wave_lat],
        "traffic_calls_during_churn": traffic_calls[0],
        "creation_stage_latency": per_stage,
        "creation_records_seen": len(creations),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=1000)
    ap.add_argument("--actor-wave", type=int, default=200,
                    help="actors created+acked per wave (bounds the spawn "
                         "burst; all waves stay alive until the kill phase)")
    ap.add_argument("--tasks", type=int, default=10000)
    ap.add_argument("--pgs", type=int, default=200)
    ap.add_argument("--objects", type=int, default=10000)
    ap.add_argument("--skip-broadcast", action="store_true")
    ap.add_argument(
        "--churn", action="store_true",
        help="ONLY the churn scenario: create/kill waves under live "
             "traffic, per-stage creation latency from task_summary",
    )
    ap.add_argument("--churn-live", type=int, default=60,
                    help="steady actor pool size during churn")
    ap.add_argument("--churn-waves", type=int, default=5)
    ap.add_argument("--churn-wave-size", type=int, default=20)
    ap.add_argument(
        "--backlog", type=int, default=0, metavar="N",
        help="ONLY the backlog scenario: absorb N queued tasks on one "
             "head with bounded RSS (ready-queue disk overflow), then "
             "drain to completion with zero lost results",
    )
    ap.add_argument(
        "--spill-after", type=int, default=10000,
        help="ready_queue_spill_after for the backlog scenario (in-memory "
             "backlog cap before specs overflow to disk; ~1.2KB of head "
             "RSS per in-memory task is the knob's direct meaning)",
    )
    ap.add_argument("--output", default=None)
    args = ap.parse_args(argv)

    if args.backlog:
        # Must be exported before ray_tpu.init resolves the knob.
        os.environ["RAY_TPU_READY_QUEUE_SPILL_AFTER"] = str(args.spill_after)

    import ray_tpu

    # Logical CPUs sized for the actor count: the envelope measures control
    # plane + process supervision, not core count (reference runs declare
    # the hardware alongside the numbers the same way).
    ray_tpu.init(num_cpus=max(8, 4))
    out = {
        "nproc": os.cpu_count(),
        "note": (
            "single host; reference numbers for these shapes come from "
            "64-node clusters (release/benchmarks/README.md)"
        ),
    }
    if args.backlog:
        out["backlog"] = bench_backlog(args.backlog, args.spill_after)
        print(json.dumps({"backlog": out["backlog"]}), flush=True)
        ray_tpu.shutdown()
        line = json.dumps(out)
        print(line)
        if args.output:
            with open(args.output, "w") as f:
                f.write(line + "\n")
        return 0
    if args.churn:
        out["actor_churn"] = bench_actor_churn(
            args.churn_live, args.churn_waves, args.churn_wave_size
        )
        print(json.dumps({"actor_churn": out["actor_churn"]}), flush=True)
        ray_tpu.shutdown()
        line = json.dumps(out)
        print(line)
        if args.output:
            with open(args.output, "w") as f:
                f.write(line + "\n")
        return 0
    out["many_tasks"] = bench_many_tasks(args.tasks)
    print(json.dumps({"many_tasks": out["many_tasks"]}), flush=True)
    out["many_objects"] = bench_many_objects(args.objects)
    print(json.dumps({"many_objects": out["many_objects"]}), flush=True)
    out["many_pgs"] = bench_many_pgs(args.pgs)
    print(json.dumps({"many_pgs": out["many_pgs"]}), flush=True)
    out["many_actors"] = bench_many_actors(args.actors, args.actor_wave)
    out["many_actors"]["rss_gb_after"] = round(_rss_gb(), 2)
    print(json.dumps({"many_actors": out["many_actors"]}), flush=True)
    if not args.skip_broadcast:
        from ray_tpu._private.ray_perf import bench_broadcast_cross_node

        out["broadcast"] = bench_broadcast_cross_node(n_nodes=3, mb=100)
        print(json.dumps({"broadcast": out["broadcast"]}), flush=True)
    ray_tpu.shutdown()
    line = json.dumps(out)
    print(line)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
