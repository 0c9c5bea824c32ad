#!/usr/bin/env python3
"""A run's wall clock by name, from the run's JSON (`benchmarks/out/<tag>.json`,
which holds the Train library's run record once a `--trace 1` run's readers
found it):

    python3 benchmarks/tools/run_record.py benchmarks/out/<tag>.json [--json]

Prints (1) the stretches of the run with the spans that name them, and what
no span names; (2) the set-up's trace / lower / compile events by program (the
harness's own second lowering of the step, `inspect_s`, is 0.05 s on the chip
and raises no event: jax has the step's lowering in memory); (3) every stall
event, classified: the share of its excess in which the thread was on no CPU
and, for a stalled step among the traced ones with the trace file still there,
the share of that step's own span (`bench_step`: the period also holds what
the loop does between two steps) in which the device ran an op.  A stall is
classified here and not made a metric: a step that stalls once in fourteen
runs gives no median.

The profiler's planes are NOT on the epoch clock (events start near 0.15 s,
relative to `start_trace`), so a stall is laid against the device by its
step: the k-th `bench_step` span of the trace is the k-th traced step.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import run_record as rr  # noqa: E402
from benchmarks.lib import trace_reduce as tr  # noqa: E402

STEP_SPAN = "bench_step"  # loops/train_steps.py
JAX_SPANS = ("jax::trace", "jax::lower", "jax::compile")


def wall_clock(run: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """Seconds of each named stretch, and the remainders no span names."""
    setup, t_loop, t_window = run["setup"], run["start"]["t_loop"], run["setup"]["t_window"]
    out: Dict[str, Optional[float]] = {
        "runtime_init_s": rr.span_s(run, "runtime::init"),
        "fit_to_loop_s": t_loop - run["clocks"]["t_fit"],
        "  worker_spawn_s": rr.worker_spawn_s(run),
        "  jax_import_s": rr.span_s(run, "train::backend::import_jax"),
        "  chip_wait_s": rr.span_s(run, "train::backend::chip_wait"),
        "  device_open_s": rr.span_s(run, "train::backend::device_open"),
        "  fit_unnamed_s": rr.fit_unnamed_s(run),
        "setup_s": t_window - t_loop,
    }
    for name in JAX_SPANS:
        out[f"  {name}"] = rr.setup_s_under(run, name)
    named = [out[f"  {n}"] for n in JAX_SPANS]
    if None not in named:
        # Execution on the chip, the reference's arithmetic, the host's own work.
        out["  neither trace, lower nor compile"] = out["setup_s"] - sum(named)
    for k in ("init_state_s", "reference_s", "first_step_s", "warmup_s", "inspect_s"):
        out[f"  ({k} by the loop's clock)"] = setup[k]
    out["window_s"] = run["summary"]["window_s"]
    out["runtime_shutdown_s"] = rr.span_s(run, "runtime::shutdown")
    record = rr.record_of(run)
    for s in (record or {}).get("runtime_spans", []):
        if s["name"].startswith("runtime::shutdown::"):
            out[f"  {s['name']}"] = s["end"] - s["start"]
    return out


def compile_events(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The set-up's jax events by (program, kind): count, seconds, cache."""
    record = rr.record_of(run)
    if not record:
        return []
    t_window = run["setup"]["t_window"]
    rows: Dict[tuple, Dict[str, Any]] = {}
    for s in record["spans"]:
        if s["name"] not in JAX_SPANS or not run["start"]["t_loop"] <= s["start"] < t_window:
            continue
        fun = s["attrs"].get("fun_name", "")
        key = (fun, s["name"], s["attrs"].get("cache"))
        row = rows.setdefault(key, {"fun_name": fun, "kind": s["name"], "cache": key[2], "n": 0, "seconds": 0.0})
        row["n"] += 1
        row["seconds"] += s["end"] - s["start"]
    return sorted(rows.values(), key=lambda r: -r["seconds"])


def _device_busy_by_traced_step(run: Dict[str, Any]) -> Dict[int, float]:
    """Window step index -> % of that step in which a device op ran (mean
    over devices), for the traced steps; nothing without the trace file."""
    trace = run.get("trace") or {}
    path = trace.get("path")
    if not path or not os.path.isfile(path):
        return {}
    profile = tr.load(path)
    steps = tr.host_spans(profile, [STEP_SPAN])[STEP_SPAN]
    busy: Dict[int, List[float]] = {}
    for plane in profile.planes:
        line = next((l for l in plane.lines if l.name == tr.OP_LINE), None) \
            if tr.DEVICE_PLANE.match(plane.name) else None
        if line is None:
            continue
        ops = tr.union((s, e) for _, s, e in tr._events(line))
        for k, (lo, hi) in enumerate(steps):
            busy.setdefault(trace["steps"][0] + k, []).append(100.0 * tr.measure(tr.clip(ops, lo, hi)) / (hi - lo))
    return {k: sum(v) / len(v) for k, v in busy.items()}


def stalls(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    record = rr.record_of(run)
    if not record:
        return []
    t_window, ends = run["setup"]["t_window"], run["summary"]["step_ends"]
    busy = _device_busy_by_traced_step(run) if record["stalls"] else {}
    out = []
    for e in record["stalls"]:
        row = {k: e[k] for k in ("step", "period_s", "median_s", "off_cpu_pct", "thread_cpu_s", "process_cpu_s",
                                 "make_batch_s", "dispatch_s", "involuntary_switches", "major_faults",
                                 "gc_collections", "gc_s")}
        since = e["start"] - t_window
        row["where"] = "set-up" if since < 0 else "window"
        if since >= 0:
            row["window_step"] = sum(1 for t in ends if t <= since)
            row["device_busy_pct"] = busy.get(row["window_step"])
        out.append(row)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("run_json")
    ap.add_argument("--json", action="store_true", help="one JSON object instead of the tables")
    args = ap.parse_args(argv)
    with open(args.run_json) as f:
        run = json.load(f)["run"]
    if rr.record_of(run) is None:
        print("no run record in this file (a --trace 0 run, or a program from before the record)")
        return 1
    result = {"cell": run["cell"]["name"], "wall_clock": wall_clock(run), "compile_events": compile_events(run),
              "stalls": stalls(run), "reports": rr.record_of(run)["reports"]}
    if args.json:
        print(json.dumps(result))
        return 0
    print(f"[run record] {result['cell']}")
    for name, v in result["wall_clock"].items():
        print(f"  {name:46s} {'-' if v is None else f'{v:9.3f}'}")
    print("[run record] set-up's jax events, by seconds")
    for r in result["compile_events"][:12]:
        print(f"  {r['seconds']:8.3f} s  x{r['n']:<3d} {r['kind']:13s} {str(r['cache'] or ''):5s} {r['fun_name']}")
    print(f"[run record] reports {json.dumps(result['reports'])}")
    for r in result["stalls"]:
        print(f"[run record] stall {json.dumps(r)}")
    if not result["stalls"]:
        print("[run record] no stalled step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
