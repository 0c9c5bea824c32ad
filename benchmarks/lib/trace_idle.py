"""Whose idle is it: the device's idle seconds of the traced window, split
into those under the LIBRARY's own host spans and the rest.

The program writes three spans of its host work onto the profiler's clock
(`tracing.annotate`, so `TraceAnnotation`s on `/host:CPU`):
`train_step/make_batch` and `train_step/dispatch` in
`LMTrainContext.train_step`, `train/report` in `TrainSession.report`.  An
idle second of a device that lies under one of them is ray_tpu's: the chip
waited while the library sharded a batch, enqueued the step or took a
report.  Idle outside them is the caller's loop: its data, its fetch.

Window, clipping and busy unions are `trace_reduce.reduce`'s: the window runs
from the first `bench_step` event's start to the last one's end, a device is
busy under the union of its `XLA Ops` events, idle in the gaps.  The two
shares are means over the devices and add up to the MEAN idle share
(`trace_reduce.mean_share_pct(trace, "idle_s")`; `device_idle_pct` is the
worst device's).

A trace without `train/report` events in the window is a program's that does
not mark its report (a parent commit's): no split is made, both read as
nothing.  Nothing here may take a run down (`trace_scopes._never_raises`).
"""

from __future__ import annotations

import importlib
import json
from typing import Any, Dict, Optional

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.trace_scopes import _never_raises

LIBRARY_SPANS = ("train_step/make_batch", "train_step/dispatch", "train/report")
KEY = "idle_by_program_span"


def split(profile, *, window_span: str) -> Optional[Dict[str, Any]]:
    """Mean over the devices, in seconds: `idle_s`, `in_library_s`, and
    `by_span_s` the three spans apart.  None without the window's span, a
    device with ops, or a `train/report` event inside the window."""
    spans = tr.host_spans(profile, [window_span, *LIBRARY_SPANS])
    if not spans[window_span]:
        return None
    lo = min(s for s, _ in spans[window_span])
    hi = max(e for _, e in spans[window_span])
    under = {n: tr.union(tr.clip(spans[n], lo, hi)) for n in LIBRARY_SPANS}
    if not under["train/report"]:
        return None
    library = tr.union(i for n in LIBRARY_SPANS for i in under[n])
    idle_s = in_library_s = 0.0
    by_span = dict.fromkeys(LIBRARY_SPANS, 0.0)
    n_dev = 0
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        line = next((l for l in plane.lines if l.name == tr.OP_LINE), None)
        busy = tr.union(tr.clip(((s, e) for _, s, e in tr._events(line)), lo, hi)) if line is not None else []
        if not busy:
            continue
        n_dev += 1
        idle = tr.gaps(busy, lo, hi)
        idle_here = tr.measure(idle)
        idle_s += idle_here
        in_library_s += idle_here - tr.measure(tr.subtract(idle, library))
        for n in LIBRARY_SPANS:
            by_span[n] += idle_here - tr.measure(tr.subtract(idle, under[n]))
    if not n_dev:
        return None
    return {"window_s": hi - lo, "devices": n_dev, "idle_s": idle_s / n_dev, "in_library_s": in_library_s / n_dev,
            "by_span_s": {n: s / n_dev for n, s in by_span.items()}}


@_never_raises
def of(run) -> Optional[Dict[str, Any]]:
    """`split` of the run's trace file, once per run, left on `run` and
    printed as the line `[bench] idle by program span {...}`."""
    if KEY not in run:
        run[KEY] = None
        trace = run.get("trace")
        path = trace.get("path") if trace else None
        if path:
            loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
            run[KEY] = split(tr.load(path), window_span=loop.STEP_SPAN)
            print("[bench] idle by program span " + json.dumps(run[KEY]), flush=True)
    return run[KEY]


def in_library_pct(run) -> Optional[float]:
    got = of(run)
    return 100.0 * got["in_library_s"] / got["window_s"] if got else None


def outside_library_pct(run) -> Optional[float]:
    got = of(run)
    return 100.0 * (got["idle_s"] - got["in_library_s"]) / got["window_s"] if got else None
