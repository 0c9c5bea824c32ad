"""The expert layer's share of the traced steps, by the names the program
gives it INSIDE `layer/mlp` (`ray_tpu/models/moe.py`): `moe/router` (router
matmul, softmax, top-k, the loss statistics), `moe/dispatch` (sort, group
sizes, the gather into expert order), `moe/experts` (the three grouped
matmuls and `silu * up`), `moe/combine` (gate multiply, un-permute, sum).

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/mlp` there and `mlp_time_pct` stays "the FFN block" in every
cell; this module reads the same trace file with its own name set: the
window, the clipping and the self times are `trace_reduce`'s, the paths
`trace_scopes.event_paths`'s, and nothing here may take a run down
(`trace_scopes._never_raises`).  A program without these names (the parent
of PR 26, any dense cell) reads as nothing.
"""

from __future__ import annotations

import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import trace_scopes as ts

NAMES = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine")

# A name counts only as a whole run of path components: after `/` or `(`, before `/`, `)`, `:` or the end.
_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, NAMES)) + r")(?=[/):]|$)")

_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost `moe/*` name of an op's `op_name` path, in whatever
    direction (forward, `transpose(`, `rematted_computation`); None if none."""
    found = _COMPONENT.findall(path) if path else None
    return found[-1] if found else None


def reduce_moe(path: str, *, window_span: str) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per `moe/*` name, all
    directions, mean over the devices.  None without a window span or ops."""
    from jax.profiler import ProfileData

    data = ts._read_bytes(path)
    paths = ts.event_paths(data)
    profile = ProfileData.from_serialized_xspace(data)
    del data
    spans = tr.host_spans(profile, [window_span])[window_span]
    if not spans:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    seconds = dict.fromkeys(NAMES, 0.0)
    n_dev = 0
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        line = next((l for l in plane.lines if l.name == tr.OP_LINE), None)
        if line is None:
            continue
        table = paths.get(plane.name, {})
        name_of: Dict[str, Optional[str]] = {}
        events = []
        for text, s, e in tr._events(line):
            if min(e, hi) > max(s, lo):
                op = tr.op_name(text)
                events.append((op, max(s, lo), min(e, hi)))
                if op not in name_of:
                    name_of[op] = classify(table.get(text))
        if not events:
            continue
        n_dev += 1
        for op, _, _, t in tr.self_times(events):
            if name_of[op] is not None:
                seconds[name_of[op]] += t
    if not n_dev:
        return None
    return {"window_s": hi - lo, "steps": len(spans), "devices": n_dev,
            "seconds": {k: v / n_dev for k, v in seconds.items()}}


def moe_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_moe` of the run's trace file, once per process, printed as the
    line `[bench] moe {...}` (seconds per step).  None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        _memo[path] = got = reduce_moe(path, window_span=loop.STEP_SPAN)
        print("[bench] moe " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


@ts._never_raises
def share_pct(run, name: str) -> Optional[float]:
    """Self time under `name`, every direction, as % of the traced window;
    nothing where the program has no such name."""
    got = moe_of(run)
    if not got or not any(got["seconds"].values()):
        return None
    return 100.0 * got["seconds"][name] / got["window_s"]


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """Needed expert-matmul FLOPs of the traced steps on one chip (the three
    grouped matmuls, forward + backward, K experts per token:
    `builders/moe_decoder.expert_flops_per_token`) over the chip's bf16 peak,
    over the device time under `moe/experts` in every direction: what the
    backward recomputes is time, not work.  Compute-bound: ~1,024 rows per
    expert is 1,024 FLOP per weight byte against the chip's 240."""
    got = moe_of(run)
    seconds = got["seconds"]["moe/experts"] if got else 0.0
    if seconds <= 0:
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    needed = (builder.expert_flops_per_token(config) * run["summary"]["tokens_per_step"]
              / run["cell"]["chips"] * got["steps"])
    peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * needed / peak / seconds
