"""The Mamba-2 mixer's share of the traced steps, by the names the program
gives it INSIDE the two mixer scopes every layer has
(`ray_tpu/models/transformer.py` `_mamba_layer`, `ray_tpu/ops/ssm.py`):
`ssm/proj` (ln1, `in_proj`, `out_proj`, the scaled residual add) and
`ssm/conv` (convolution + SiLU, softplus, the gated RMSNorm) under
`layer/attn_proj`; `ssm/scan` (the whole selective scan: within-chunk
products, chunk states, the pass over chunks, state to output, `D*x`) under
`layer/attn_core`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` there and `attn_proj_time_pct` /
`attn_core_time_pct` read "the mixer's projections" / "the mixer's core" of
both kinds of layer; this module reads the same trace file with its own name
set, as `trace_moe` does for the expert layer: the window, the clipping and
the self times are `trace_reduce`'s, the paths `trace_scopes.event_paths`'s,
and nothing here may take a run down (`trace_scopes._never_raises`).  A
program without these names (the parent of PR 30, every other cell) reads as
nothing.
"""

from __future__ import annotations

import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import trace_scopes as ts

NAMES = ("ssm/proj", "ssm/conv", "ssm/scan")

# A name counts only as a whole run of path components: after `/` or `(`, before `/`, `)`, `:` or the end.
_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, NAMES)) + r")(?=[/):]|$)")

_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost `ssm/*` name of an op's `op_name` path, in whatever
    direction (forward, `transpose(`, `rematted_computation`); None if none."""
    found = _COMPONENT.findall(path) if path else None
    return found[-1] if found else None


def reduce_ssm(path: str, *, window_span: str) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per `ssm/*` name, all
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


def ssm_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_ssm` of the run's trace file, once per process, printed as the
    line `[bench] ssm {...}` (seconds per step).  None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        _memo[path] = got = reduce_ssm(path, window_span=loop.STEP_SPAN)
        print("[bench] ssm " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


@ts._never_raises
def share_pct(run, name: str) -> Optional[float]:
    """Self time under `name`, every direction, as % of the traced window;
    nothing where the program has no such name."""
    got = ssm_of(run)
    if not got or not any(got["seconds"].values()):
        return None
    return 100.0 * got["seconds"][name] / got["window_s"]


@ts._never_raises
def scan_roofline_pct(run) -> Optional[float]:
    """Needed FLOPs of the selective scan in the traced steps on one chip
    (`builders/hybrid_decoder.ssd_flops_per_token`: the chunked form at the
    PUBLISHED chunk, causal half, forward + backward) over the chip's bf16
    peak, over the device time under `ssm/scan` in every direction: what the
    backward recomputes is time, not work.  Against the COMPUTE peak: by its
    needed counts the scan does about 300 FLOP per byte of x, B, C, dt and y,
    over the chip's ridge of 240; a form that writes [chunk, chunk] masks to
    HBM reads far below it, which is the finding the metric exists for."""
    got = ssm_of(run)
    seconds = got["seconds"]["ssm/scan"] if got else 0.0
    if seconds <= 0:
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    needed = (builder.ssd_flops_per_token(config) * run["summary"]["tokens_per_step"]
              / run["cell"]["chips"] * got["steps"])
    peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * needed / peak / seconds
