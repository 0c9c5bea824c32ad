"""The traced steps by the names the PROGRAM gives its own work: device time
per scope and direction, the three flash kernels apart, and the program's own
host spans.  Read in the driver after the chip is released, from the trace
file the loop reports (`run["trace"]["path"]`); the window, the clipping and
the self times are `trace_reduce`'s.

Where a name reaches the trace (looked at by hand, PR 24, libtpu 0.0.34; see
PERF.md "Trace anatomy"):

  * An `XLA Ops` event's NAME is the optimized-HLO instruction without its
    metadata.  The metadata's `op_name` is the stat `tf_op` of the event's
    METADATA entry in the plane (`jit(_train_step)/transpose(jvp())/while/
    body/closed_call/checkpoint/layer/mlp/bse,ef->bsf/dot_general:`).
    `jax.profiler.ProfileData` hands out an event's own stats but not its
    metadata's, so `event_paths` reads that one table from the same file
    with a minimal protobuf wire reader (XSpace/XPlane/XEventMetadata field
    numbers below); the events themselves come from `ProfileData`.
  * `jax.named_scope("layer/mlp")` is one path component; transforms wrap
    the component after them (`jvp(loss)`, `transpose(jvp(lm_head))`,
    `transpose(jvp(layers))/while/body/.../layer/mlp/...`).  `layers` is
    around the loop over the layer stack, so an op whose innermost name it
    is belongs to the loop itself: a layer's weights sliced out of the
    stack, gradients and residuals written back into it.
  * `pl.pallas_call(name="flash_fwd")` names the HLO instruction itself
    (`%flash_fwd.21 = ... custom-call`), and the `named_scope` of the same
    name around the call is in its path.

An op's SCOPE is the innermost of `SCOPES + KERNELS` that is a whole
component of its path, its DIRECTION `recompute` if the path carries
`rematted_computation` (or it is a `flash_fwd` under `transpose(`: the
forward kernel run again for the backward's residual), `bwd` if it carries
`transpose(`, else `fwd`.  A fusion carries ONE path (its root's), so a
fusion that spans two regions counts whole for one: `unscoped` (no name in
the path, or no path) bounds what the names do not reach.

Nothing here may take a run down: what the readers call is wrapped in one
place (`_never_raises`), which prints `[bench] scopes FAILED: <reason>` and
returns None, as does every reader that finds nothing to read.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import re
import statistics
import time
from typing import Any, Dict, Iterator, Optional, Tuple

from benchmarks.lib import flops
from benchmarks.lib import trace_reduce as tr

SCOPES = ("embed", "layers", "layer/attn_proj", "layer/attn_core", "layer/mlp", "final_norm",
          "lm_head", "loss", "optimizer")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
PROGRAM_SPANS = ("train_step/make_batch", "train_step/dispatch")
DIRECTIONS = ("fwd", "bwd", "recompute")
UNSCOPED = "unscoped"
UNSCOPED_TOP = 8  # how many of the ops no name reaches the `[bench] scopes` line lists
TPU_CALL = 'custom_call_target="tpu_custom_call"'

# A name counts only as a whole path component: after `/` or `(`, before `/`, `)`, `:` or the end.
_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, KERNELS + SCOPES)) + r")(?=[/):]|$)")

_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def _never_raises(read):
    """The one boundary: whatever goes wrong under a reader is said on one
    line and reads as nothing.  A reduction never fails a run."""
    @functools.wraps(read)
    def guarded(*args, **kwargs):
        try:
            return read(*args, **kwargs)
        except Exception as e:  # noqa: BLE001
            print(f"[bench] scopes FAILED: {type(e).__name__}: {e}"[:500], flush=True)
            return None

    return guarded


# -- the path of an op ---------------------------------------------------------


def classify(path: Optional[str]) -> Tuple[str, str]:
    """(scope, direction) of an op from its `op_name` path."""
    if not path:
        return UNSCOPED, "fwd"
    found = _COMPONENT.findall(path)
    scope = found[-1] if found else UNSCOPED
    if "rematted_computation" in path:
        return scope, "recompute"
    if "transpose(" in path:
        return scope, "recompute" if scope == "flash_fwd" else "bwd"
    return scope, "fwd"


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: a varint as int, a
    length-delimited field as a memoryview; fixed-width fields are skipped.
    A truncated message raises (IndexError or ValueError)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            if i + size > n:
                raise ValueError("truncated protobuf field")
            yield key >> 3, buf[i: i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def _read_bytes(path: str) -> bytes:
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return f.read()
    with open(path, "rb") as f:
        return f.read()


def event_paths(data: bytes, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """plane name -> {event-metadata name: its `tf_op` stat} of a serialized
    XSpace.  Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes
    1; XPlane.name 2, .event_metadata 4, .stat_metadata 5 (map entries: key 1,
    value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7 (a stat-metadata id whose
    name is the string)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = next((_text(x) for sf, x in _fields(entry[2]) if sf == 2), "")
        if not tr.DEVICE_PLANE.match(name):
            continue
        want = {k for k, label in stat_names.items() if label == stat}
        table = out.setdefault(name, {})
        for entry in events:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            text, path = "", None
            for mf, mv in _fields(meta):
                if mf == 2:
                    text = _text(mv)
                elif mf == 5:
                    st = dict(_fields(mv))
                    if st.get(1) in want:
                        path = _text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
            if path:
                table[text] = path.rstrip(":")
    return out


def _text(value) -> str:
    return bytes(value).decode(errors="replace")


# -- the reduction ---------------------------------------------------------------


def reduce_scopes(path: str, *, window_span: str, kernel_ops=()) -> Optional[Dict[str, Any]]:
    """Seconds in the traced window per (scope, direction), mean over the
    devices; per kernel name its calls and seconds; the program's host spans.
    None when the trace has no window span or no device ops."""
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    data = _read_bytes(path)
    paths = event_paths(data)
    profile = ProfileData.from_serialized_xspace(data)
    del data
    spans = tr.host_spans(profile, [window_span, *PROGRAM_SPANS])
    if not spans[window_span]:
        return None
    lo = min(s for s, _ in spans[window_span])
    hi = max(e for _, e in spans[window_span])
    kernel_names = {k.lstrip("%") for k in kernel_ops}
    seconds: Dict[str, Dict[str, float]] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    unscoped: Dict[str, float] = {}  # "label | path" of the ops no name reaches -> seconds
    busy_s = 0.0
    n_dev = 0
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        line = next((l for l in plane.lines if l.name == tr.OP_LINE), None)
        if line is None:
            continue
        table = paths.get(plane.name, {})
        cls: Dict[str, Tuple[str, str, bool, str]] = {}  # instruction -> (scope, direction, is a kernel, label)
        events = []
        for text, s, e in tr._events(line):
            if min(e, hi) > max(s, lo):
                name = tr.op_name(text)
                events.append((name, max(s, lo), min(e, hi)))
                if name not in cls:
                    op_path = table.get(text)
                    cls[name] = (*classify(op_path), name in kernel_names or TPU_CALL in text,
                                 f"{tr.op_label(text)} | {op_path}")
        if not events:
            continue
        n_dev += 1
        busy_s += tr.measure(tr.union((s, e) for _, s, e in events))
        for name, _, _, t in tr.self_times(events):
            scope, direction, is_kernel, label = cls[name]
            row = seconds.setdefault(scope, {})
            row[direction] = row.get(direction, 0.0) + t
            if scope == UNSCOPED:
                unscoped[label] = unscoped.get(label, 0.0) + t
            if is_kernel and scope in KERNELS:
                k = kernels.setdefault(scope, {"calls": 0, "seconds": 0.0})
                k["calls"] += 1
                k["seconds"] += t
    if not n_dev:
        return None
    for row in [*seconds.values(), *kernels.values()]:
        for key in row:
            row[key] /= n_dev
    return {
        "window_s": hi - lo, "steps": len(spans[window_span]), "devices": n_dev,
        "busy_s": busy_s / n_dev, "seconds": seconds, "kernels": kernels,
        "scoped": any(s != UNSCOPED for s in seconds),
        "unscoped_top": [[label, t / n_dev] for label, t in
                         sorted(unscoped.items(), key=lambda kv: -kv[1])[:UNSCOPED_TOP]],
        "program_span_s": {n: [e - s for s, e in tr.clip(spans[n], lo, hi)] for n in PROGRAM_SPANS},
        "bytes": os.path.getsize(path), "load_s": time.perf_counter() - t0,
    }


def scopes_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_scopes` of the run's trace file, once per process (a failure
    is remembered as nothing to read), printed as the line
    `[bench] scopes {...}` (seconds per step).  None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        _memo[path] = got = reduce_scopes(path, window_span=loop.STEP_SPAN,
                                          kernel_ops=run["summary"]["facts"].get("kernel_ops", ()))
        print("[bench] scopes " + json.dumps(_per_step(got) if got else None), flush=True)
    return _memo[path]


def _per_step(got: Dict[str, Any]) -> Dict[str, Any]:
    steps = got["steps"]
    return {
        "steps": steps, "devices": got["devices"], "window_s": got["window_s"], "bytes": got["bytes"],
        "load_s": got["load_s"], "busy_s_per_step": got["busy_s"] / steps,
        "s_per_step": {scope: {d: t / steps for d, t in row.items()} for scope, row in got["seconds"].items()},
        "kernels_per_step": {k: {"calls": v["calls"] / steps, "seconds": v["seconds"] / steps}
                             for k, v in got["kernels"].items()},
        "program_span_ms": {n: 1e3 * statistics.median(v) for n, v in got["program_span_s"].items() if v},
        "unscoped_top_s_per_step": [[label[:160], t / steps] for label, t in got["unscoped_top"]],
    }


# -- what the readers in layer_metrics/ call ----------------------------------------


@_never_raises
def share_pct(run, scopes=None, directions=DIRECTIONS) -> Optional[float]:
    """Self time under `scopes` (any, `unscoped` too, if None) in
    `directions`, as % of the traced window; nothing without scopes."""
    got = scopes_of(run)
    if not got or not got["scoped"]:
        return None
    wanted = list(got["seconds"]) if scopes is None else scopes
    total = sum(got["seconds"].get(s, {}).get(d, 0.0) for s in wanted for d in directions)
    return 100.0 * total / got["window_s"]


# The kernel's own matmuls per (query, key) pair, 2*D flops each: forward
# QK^T and PV; dq QK^T, dP = dO V^T and dq = dS K; dkv QK^T, dV = P^T dO,
# dP = dO V^T and dk = dS^T Q.  The 6 a step NEEDS (`flops.py`: forward 2,
# backward 4) leave out the second forward call and the three products the
# two backward kernels compute again.
KERNEL_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
NEEDED_MATMULS = 6


@_never_raises
def kernel_roofline_pct(run, kernel: str) -> Optional[float]:
    """Executed causal FLOPs of every call of `kernel` in the traced window
    on one chip, over the chip's bf16 peak, over the calls' device time."""
    got = scopes_of(run)
    k = got["kernels"].get(kernel) if got else None
    if not k or k["seconds"] <= 0:
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    per_call = (builder.attention_flops_per_token(config, run["traffic"]["seq_len"])
                / config["num_hidden_layers"] * KERNEL_MATMULS[kernel] / NEEDED_MATMULS
                * run["summary"]["tokens_per_step"] / run["cell"]["chips"])
    peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * k["calls"] * per_call / peak / k["seconds"]


@_never_raises
def program_span_ms(run, name: str) -> Optional[float]:
    """Median of the program's own `name` spans inside the traced window."""
    got = scopes_of(run)
    values = got["program_span_s"].get(name, []) if got else []
    return 1e3 * statistics.median(values) if values else None
