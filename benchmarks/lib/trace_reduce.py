"""From a profiler trace (`.xplane.pb`) to numbers: the one reduction every
PR's traced run goes through.  Reads the file with `jax.profiler.ProfileData`
and nothing else.

What a v5e trace looks like (looked at by hand in PR 22, libtpu 0.0.34; see
PERF.md "Trace anatomy"): one plane per chip named `/device:TPU:<n>`; on it
the line `XLA Ops` carries one event per executed HLO instruction, named as
in the optimized HLO (`fusion.12`, `custom-call.3`, `all-gather-start.2`),
one at a time per core, with `while`/`conditional` events ENCLOSING the ops
of their bodies; `XLA Modules` carries one event per executed program and
`Steps` one per step.  The host's threads are lines of `/host:CPU`, and
`jax.profiler.TraceAnnotation` spans land there on the same clock.

Definitions (all per device, inside the WINDOW, which is the span from the
start of the first to the end of the last host event named `window_span`):
  busy      union of the op line's events
  self time an event's duration minus the events it encloses, so a `while`
            is not counted on top of its body
  kernel    ops whose name is in `kernel_ops` (the compiled step's Mosaic
            `tpu_custom_call` instructions, read from its HLO by the caller)
  collective  ops whose name is of a collective op CLASS (all-gather,
            all-reduce, reduce-scatter, collective-permute, all-to-all, with
            -start/-done forms, and XLA:TPU's `async-collective-start/-done`
            fusions).  An async pair covers the interval from its
            `-start` beginning to its `-done` end: the transfer is in flight
            all that time.  EXPOSED is the part of the collective intervals
            in which no other (non-collective, non-container) op runs.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all|async-collective)(-start|-done)?(\.[\d.]+)?$"
)


# -- interval arithmetic (pure; tested on hand-made cases) --------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def measure(disjoint: Sequence[Interval]) -> float:
    return float(sum(hi - lo for lo, hi in disjoint))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of disjoint sorted `a` not covered by disjoint sorted `b`."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(disjoint: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], disjoint)


def self_times(events: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end) events of one serial line, possibly nested ->
    (name, start, end, self_seconds): duration minus enclosed events."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_t = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i in order:
        _, start, end = events[i]
        while stack and events[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][2]:
            self_t[stack[-1]] -= end - start
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(self_t[i], 0.0)) for i in range(len(events))]


def collective_intervals(events: Sequence[Tuple[str, float, float]]) -> List[Interval]:
    """Intervals in which a collective is in flight: a sync op's own event,
    and for an async pair `-start` begin to the matching `-done` end (same
    class and instruction suffix, next in time)."""
    out: List[Interval] = []
    open_starts: Dict[Tuple[str, str], List[float]] = {}
    for name, start, end in sorted(events, key=lambda e: e[1]):
        m = COLLECTIVE.match(name)
        if not m:
            continue
        cls, form, suffix = m.group(1), m.group(2), m.group(3) or ""
        if form == "-start":
            open_starts.setdefault((cls, suffix), []).append(start)
            out.append((start, end))
        elif form == "-done":
            pending = open_starts.get((cls, suffix))
            out.append((pending.pop(0) if pending else start, end))
        else:
            out.append((start, end))
    return union(out)


# -- the trace ---------------------------------------------------------------


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """The instruction's name.  libtpu names a device event by the whole HLO
    instruction (`%fusion.3 = bf16[...] fusion(...), kind=...`)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_label(text: str) -> str:
    """`name opcode result-shape`, short enough for a breakdown:
    `fusion.3 fusion bf16[2,4096,8192]`.  Without scopes in the program the
    shape is what tells an MLP fusion from a logits one."""
    head, sep, rest = text.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    if not m:
        return op_name(text)
    shape = re.sub(r"\{[^}]*\}", "", rest[: m.start()]).strip()
    return f"{op_name(text)} {m.group(1)} {shape[:48]}".strip()


def _events(line, scale: float = 1e-9) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * scale, (e.start_ns + e.duration_ns) * scale) for e in line.events]


def load(path: str):
    """A `.xplane.pb` as the profiler wrote it, or gzipped (the recorded
    trace the tests keep)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def host_spans(profile, names: Sequence[str]) -> Dict[str, List[Interval]]:
    """Every host event whose name is in `names`, by name, in seconds."""
    want = set(names)
    found: Dict[str, List[Interval]] = {n: [] for n in names}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, start, end in _events(line):
                if name in want:
                    found[name].append((start, end))
    for spans in found.values():
        spans.sort()
    return found


def reduce(profile, *, window_span: str, span_names: Sequence[str],
           kernel_ops: Sequence[str] = (), top: int = 10, top_gaps: int = 5) -> Optional[Dict[str, Any]]:
    """The reduced trace, or None when it holds no device plane with ops or
    no `window_span` event (a reader then has nothing to read)."""
    spans = host_spans(profile, [window_span, *span_names])
    if not spans[window_span]:
        return None
    lo = min(s for s, _ in spans[window_span])
    hi = max(e for _, e in spans[window_span])
    window_s = hi - lo
    kernels = {k.lstrip("%") for k in kernel_ops}
    devices = []
    op_seconds: Dict[str, float] = {}
    gap_list: List[Tuple[float, float, str]] = []
    collective_names: Dict[str, List[float]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        line = next((l for l in plane.lines if l.name == OP_LINE), None)
        if line is None:
            continue
        labels: Dict[str, str] = {}
        events = []
        for text, s, e in _events(line):
            if min(e, hi) > max(s, lo):
                events.append((op_name(text), max(s, lo), min(e, hi)))
                labels.setdefault(events[-1][0], op_label(text))
        if not events:
            continue
        busy = union((s, e) for _, s, e in events)
        timed = self_times(events)
        coll = clip(collective_intervals(events), lo, hi)
        compute = union((s, e) for n, s, e, t in timed
                        if not COLLECTIVE.match(n) and t >= 0.5 * (e - s))  # leaves, not containers
        exposed = subtract(coll, compute)
        kernel_s = sum(t for n, _, _, t in timed if n in kernels)
        coll_self = sum(t for n, _, _, t in timed if COLLECTIVE.match(n))
        for n, s, e, t in timed:
            op_seconds[labels[n]] = op_seconds.get(labels[n], 0.0) + t
            m_c = COLLECTIVE.match(n)
            if m_c:  # how this libtpu names them: class+form -> [events, seconds]
                seen = collective_names.setdefault(m_c.group(1) + (m_c.group(2) or ""), [0, 0.0])
                seen[0] += 1
                seen[1] += e - s
        busy_s = measure(busy)
        devices.append({
            "device": int(m.group(1)), "ops": len(events), "busy_s": busy_s,
            "idle_s": window_s - busy_s, "kernel_s": kernel_s,
            "collective_s": measure(coll), "collective_exposed_s": measure(exposed),
            "collective_op_s": coll_self,
            "xla_compute_s": busy_s - kernel_s - coll_self,
        })
        for g_lo, g_hi in gaps(busy, lo, hi):
            gap_list.append((g_hi - g_lo, g_lo, _attribute(g_lo, g_hi, spans, span_names)))
    if not devices:
        return None
    n_dev = len(devices)
    gap_list.sort(reverse=True)
    by_span: Dict[str, float] = {}
    for dur, _, where in gap_list:
        by_span[where] = by_span.get(where, 0.0) + dur / n_dev
    return {
        "window_s": window_s, "window_spans": len(spans[window_span]), "devices": devices,
        "device_ops": [[n, s / n_dev] for n, s in
                       sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[where, dur] for dur, _, where in gap_list[:top_gaps]],
        "idle_by_span_s": by_span, "collective_names": collective_names,
        "host_span_s": {n: [e - s for s, e in clip(spans[n], lo, hi)] for n in span_names},
    }


def mean_share_pct(trace: Optional[Dict[str, Any]], key: str) -> Optional[float]:
    """Mean over the devices of a reduced trace's `key` seconds, as a share
    of the traced window in %; nothing without a trace."""
    if not trace:
        return None
    return 100.0 * sum(d[key] for d in trace["devices"]) / len(trace["devices"]) / trace["window_s"]


def _attribute(lo: float, hi: float, spans: Dict[str, List[Interval]], names: Sequence[str]) -> str:
    """The host span that covers most of [lo, hi]; 'between_spans' if none."""
    best, best_s = "between_spans", 0.0
    for n in names:
        s = measure(clip(spans[n], lo, hi))
        if s > best_s:
            best, best_s = n, s
    return best
