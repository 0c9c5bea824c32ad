#!/usr/bin/env python3
"""The device ops under a program scope, one by one: what a name of
`[bench] kimi {...}` / `[bench] qwen3_next {...}` is made of.

    python3 scripts/scope_ops.py <trace dir or .xplane.pb> <name> [<name> ...] [--top 40] [--out file.json]

reads a traced run's file (`benchmarks/out/traces/<tag>/`; the file stays on
the machine that wrote it, so run this in the SAME `chiprun` call as the
traced run) and prints, for every op whose innermost name among the given
ones is `<name>` (`kda/conv`, `gdn/conv`, ..; the rule is `trace_moe`'s), one
JSON line: its direction (`trace_scopes.classify`: fwd / recompute / bwd),
the instruction with its result's shape AND layout, whether it is a Mosaic
kernel, its calls and its milliseconds of self time a traced step.  Then one
line a name and direction with the sums.  The window, the clipping and the
self times are `benchmarks/lib/trace_reduce.py`'s, the paths
`trace_scopes.event_paths`'s: no arithmetic of its own but the grouping.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks.lib import trace_reduce as tr  # noqa: E402
from benchmarks.lib import trace_scopes as ts  # noqa: E402
from benchmarks.loops import train_steps  # noqa: E402

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def _label(text: str) -> str:
    """`name opcode result-shape{layout} [kind]`: `trace_reduce.op_label` drops the layout, which is the question here."""
    head, sep, rest = text.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    if not m:
        return tr.op_name(text)
    kind = re.search(r"kind=(\w+)", rest)
    return " ".join(filter(None, [tr.op_name(text), m.group(1), rest[: m.start()].strip()[:120], kind and kind.group(1)]))


def ops_under(path: str, names, window_span: str = train_steps.STEP_SPAN):
    """{(name, direction, label, is kernel): [calls, seconds]} over the traced window of one device, and the steps."""
    from jax.profiler import ProfileData

    component = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, names)) + r")(?=[/):]|$)")
    data = ts._read_bytes(path)
    paths = ts.event_paths(data)
    profile = ProfileData.from_serialized_xspace(data)
    spans = tr.host_spans(profile, [window_span])[window_span]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    rows = collections.defaultdict(lambda: [0, 0.0])
    for plane in profile.planes:
        line = tr.DEVICE_PLANE.match(plane.name) and next((l for l in plane.lines if l.name == tr.OP_LINE), None)
        if not line:
            continue
        table, key_of, events = paths.get(plane.name, {}), {}, []
        for text, s, e in tr._events(line):
            if min(e, hi) > max(s, lo):
                op = tr.op_name(text)
                events.append((op, max(s, lo), min(e, hi)))
                if op not in key_of:
                    op_path = table.get(text)
                    found = component.findall(op_path) if op_path else None
                    key_of[op] = found and (found[-1], ts.classify(op_path)[1], _label(text), ts.TPU_CALL in text)
        for op, _, _, t in tr.self_times(events):
            if key_of[op]:
                rows[key_of[op]][0] += 1
                rows[key_of[op]][1] += t
        break  # one device: the cells this is for run on one chip
    return rows, len(spans)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("names", nargs="+")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--out")
    args = ap.parse_args()
    path = args.trace
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))[-1]
    rows, steps = ops_under(path, args.names)
    lines, sums = [], collections.defaultdict(float)
    for (name, direction, label, kernel), (calls, seconds) in sorted(rows.items(), key=lambda kv: -kv[1][1]):
        sums[name, direction] += seconds
        lines.append({"name": name, "direction": direction, "op": label, "kernel": kernel,
                      "calls_per_step": calls / steps, "ms_per_step": 1e3 * seconds / steps})
    totals = [{"name": n, "direction": d, "ms_per_step": 1e3 * s / steps} for (n, d), s in sorted(sums.items())]
    for name in args.names:
        for line in [l for l in lines if l["name"] == name][: args.top]:
            print("[ops] " + json.dumps(line))
    for total in totals:
        print("[ops total] " + json.dumps(total))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"trace": path, "steps": steps, "ops": lines, "totals": totals}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
