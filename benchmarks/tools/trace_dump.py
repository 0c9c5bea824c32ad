#!/usr/bin/env python3
"""Look at a trace by hand before trusting a reduction of it:
`python benchmarks/tools/trace_dump.py <file.xplane.pb> [--top 25]` prints
each plane, its lines with event counts, and per line the names that took
most time with one event's stats."""

from __future__ import annotations

import argparse
import collections
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(args.path)
    for plane in profile.planes:
        print(f"PLANE {plane.name!r} stats={list(plane.stats)[:6]}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            sample = {}
            t_min, t_max = float("inf"), 0.0
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                sample.setdefault(e.name, e)
                t_min, t_max = min(t_min, e.start_ns), max(t_max, e.start_ns + e.duration_ns)
            n = sum(count.values())
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {len(count)} names, "
                  f"span {(t_max - t_min) / 1e6:.3f} ms, start {t_min / 1e6:.3f} ms")
            for name, ns in total.most_common(args.top):
                stats = [(k, str(v)[:60]) for k, v in list(sample[name].stats)[:8]]
                print(f"    {ns / 1e6:10.3f} ms  x{count[name]:<6} {name[:90]!r} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
