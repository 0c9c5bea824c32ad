#!/usr/bin/env python3
"""One run of a benchmark cell as the driver makes it, and then what the run's
record kept of the router, step by step:

    chiprun -- python3 scripts/routing_record.py --workload mellum2-ep4-1chip.seq16k --seed 7 [--trace 1]

`benchmarks/run.py` prints the newest value of the step counters only in a
traced run and never their series; a cell that holds a SHARE of its experts
has a step time that follows the rows the router gives the held ones, so a
spread over seeds is read beside this line: `[routing] {"seed", "tokens_per_s",
"series": [[train_step call, {every `moe_*` step counter the cell's program
keeps: moe_load_max_over_mean, a share's moe_held_rows_mean / _max and
moe_rows_moved_share, a network router's moe_gate_mean, moe_experts_in_use
where the stored bias follows the load}], ...]}` (calls 0..2 are the compile
step and the warm-up, the window follows).  A diagnostic for PERF.md: no cell
or metric reads it, and the run's own result line comes first, unchanged.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks import run as harness

    argv = sys.argv[1:]
    ap = argparse.ArgumentParser()  # the three options that name the run's file; the rest is the harness's
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args, _ = ap.parse_known_args(argv)
    rc = harness.main(argv)
    from ray_tpu.train import last_run_record

    record = last_run_record() or {}
    cell, seed = args.workload, args.seed
    tag = f"{cell}.seed{seed}.trace{args.trace}" + (".rehearsal" if args.rehearse else "")
    values = {}
    try:
        with open(os.path.join(harness.OUT_DIR, tag + ".json")) as f:
            values = json.load(f)["values"]
    except (OSError, ValueError, KeyError):
        pass
    series = [[step, {k: round(float(v), 4) for k, v in sorted(counters.items()) if k.startswith("moe_")}]
              for step, counters in record.get("step_counter_series") or ()]
    print("[routing] " + json.dumps({"cell": cell, "seed": seed, "rc": rc,
                                     "tokens_per_s": None if args.rehearse else values.get("tokens_per_s_per_chip"), "series": series}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
