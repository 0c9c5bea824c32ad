#!/usr/bin/env python3
"""Every metric the program's train step returns, step by step, on a cell's
seeded weights and stream at its published widths on the chip:

    chiprun -- python3 benchmarks/tools/step_metrics.py --workload olmoe-1chip.seq4k --seed 1

The benchmark's loop fetches `metrics["loss"]` alone (the objective that is
differentiated).  For a model with experts the step also returns `ce_loss`,
`moe_lb_loss`, `moe_z_loss` (unweighted) and `moe_load_max_over_mean`; this
prints them beside the loss, the same batches the cell's run draws.  Bare
`LMTrainContext.train_step`, no runtime: a diagnostic for PERF.md, not a
measurement of speed, and no cell or metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--cpu-toy", action="store_true", help="the harness's rehearsal widths, on the CPU")
    args = ap.parse_args()
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmarks import run as harness
    from benchmarks.lib import datagen

    cell, config, traffic = harness.load_cell(args.workload)
    seq = traffic["seq_len"]
    if args.cpu_toy:
        config, seq = dict(config, **harness.REHEARSAL_CONFIG), harness.REHEARSAL_SEQ
    builder = harness.load_plugin("builders", config["kind"])
    _, ctx = builder.build(config, seq, jax.devices())
    state = ctx.init_state(seed=args.seed)
    stream = datagen.PackedStream(args.seed, config["vocab_size"], traffic["stream"])
    print(json.dumps({"cell": cell["name"], "seed": args.seed, "device": jax.devices()[0].device_kind,
                      "widths": "toy" if args.cpu_toy else "published"}))
    for step in range(args.steps):
        state, metrics = ctx.train_step(state, stream.next_batch(traffic["seqs_per_chip"] * cell["chips"], seq))
        print(json.dumps({"step": step, **{k: round(float(v), 4) for k, v in sorted(metrics.items()) if k != "step"}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
