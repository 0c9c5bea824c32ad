#!/usr/bin/env python3
"""What the comparison that decides `correct` would read in the learned-sparse
cell if one of its mechanisms were got wrong, at the cell's own sizes:

    chiprun -- python3 scripts/dsa_control.py --seed 7 [--wrong dense_causal no_gate ...]

Builds `dots3-note-ep32-1chip.seq8k`'s program as the harness does (builder,
seeded `init_state`, the reference check's own seeded sequences), takes the
program's logits once, and compares them with the plain reference as it is
and with the reference computing ONE mechanism wrong
(`benchmarks/lib/reference_dots3_note.WRONG`: every causal key in place of the
selected set, the indexer without its weights or its ReLU, no gate, a window
one key short, no rescale of the latents, the sliding kind's rescale from the
full kind's rank).  Prints one line, `[control] {"tolerance",
"program_vs_reference", "wrong": {name: program_vs_wrong}}`, each a list of
relative rms errors, one a sequence: every control has to read over the
tolerance where the program reads under it.  A diagnostic for PERF.md
(section 6, PR 66); no cell or metric reads it.  `--cpu-toy` runs the
harness's rehearsal widths on the CPU (no device number).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "dots3-note-ep32-1chip.seq8k"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wrong", nargs="*", default=None)
    ap.add_argument("--cpu-toy", action="store_true")
    args = ap.parse_args()
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmarks import run as harness
    from benchmarks.lib import datagen, reference
    from benchmarks.lib import reference_dots3_note as ref

    _, config, traffic = harness.load_cell(CELL)
    if args.cpu_toy:
        config = dict(config, **harness.REHEARSAL_CONFIG)
        traffic = dict(traffic, seq_len=harness.REHEARSAL_SEQ)
    builder = harness.load_plugin("builders", config["kind"])
    seq, n_ref = traffic["seq_len"], traffic["reference_seqs"]
    last = seq if seq <= 1024 else 256
    _, ctx = builder.build(config, seq, jax.devices())
    params = ctx.init_state(seed=args.seed)["params"]
    tokens = datagen.PackedStream(args.seed + 1_000_003, config["vocab_size"], traffic["stream"]).next_batch(n_ref, seq)["tokens"]
    got = [jax.device_get(ctx.apply(params, tokens[i: i + 1])[0, -last:]) for i in range(n_ref)]

    def errors(wrong=None):
        want = ref.logits(config, params, tokens, last=last, wrong=wrong)
        return [reference.rel_rms_error(x, y) for x, y in zip(got, want)]

    out = {"cell": CELL, "seed": args.seed, "positions": last, "tolerance": reference.tolerance(config["num_hidden_layers"]),
           "program_vs_reference": errors(), "wrong": {}}
    for name in args.wrong if args.wrong is not None else ref.WRONG:
        out["wrong"][name] = errors(name)
    print("[control] " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
