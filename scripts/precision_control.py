#!/usr/bin/env python3
"""What the comparison that decides `correct` would read if float32 were
forgotten where a configuration states it, at the cell's own sizes:

    chiprun -- python3 scripts/precision_control.py --workload mellum2-ep4-1chip.seq16k --seed 7

Builds the cell's program as the harness does (builder, seeded `init_state`,
the reference check's own seeded sequences), takes the program's logits once,
and compares them with the plain reference as it is and with the reference
computing ONE of the stated-float32 parts in bfloat16 (the reference's
`STATED`: router, norms' statistics, rope), then all of them.  Prints one line,
`[control] {"tolerance", "program_vs_reference", "lowered": {part:
{"program_vs_lowered", "lowered_vs_reference"}}}`, each a list of relative rms
errors, one a sequence.  A cell of the kind `mla_moe_decoder`
(`glm47-flash-ep8-1chip.seq8k`) has TWO compared outputs, the main logits and
the multi-token-prediction module's (`ctx.apply_mtp` against the reference's
second output): every list is then the main logits' errors followed by the
module's.  A cell of the kind `block_diffusion_moe_decoder`
(`sdar-ep8-1chip.seq8k`) has two as well: the plain forward's logits, then the
TIMED training forward's on the noisy rows (`ctx.apply_diffusion` on `[x_t ‖
x_0]` with the builder's fixed noise, against `reference_sdar.training_logits`).
A diagnostic for PERF.md (section 6, PRs 50 and 54: the limit of the
harness beside both readings); no cell or metric reads it.  `--cpu-toy` runs
the harness's rehearsal widths on the CPU (no device number).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-toy", action="store_true")
    args = ap.parse_args()
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.lib import datagen, reference

    _, config, traffic = harness.load_cell(args.workload)
    if args.cpu_toy:
        config = dict(config, **harness.REHEARSAL_CONFIG)
        traffic = dict(traffic, seq_len=harness.REHEARSAL_SEQ)
    builder = harness.load_plugin("builders", config["kind"])

    seq, n_ref = traffic["seq_len"], traffic["reference_seqs"]
    last = seq if seq <= 1024 else 256
    _, ctx = builder.build(config, seq, jax.devices())
    params = ctx.init_state(seed=args.seed)["params"]
    stream = datagen.PackedStream(args.seed + 1_000_003, config["vocab_size"], traffic["stream"])
    tokens = stream.next_batch(n_ref, seq)["tokens"]
    got = [jax.device_get(ctx.apply(params, tokens[i: i + 1])[0, -last:]) for i in range(n_ref)]
    if config["kind"] == "mla_moe_decoder":  # two compared outputs: the main logits, then the module's
        from benchmarks.lib import reference_glm_moe_lite as ref

        after = np.roll(tokens, -1, axis=1)  # as the builder's comparison takes the token after each position
        got += [jax.device_get(ctx.apply_mtp(params, tokens[i: i + 1], after[i: i + 1])[0, -last:]) for i in range(n_ref)]

        def reference_outputs(lowered=()):
            main, module = ref.both_logits(config, params, tokens, after, last=last, lowered=lowered)
            return [*main, *module]
    elif config["kind"] == "block_diffusion_moe_decoder":  # two too: the plain forward, then the training forward's noisy rows
        from benchmarks.lib import reference_sdar as ref

        noisy = np.asarray(ref.noise(jax.random.PRNGKey(builder.NOISE_KEY), jax.numpy.asarray(tokens),
                                     block=builder.block_length(config), mask_id=ref.mask_id(config),
                                     eps=float(config["assumed"]["noise_schedule"]["eps"]))[0])
        got += [jax.device_get(ctx.apply_diffusion(params, noisy[i: i + 1], tokens[i: i + 1])[0, -last:]) for i in range(n_ref)]

        def reference_outputs(lowered=()):
            return [*ref.logits(config, params, tokens, last=last, lowered=lowered),
                    *ref.training_logits(config, params, noisy, tokens, last=last, lowered=lowered)]
    else:
        from benchmarks.lib import reference_mellum as ref

        def reference_outputs(lowered=()):
            return list(ref.logits(config, params, tokens, last=last, lowered=lowered))

    def errors(a, b):
        return [reference.rel_rms_error(x, y) for x, y in zip(a, b)]

    want = reference_outputs()
    out = {"cell": args.workload, "seed": args.seed, "positions": last,
           "tolerance": reference.tolerance(config["num_hidden_layers"]),
           "program_vs_reference": errors(got, want), "lowered": {}}
    for parts in [(part,) for part in ref.STATED] + [ref.STATED] + ([(ref.WEIGHTS,)] if hasattr(ref, "WEIGHTS") else []):
        low = reference_outputs(parts)
        out["lowered"]["+".join(parts)] = {"program_vs_lowered": errors(got, low), "lowered_vs_reference": errors(low, want)}
        del low
    print("[control] " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
