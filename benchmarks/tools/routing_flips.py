#!/usr/bin/env python3
"""How often a bf16 program and the float32 reference route a token
differently, per layer, at a cell's published widths on the chip:

    chiprun -- python3 benchmarks/tools/routing_flips.py --workload olmoe-1chip.seq4k --seed 1

Routing is discrete: near a tie of the K-th and (K+1)-th router probability
the two pick another expert, and the logits then differ by more than rounding
(benchmarks/lib/reference_moe.py).  This prints, for `reference_seqs` seeded
sequences of the cell's stream and the cell's seeded weights, the share of
tokens per layer whose chosen SET differs, the share of single choices that
differ, and the logits' relative RMS error beside the loop's tolerance.  The
program's choices are read by running its own layers one at a time with the
router's result recorded.  A diagnostic for PERF.md; no cell or metric reads it.
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
    ap.add_argument("--cpu-toy", action="store_true", help="the harness's rehearsal widths, on the CPU")
    args = ap.parse_args()
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.lib import datagen, reference_moe
    from ray_tpu.models import moe, transformer

    cell, config, traffic = harness.load_cell(args.workload)
    seq = traffic["seq_len"]
    if args.cpu_toy:
        config = dict(config, **harness.REHEARSAL_CONFIG)
        seq = harness.REHEARSAL_SEQ
    builder = harness.load_plugin("builders", config["kind"])
    cfg, ctx = builder.build(config, seq, jax.devices())
    params = jax.jit(lambda key: transformer.init_params(cfg, key))(jax.random.PRNGKey(args.seed))
    stream = datagen.PackedStream(args.seed + 1_000_003, config["vocab_size"], traffic["stream"])
    tokens = stream.next_batch(traffic["reference_seqs"], seq)["tokens"]
    last = seq if seq <= 1024 else 256

    theirs = []
    want = reference_moe.logits(config, params, tokens, last=last, record=theirs)
    got = ctx.apply(params, tokens)
    errors = [reference_moe.rel_rms_error(got[i, -last:], want[i]) for i in range(tokens.shape[0])]

    ours = []
    route = moe._route

    def recording_route(layer_params, flat_tokens, config_):
        out = route(layer_params, flat_tokens, config_)
        ours.append(np.asarray(out[0]).reshape(tokens.shape + (-1,)))
        return out

    moe._route = recording_route
    try:
        x = params["embed"]["tokens"].astype(cfg.dtype)[jnp.asarray(tokens)]
        positions = jnp.arange(seq)
        for layer in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
            x, _ = transformer._layer(x, lp, positions, cfg, None, None)
    finally:
        moe._route = route

    layers = []
    for a, b in zip(ours, theirs):
        a, b = np.sort(a, axis=-1), np.sort(np.asarray(b), axis=-1)
        missing = np.array([[len(set(p) - set(q)) for p, q in zip(pa, qa)] for pa, qa in zip(a, b)])
        layers.append({"tokens_with_another_set_pct": 100.0 * float(np.mean(missing > 0)),
                       "choices_that_differ_pct": 100.0 * float(np.mean(missing) / a.shape[-1]),
                       "most_in_one_token": int(missing.max())})
    out = {"cell": cell["name"], "seed": args.seed, "device": jax.devices()[0].device_kind,
           "widths": "toy" if args.cpu_toy else "published", "layers": layers,
           "rel_rms_error": errors, "tolerance": reference_moe.tolerance(cfg.n_layers)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
