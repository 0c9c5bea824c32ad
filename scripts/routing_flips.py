#!/usr/bin/env python3
"""How often a bf16 program and the float32 reference give a token ANOTHER expert in a cell whose router carries a
state from layer to layer (`router_kind` "mlp", top-1: a flipped argmax changes the token's whole routed output), a
layer, what part of the comparison behind `correct` the flips are, and the comparison's control of precision:

    chiprun -- python3 scripts/routing_flips.py --seed 1 [--lowered]      # published widths, the cell's traffic
    python3 scripts/routing_flips.py --cpu --tokens 2048 --seed 1         # published widths on the CPU, a shorter sequence
    python3 scripts/routing_flips.py --cpu-toy                            # the harness's rehearsal widths

`benchmarks/tools/routing_flips.py` (accepted; the linear routers' tool) reads `reference_moe` and one `layers` stack
through `transformer._layer`; this one runs the stack's own `transformer.layer` with the carried state and a reference
that takes `record=` / `routing=` / `lowered=` (`reference_zaya`).  For the cell's seeded weights and `reference_seqs`
seeded sequences of its stream it prints, a layer, the share of tokens whose expert differs and the mean gap between the
reference's best two scores at the flipped tokens beside all tokens'; `rel_rms_error` as the loop compares it beside the
tolerance; the same error against the reference GIVEN THE PROGRAM'S CHOICES (`logits(routing=...)`: a diagnostic, never
the comparison that decides `correct`), which is what bf16 alone costs: the rest, in squares, is the flips'; and with
`--lowered` what the reference itself reads against itself with its float32 parts (the router, the q|k mixing) in
bfloat16 (`logits(lowered=True)`), with the share of its tokens that change expert.  Also the routed branch's rms
beside the stream's it joins, a layer.  A diagnostic for PERF.md; no cell or metric reads it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="zaya1-vp8-1chip.seq16k")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=None, help="a shorter sequence than the cell's")
    ap.add_argument("--cpu", action="store_true", help="published widths on the CPU")
    ap.add_argument("--cpu-toy", action="store_true", help="the harness's rehearsal widths, on the CPU")
    ap.add_argument("--lowered", action="store_true", help="also the reference with its float32 parts in bfloat16, against itself")
    args = ap.parse_args()
    if args.cpu or args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.lib import datagen, reference_zaya as ref
    from ray_tpu.models import moe, transformer
    from ray_tpu.models.mixers import MIXERS

    cell, config, traffic = harness.load_cell(args.workload)
    seq = args.tokens or traffic["seq_len"]
    if args.cpu_toy:
        config = dict(config, **harness.REHEARSAL_CONFIG)
        seq = harness.REHEARSAL_SEQ
    builder = harness.load_plugin("builders", config["kind"])
    cfg, ctx = builder.build(config, seq, jax.devices())
    params = jax.jit(lambda key: transformer.init_params(cfg, key))(jax.random.PRNGKey(args.seed))
    stream = datagen.PackedStream(args.seed + 1_000_003, config["vocab_size"], traffic["stream"])
    tokens = jnp.asarray(stream.next_batch(traffic["reference_seqs"], seq)["tokens"])
    last = seq if seq <= 1024 else 256

    theirs, scores = [], []
    route = ref._route_jit

    def scoring_route(*a, **kw):  # the reference's own scores beside its choices
        out = route(*a, **kw)
        scores.append(np.asarray(out[3]))
        return out

    ref._route_jit = scoring_route
    try:
        want = ref.logits(config, params, tokens, last=last, record=theirs)
    finally:
        ref._route_jit = route
    got = ctx.apply(params, tokens)
    errors = [ref.rel_rms_error(got[i, -last:], want[i]) for i in range(tokens.shape[0])]

    # the program's choices: its own layers one at a time, the router's result returned beside the stream
    def one_layer(x, lp, carried):
        seen, own = [], moe._route

        def recording(*a, **kw):
            out = own(*a, **kw)
            seen.append(out[0])
            return out

        moe._route = recording
        try:
            x, _, _, carried = transformer.layer(MIXERS["cca"], x, lp, jnp.arange(seq), cfg, None, carried=carried)
        finally:
            moe._route = own
        return x, carried, seen[0].reshape(x.shape[0], seq)

    one_layer = jax.jit(one_layer)
    x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
    carried = {"router_state": jnp.zeros((*x.shape[:2], cfg.router_hidden), jnp.float32)}
    ours = []
    for layer in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], params[ref.STACK])
        x, carried, idx = one_layer(x, lp, carried)
        ours.append(np.asarray(idx))

    given = ref.logits(config, params, tokens, last=last, routing=ours)
    errors_given = [ref.rel_rms_error(got[i, -last:], given[i]) for i in range(tokens.shape[0])]

    layers = []
    n = tokens.shape[0]
    for l, (a, b) in enumerate(zip(ours, theirs)):
        b = np.asarray(b)
        p = np.stack(scores[l * n:(l + 1) * n])  # [N, T, E]
        top = np.sort(p, axis=-1)
        gap = top[..., -1] - top[..., -2]
        flipped = a != b
        layers.append({"tokens_with_another_expert_pct": 100.0 * float(np.mean(flipped)),
                       "gap_of_best_two_at_flipped_tokens": float(np.mean(gap[flipped])) if flipped.any() else None,
                       "gap_of_best_two_all_tokens": float(np.mean(gap)), "mean_gate": float(np.mean(top[..., -1])),
                       "busiest_expert_share_pct": 100.0 * float(np.max(np.bincount(b.reshape(-1), minlength=p.shape[-1])) / b.size)})
    control = {}
    if args.lowered:
        lows = []
        low = ref.logits(config, params, tokens, last=last, lowered=True, record=lows)
        control = {"rel_rms_error_of_the_lowered_reference": [ref.rel_rms_error(low[i], want[i]) for i in range(n)],
                   "lowered_tokens_with_another_expert_pct": [100.0 * float(np.mean(np.asarray(a) != np.asarray(b))) for a, b in zip(lows, theirs)]}
    worst = max(range(n), key=lambda i: errors[i])
    flips_part = math.sqrt(max(errors[worst] ** 2 - errors_given[worst] ** 2, 0.0))
    print(json.dumps({"cell": cell["name"], "seed": args.seed, "device": jax.devices()[0].device_kind, "tokens": seq,
                      "widths": "toy" if args.cpu_toy else "published", "layers": layers, "rel_rms_error": errors,
                      "rel_rms_error_given_the_programs_choices": errors_given, "flips_part_of_the_worst": flips_part,
                      "tolerance": ref.tolerance(cfg.n_layers), **control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
