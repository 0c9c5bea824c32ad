#!/usr/bin/env python3
"""On the chip: what the predicated-off grid steps of a causal flash call cost, by kernel, under the map `j -> j`
(every step names its own block, so the pipeline copies for a step that computes nothing) and under the maps the
kernels have since PR 55 (held on the diagonal's tile through the off steps: `flash_attention._inner_tile`).

    chiprun -- python3 scripts/flash_off_steps_check.py [--heads 32] [--d 128] [--seqs 4096 8192 16384 32768]
        [--off-seq 16384] [--tiles 1024 1024 512]

Two measurements, both maps in ONE process (`_inner_tile` patched for the first), bf16, one sequence:
1. by length: ms a call of the forward, of dq alone and of dkv alone (`_flash_bwd` with the other output dropped, so
   XLA removes its kernel; `delta`'s elementwise pass rides both), and whether out, lse, dq, dk, dv are bit-equal
   between the two maps;
2. ONE off step: a call with `--off-seq` queries against as many keys and against twice as many.  The keys past the
   last query are masked for every query, so the second call runs the SAME visible pairs and adds only off steps
   (forward `n_q * n_k` a head, dq and dkv `n_q * 2 n_k` at the backward's key tile; dkv's lie in rows of their own,
   each with an init and a write of zeros): the difference over their count is the time of one.
Lengths alone cannot separate a run step from an off step: `run - off` is the row count at every length.
Off the chip it exits 1 before it times anything (the kernels would run interpreted); the device is the first line.
PERF.md section 6, PR 55, holds the readings (TPU v5 lite): an off step 0.78 -> 0.24 us (forward), 0.43 -> 0.20 (dq),
2.22 -> 0.30 (dkv).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.pallas import flash_attention as fa

_held = fa._inner_tile


def _every_step_its_own(*args, **masks):
    n, tile = _held(*args, **masks)
    return (n, lambda i, j: j) if args[4] is None else (n, tile)


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--seqs", type=int, nargs="*", default=[4096, 8192, 16384, 32768])
    ap.add_argument("--off-seq", type=int, default=16384)
    ap.add_argument("--tiles", type=int, nargs=3, default=[1024, 1024, 512], metavar=("BLOCK_Q", "BLOCK_K", "BWD_BLOCK_K"))
    ap.add_argument("--reps", type=int, default=12)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: off it the kernels run interpreted and their times say nothing", file=sys.stderr)
        return 1
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    h, d, (bq, bk, bbk) = args.heads, args.d, args.tiles
    scale = d ** -0.5

    def bwd(pick):
        return jax.jit(lambda q, k, v, o, l, g: pick(fa._flash_bwd(
            q, k, v, o, l, g, causal=True, scale=scale, block_q=bq, block_k=bbk)))

    def calls(sq, sk):
        """(ms a call by kernel, the five results) of one sequence of `sq` queries and `sk` keys.  Every program
        is jitted HERE: a jitted function kept across the two maps would hit its cache and run the first map's."""
        fwd = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, causal=True, scale=scale, block_q=bq, block_k=bk))
        keys = jax.random.split(jax.random.PRNGKey(sq + sk), 4)
        q, g = (jax.random.normal(kk, (1, sq, h, d), jnp.bfloat16) for kk in keys[:2])
        k, v = (jax.random.normal(kk, (1, sk, h, d), jnp.bfloat16) for kk in keys[2:])
        o, lse = jax.block_until_ready(fwd(q, k, v))
        ms = {"fwd": timed(fwd, (q, k, v), args.reps), "dq": timed(bwd(lambda r: r[0]), (q, k, v, o, lse, g), args.reps),
              "dkv": timed(bwd(lambda r: r[1:]), (q, k, v, o, lse, g), args.reps)}
        grads = jax.block_until_ready(bwd(lambda r: r)(q, k, v, o, lse, g))
        return {n: 1e3 * t for n, t in ms.items()}, [np.asarray(x.astype(jnp.float32)) for x in (o, lse, *grads)]

    results = {}
    for maps, inner in (("j->j", _every_step_its_own), ("held", _held)):
        fa._inner_tile = inner
        for seq in args.seqs:
            ms, results[maps, seq] = calls(seq, seq)
            print(json.dumps({"maps": maps, "seq": seq, **{n: round(t, 4) for n, t in ms.items()},
                              "unit": f"ms a call of {h} heads"}), flush=True)
        sq = args.off_seq
        (once, _), (twice, _) = calls(sq, sq), calls(sq, 2 * sq)
        extra = {"fwd": (sq // bq) * (sq // bk), "dq": (sq // bq) * (sq // bbk), "dkv": (sq // bbk) * (sq // bq)}
        print(json.dumps({"maps": maps, "off_step_us": {n: round(1e3 * (twice[n] - once[n]) / h / extra[n], 4) for n in extra},
                          "extra_off_steps_a_head": extra, "queries": sq}), flush=True)
    fa._inner_tile = _held
    for seq in args.seqs:
        same = [bool(np.array_equal(a, b)) for a, b in zip(results["j->j", seq], results["held", seq])]
        print(json.dumps({"seq": seq, "bit_equal out lse dq dk dv": same}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
