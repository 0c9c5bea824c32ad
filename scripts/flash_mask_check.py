#!/usr/bin/env python3
"""On the chip: what the mask costs a run step of the three flash kernels, and that leaving it off the wholly visible
tiles changes no bit.  Since PR 63 a run step whose tile the mask's edge does not cross takes the body without the
mask's index compares and select (since PR 70 the kind word of `flash_attention.tile_pairs` says which); here each kernel
runs as written and with the masked bit forced onto every pair (every grid step masked: the kernels of PR 62), both in ONE
process.

    chiprun -- python3 scripts/flash_mask_check.py [--heads 32] [--reps 12]

Three calls, bf16, one sequence, the tiles `flash_attention` gives them: 16,384 causal positions at heads of 128
(`mistral7b-1chip.seq16k`), 8,192 causal at heads of 256 / 256 (`glm47-flash`: the forward on a key tile of 512), and
2 x 8,192 block-diffusion rows in blocks of 4 (`sdar-ep8-1chip.seq8k`).  By kernel: ms a call both ways; the us of a
masked grid step (the forced call's time over its pairs: since PR 70 there is no off step to take out) and of an unmasked
one (the written call's time less its crossed pairs, over its wholly visible ones); and whether out, lse, dq, dk, dv are
bit-equal.  dq and dkv are timed alone (`_flash_bwd` with the other output dropped,
so XLA removes its kernel; `delta`'s elementwise pass rides both).  Off the chip it exits 1 before it times anything.
PERF.md section 6, PR 63, holds the readings.
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

from ray_tpu.ops.attention import BlockDiffusion
from ray_tpu.ops.pallas import flash_attention as fa

_written = fa.tile_pairs
# (name, rows, q/k head size, v head size, block-diffusion mask or None for the causal one)
SHAPES = (("causal-16384-d128", 16384, 128, 128, None), ("causal-8192-d256", 8192, 256, 256, None),
          ("diffusion-2x8192-d128", 16384, 128, 128, BlockDiffusion(4, 8192)))


def _crossed(*call):
    table = _written(*call)
    return table._replace(kind=table.kind | fa._MASKED)  # every grid step takes the masked body


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
    ap.add_argument("--reps", type=int, default=12)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: off it the kernels run interpreted and their times say nothing", file=sys.stderr)
        return 1
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    h = args.heads

    def calls(rows, d, dv, bd, tiles):
        """(ms a call by kernel, the five results).  Every program is jitted HERE: a jitted function kept across the
        two predicates would hit its cache and run the first one's kernels."""
        mask = dict(causal=bd is None, scale=d ** -0.5, diffusion=bd)
        fwd = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, block_q=tiles[0], block_k=tiles[1], **mask))
        bwd = lambda pick: jax.jit(lambda q, k, v, o, l, g: pick(fa._flash_bwd(  # noqa: E731
            q, k, v, o, l, g, block_q=tiles[2], block_k=tiles[3], **mask)))
        keys = jax.random.split(jax.random.PRNGKey(rows + d), 4)
        q, k = (jax.random.normal(kk, (1, rows, h, d), jnp.bfloat16) for kk in keys[:2])
        v, g = (jax.random.normal(kk, (1, rows, h, dv), jnp.bfloat16) for kk in keys[2:])
        o, lse = jax.block_until_ready(fwd(q, k, v))
        ms = {"fwd": timed(fwd, (q, k, v), args.reps), "dq": timed(bwd(lambda r: r[0]), (q, k, v, o, lse, g), args.reps),
              "dkv": timed(bwd(lambda r: r[1:]), (q, k, v, o, lse, g), args.reps)}
        grads = jax.block_until_ready(bwd(lambda r: r)(q, k, v, o, lse, g))
        return {n: 1e3 * t for n, t in ms.items()}, [np.asarray(x.astype(jnp.float32)) for x in (o, lse, *grads)]

    for name, rows, d, dv, bd in SHAPES:
        blocks = fa._head_blocks(d, dv, fa.DEFAULT_BLOCKS)
        tiles = fa._diffusion_blocks(rows, bd, blocks) if bd is not None else tuple(fa._fit_block(rows, b) for b in blocks)
        ms, results = {}, {}
        for form, table in (("masked", _crossed), ("written", _written), ("masked again", _crossed)):
            fa.tile_pairs = table
            ms[form], results[form] = calls(rows, d, dv, bd, tiles)
        fa.tile_pairs = _written
        line = {"shape": name, "tiles": tiles, "heads": h}
        for kernel, (bq, bk) in (("fwd", tiles[:2]), ("dq", tiles[2:]), ("dkv", tiles[2:])):
            clear, run = fa.run_steps_unmasked(rows, bq, bk, None, bd)
            masked_ms = min(ms["masked"][kernel], ms["masked again"][kernel])
            masked_us = 1e3 * masked_ms / h / run
            line[kernel] = {
                "ms_masked": round(masked_ms, 4), "ms_written": round(ms["written"][kernel], 4),
                "ms_masked_both_runs": [round(ms[f][kernel], 4) for f in ("masked", "masked again")],
                "change_pct": round(100 * (ms["written"][kernel] / masked_ms - 1), 2),
                "pairs_a_head": {"wholly_visible": clear, "crossed": run - clear},
                "us_masked_step": round(masked_us, 3),
                "us_unmasked_step": round((1e3 * ms["written"][kernel] / h - (run - clear) * masked_us) / clear, 3),
            }
        line["bit_equal out lse dq dk dv"] = [bool(np.array_equal(a, b)) for a, b in zip(results["masked"], results["written"])]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
