#!/usr/bin/env python3
"""The three flash kernels UNDER THE BLOCK-DIFFUSION MASK against the explicit-mask softmax, on the chip:

    chiprun -- python3 scripts/flash_diffusion_check.py

The cell's comparisons with the reference (`benchmarks/builders/block_diffusion_moe_decoder.reference_logits`) hold the
FORWARD kernel; the dq and dkv kernels under the mask are compared on the CPU only, interpreted
(`tests/test_ops_attention.py`).  This runs all three as Mosaic compiles them: one sequence's doubled rows `[x_t ‖ x_0]`
(2 x `--seq`), GQA 8:1 at heads of 128 (the cell's), bf16 operands, the kernels' default tiles, blocks of 4 and of 32;
out, dq, dk and dv of `sum(out * g)` against `ops.attention.reference_attention` under the same mask in float32 at
precision "highest" on the same bf16-rounded operands.  Beside each, the same comparison for a CAUSAL call over the same
rows (the kernels as every other cell runs them): what bf16 costs, mask or no mask.  Prints one line a case,
`{"mask", "rel_rms_error": {"out", "dq", "dk", "dv"}}`.  A diagnostic for PERF.md (section 6, PR 62); no cell or metric
reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import BlockDiffusion, reference_attention
from ray_tpu.ops.pallas import flash_attention as fa


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096, help="tokens of the sequence: the call has twice as many rows")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=1)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--cpu-toy", action="store_true", help="a rehearsal of the script itself: off the chip the dispatch gives "
                    "the XLA form, so the numbers say nothing of the kernels")
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.cpu_toy:
        print("this check needs the chip: off it no Mosaic kernel runs", file=sys.stderr)
        return 1
    print(json.dumps({"device": jax.devices()[0].device_kind, "rows": 2 * args.seq}), flush=True)
    rows, scale = 2 * args.seq, args.d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(62), 4)
    q, g = (jax.random.normal(kk, (1, rows, args.heads, args.d), jnp.bfloat16) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (1, rows, args.kv_heads, args.d), jnp.bfloat16) for kk in keys[2:])

    def both(fn, *operands):
        out, vjp = jax.vjp(fn, *operands[:3])
        return (out, *vjp(operands[3].astype(out.dtype)))

    for mask, kw in [(f"block_diffusion B={b}", dict(causal=False, block_diffusion=BlockDiffusion(b, args.seq))) for b in (4, 32)] \
            + [("causal", dict(causal=True))]:
        got = jax.jit(lambda *a, kw=kw: both(lambda q, k, v: fa.flash_attention(q, k, v, scale=scale, **kw), *a))(q, k, v, g)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a, kw=kw: both(lambda q, k, v: reference_attention(q, k, v, scale=scale, **kw), *a))(
                *(x.astype(jnp.float32) for x in (q, k, v, g)))
        print(json.dumps({"mask": mask, "rel_rms_error": {n: rel(a, b) for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
