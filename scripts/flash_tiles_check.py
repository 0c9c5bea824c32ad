#!/usr/bin/env python3
"""On the chip: the three flash kernels at one head shape, timed at several tilings.

    chiprun -- python3 scripts/flash_tiles_check.py [--heads 20] [--d-qk 256] [--d-v 256] [--seq 8192]
        [--tiles 1024x512x1024x512 512x1024x1024x512 ...]

One sequence of `--seq` positions, causal, bf16, `--heads` heads of `--d-qk` /
`--d-v` (GLM-4.7-Flash's latent attention: 20 heads of 256 / 256 at 8,192).
Per tiling `block_q x block_k x bwd_block_q x bwd_block_k` one line: ms a call
of the forward alone and of forward + backward (the median of `--reps` calls,
each ended by `block_until_ready`), the needed causal FLOPs over the time as a
share of the chip's bf16 peak, and the output's distance from plain softmax
attention on the first 1,024 positions.  A tiling the compiler refuses (scoped
VMEM) prints its reason and the run goes on.  `default` is what
`flash_attention` picks by itself (`_head_blocks`: PERF.md section 6, PR 54).
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

from benchmarks.lib import flops
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.pallas.flash_attention import flash_attention


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--d-qk", type=int, default=256)
    ap.add_argument("--d-v", type=int, default=256)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiles", nargs="*", default=["1024x512x1024x512", "512x1024x1024x512", "512x512x1024x512",
                                                   "1024x512x512x512", "1024x512x1024x256", "1024x512x512x1024"])
    args = ap.parse_args()
    device = jax.devices()[0]
    peak = flops.load_peaks(device.device_kind)["bf16_flops_per_s"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda d: (1, args.seq, args.heads, d)  # noqa: E731
    q, k = (jax.random.normal(key, shape(args.d_qk), jnp.bfloat16) for key in ks[:2])
    v, do = (jax.random.normal(key, shape(args.d_v), jnp.bfloat16) for key in ks[2:])
    fwd_flops = 2.0 * args.seq * args.seq / 2 * args.heads * (args.d_qk + args.d_v)  # QK^T and PV, causal half
    want = reference_attention(q[:, :1024].astype(jnp.float32), k[:, :1024].astype(jnp.float32), v[:, :1024].astype(jnp.float32))
    print(json.dumps({"device": device.device_kind, "shape": shape(args.d_qk), "d_v": args.d_v}), flush=True)
    for tiles in ["default", *args.tiles]:
        kw = {} if tiles == "default" else dict(zip(("block_q", "block_k", "bwd_block_q", "bwd_block_k"),
                                                    map(int, tiles.split("x"))))
        fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw).astype(jnp.float32) * do),
                                argnums=(0, 1, 2)))
        try:
            t_fwd, t_both = timed(fwd, (q, k, v), args.reps), timed(both, (q, k, v), args.reps)
            got = fwd(q, k, v)[:, :1024].astype(jnp.float32)
            err = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
        except Exception as e:  # noqa: BLE001: the compiler's refusal is the answer
            print(json.dumps({"tiles": tiles, "refused": f"{type(e).__name__}: {str(e)[:300]}"}), flush=True)
            continue
        print(json.dumps({"tiles": tiles, "fwd_ms": 1e3 * t_fwd, "fwd_bwd_ms": 1e3 * t_both,
                          "fwd_pct_of_peak": 100 * fwd_flops / peak / t_fwd,
                          "fwd_bwd_pct_of_peak": 100 * 3 * fwd_flops / peak / t_both, "rel_rms_vs_softmax": err}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
