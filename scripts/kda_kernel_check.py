#!/usr/bin/env python3
"""On the chip: the KDA forward kernel (`ray_tpu/ops/pallas/kda.py`) beside the
plain form (`ray_tpu/ops/kda.py`) at the Kimi cell's shapes (1 x 16,384, 32
heads of 128), each against `kda_recurrent` at `highest`, and the time of both.

    chiprun -- python3 scripts/kda_kernel_check.py [--seeds 3]

Inputs have the statistics of the cell's own weights at initialisation
(`models/transformer.py`): q, k L2-normalised per head (q times 128^-0.5), v
the SiLU of a normal in bf16, g = -A softplus(x + dt_bias) with A uniform in
[1, 16] per head and softplus(dt_bias) log-uniform in [1e-3, 1e-1] per channel,
beta a sigmoid.  One JSON line per seed, then one per timing.  Exit 1 if the
kernel's error exceeds the plain form's by more than a tenth, or the two
differ by more than three bf16 passes' own rounding (3e-4)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import kda
from ray_tpu.ops.pallas import kda as kernels

B, S, H, D = 1, 16384, 32, 128


def inputs(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = unit(jax.nn.silu(jax.random.normal(ks[0], (B, S, H, D)))) * D ** -0.5
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (B, S, H, D))))
    v = jax.nn.silu(jax.random.normal(ks[2], (B, S, H, D))).astype(jnp.bfloat16)
    a = jax.random.uniform(ks[3], (H, 1), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (H, D), jnp.float32, np.log(1e-3), np.log(1e-1)))
    bias = dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus
    g = -a * jax.nn.softplus(0.5 * jax.random.normal(ks[5], (B, S, H, D)) + bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (B, S, H)))
    return q, k, v, g, beta


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def timed(f, *args, n: int = 5) -> float:
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's products", file=sys.stderr)
        return 1
    per = kda._per_segment(S, kda.CHUNK)
    segments = lambda x: kda._segments(x, kda.CHUNK, per)
    prepare = jax.jit(lambda q, k, v, g, beta: (*map(segments, (q, k, v, g)), beta, segments(beta[..., None])))
    kernel = jax.jit(lambda q, k, v, g, beta, _: kernels.kda_fwd(q, k, v, g, beta))
    plain = jax.jit(lambda q, k, v, g, _, beta: kda._plain_forward(q, k, v, g, beta))
    recurrent = jax.jit(kda.kda_recurrent)
    ok = True
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        raw = inputs(seed)
        prepared = prepare(*raw)
        want = recurrent(*raw[:2], raw[2].astype(jnp.float32), *raw[3:])
        o_kernel, s_kernel = kernel(*prepared)
        o_plain, s_plain = plain(*prepared)
        line = {
            "seed": seed,
            "kernel_vs_recurrent": rel(kda._positions(o_kernel), want),
            "plain_vs_recurrent": rel(kda._positions(o_plain), want),
            "kernel_vs_plain": rel(o_kernel, o_plain),
            "states_kernel_vs_plain": rel(s_kernel[1:], s_plain[1:]),
            "finite": bool(jnp.all(jnp.isfinite(o_kernel))),
            "g_min_chunk_sum": float(jnp.min(jnp.sum(raw[3].reshape(B, S // 64, 64, H, D), axis=2))),
        }
        ok &= line["finite"] and line["kernel_vs_recurrent"] <= 1.1 * line["plain_vs_recurrent"] + 1e-6
        ok &= line["kernel_vs_plain"] <= 3e-4
        print(json.dumps(line), flush=True)
    pairs = B * H * S // kda.CHUNK
    for name, f in (("plain", plain), ("kernel", kernel)):
        ms = timed(f, *prepared)
        print(json.dumps({"forward": name, "ms": ms, "us_per_chunk_and_head": ms * 1e3 / pairs}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
