#!/usr/bin/env python3
"""On the chip: the KDA kernels (`ray_tpu/ops/pallas/kda.py`) beside the plain
form (`ray_tpu/ops/kda.py`) at the Kimi cell's shapes (1 x 16,384, 32 heads of
128), each against `kda_recurrent` at `highest`, and the time of both.

    chiprun -- python3 scripts/kda_kernel_check.py [--seeds 3]              # the forward
    chiprun -- python3 scripts/kda_kernel_check.py --backward [--seeds 3]   # the five cotangents

`--backward`: the cotangents of a seeded probe on o, from `kda_bwd` and from
`_plain_backward` (JAX's differentiation of `_segment`), over the whole
sequence against each other and over its first 2,048 positions (one segment,
four programs of the kernel) against `jax.grad` of `kda_recurrent`: the
recurrence keeps one [32, 128, 128] state a token for its backward, 4.3 GB at
2,048.  Then the time of one layer's backward, both ways.

Inputs have the statistics of the cell's own weights at initialisation
(`models/mixers/kda.py`): q, k L2-normalised per head (q times 128^-0.5), v
the SiLU of a normal in bf16, g = -A softplus(x + dt_bias) with A uniform in
[1, 16] per head and softplus(dt_bias) log-uniform in [1e-3, 1e-1] per channel,
beta a sigmoid.  One JSON line per seed, then one per timing.  Exit 1 if the
kernel's error exceeds the plain form's by more than a tenth, or the two
differ by more than three bf16 passes' own rounding (3e-4); with `--backward`
the same of each cotangent."""

from __future__ import annotations

import argparse
import functools
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


NAMES = ("dq", "dk", "dv", "dg", "dbeta")
PREFIX = 2048


def backward(first_seed: int, seeds: int) -> bool:
    def segmented(*arrays):
        per = kda.per_segment(arrays[0].shape[1], kda.CHUNK)
        return tuple(kda.segments(x, kda.CHUNK, per) for x in arrays)

    def both(q, k, v, g, beta, probe):
        """(the kernel's five cotangents, the plain form's), as [b, S, H, d] / [b, S, H]."""
        *blocks, d_o, cut = segmented(q, k, v, g, probe, beta[..., None])
        pairs = kernels.kda_fwd(*blocks, beta, pair_states=True)[2]
        *d, dbeta = kernels.kda_bwd(*blocks, beta, pairs, d_o)
        kernel = (*map(kda.positions, d), dbeta)
        entering = kda.plain_forward(*blocks, cut)[1]
        *d, dbeta = kda.plain_backward(*blocks, cut, entering, d_o)
        return kernel, (*map(kda.positions, d), kda.positions(dbeta)[..., 0])

    both = jax.jit(both)
    recurrent = jax.jit(jax.grad(lambda q, k, v, g, beta, probe: jnp.sum(kda.kda_recurrent(q, k, v, g, beta) * probe),
                                 argnums=range(5)))
    ok = True
    for seed in range(first_seed, first_seed + seeds):
        q, k, v, g, beta = inputs(seed)
        probe = jax.random.normal(jax.random.PRNGKey(seed + 1), v.shape, jnp.float32)
        kernel, plain = both(q, k, v, g, beta, probe)
        line = {"seed": seed, "positions": S, "finite": all(bool(jnp.all(jnp.isfinite(x))) for x in kernel)}
        line.update({f"{name}_kernel_vs_plain": rel(a, b) for name, a, b in zip(NAMES, kernel, plain)})
        # a cotangent in bf16 (dv) is rounded once more, each side its own way: one ulp is 3.9e-3
        ok &= line["finite"] and all(line[f"{name}_kernel_vs_plain"] <= (3e-4 if a.dtype == jnp.float32 else 4e-3)
                                     for name, a in zip(NAMES, kernel))
        print(json.dumps(line), flush=True)
        head = tuple(x[:, :PREFIX] for x in (q, k, v.astype(jnp.float32), g, beta, probe))
        want = recurrent(*head)
        # one segment alone: positions past it have no part in its cotangents when the probe ends with it
        kernel, plain = both(*head[:2], head[2].astype(jnp.bfloat16), *head[3:])
        line = {"seed": seed, "positions": PREFIX}
        for name, a, b, w in zip(NAMES, kernel, plain, want):
            line[f"{name}_kernel_vs_recurrent"], line[f"{name}_plain_vs_recurrent"] = rel(a, w), rel(b, w)
            ok &= line[f"{name}_kernel_vs_recurrent"] <= 1.1 * line[f"{name}_plain_vs_recurrent"] + 1e-6
        print(json.dumps(line), flush=True)
    *blocks, d_o, cut = jax.jit(segmented)(q, k, v, g, probe, beta[..., None])
    _, entering, pairs = jax.jit(functools.partial(kernels.kda_fwd, pair_states=True))(*blocks, beta)
    heads_and_pairs = B * H * S // (2 * kda.CHUNK)
    for name, f, args in (("plain", kda.plain_backward, (*blocks, cut, entering, d_o)),
                          ("kernel", kernels.kda_bwd, (*blocks, beta, pairs, d_o)),
                          ("forward_with_pair_states", functools.partial(kernels.kda_fwd, pair_states=True),
                           (*blocks, beta)),
                          ("forward", kernels.kda_fwd, (*blocks, beta))):
        ms = timed(jax.jit(f), *args)
        print(json.dumps({"one_layer": name, "ms": ms, "us_per_pair_and_head": ms * 1e3 / heads_and_pairs}), flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--backward", action="store_true", help="the five cotangents and the backward's time")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's products", file=sys.stderr)
        return 1
    if args.backward:
        return 0 if backward(args.first_seed, args.seeds) else 1
    per = kda.per_segment(S, kda.CHUNK)
    segments = lambda x: kda.segments(x, kda.CHUNK, per)
    prepare = jax.jit(lambda q, k, v, g, beta: (*map(segments, (q, k, v, g)), beta, segments(beta[..., None])))
    kernel = jax.jit(lambda q, k, v, g, beta, _: kernels.kda_fwd(q, k, v, g, beta))
    plain = jax.jit(lambda q, k, v, g, _, beta: kda.plain_forward(q, k, v, g, beta))
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
            "kernel_vs_recurrent": rel(kda.positions(o_kernel), want),
            "plain_vs_recurrent": rel(kda.positions(o_plain), want),
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
