#!/usr/bin/env python3
"""On the chip: the scalar-decay kernels (`ray_tpu/ops/pallas/gdn.py`) at the
Qwen3-Next cell's shapes (1 x 8,192, 16 key heads and 32 value heads of 128),
beside the plain form they replace on TPU (`ops/kda.py`'s, on q and k repeated
and the head's decay broadcast) and beside the per-channel kernels `kda_fwd` /
`kda_bwd` on those same repeated inputs, which is what the cell ran before.

    chiprun -- python3 scripts/gdn_kernel_check.py [--seeds 3]

Per seed: o and the five cotangents of a seeded probe, kernel against plain
form over the whole sequence; then over the first 2,048 positions (one
segment) both against `kda_recurrent` and `jax.grad` of it at `highest` (the
recurrence keeps one [32, 128, 128] state a token for its backward, 4.3 GB at
2,048).  Then the milliseconds a call of `gdn_fwd`, `gdn_fwd` with pair states,
`gdn_bwd`, and of the op forward and forward + backward through
`gdn_chunked`, each beside its per-channel counterpart (repeat and broadcast
included, as the layer paid them).

Inputs have the statistics of the cell's own weights at initialisation
(`models/mixers/gdn.py`): q, k L2-normalised per head (q times 128^-0.5), v the
SiLU of a normal in bf16, g = -A softplus(x + dt_bias) with A uniform in
[1, 16] and softplus(dt_bias) log-uniform in [1e-3, 1e-1], one of each a value
head, beta a sigmoid.  One JSON line per seed and per timing.  Exit 1 if a
kernel's error against the recurrence exceeds the plain form's by more than a
tenth, or the two differ by more than three bf16 passes' own rounding (3e-4;
4e-3 for dv, which leaves in bf16)."""

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

from ray_tpu.ops import gdn, kda
from ray_tpu.ops.pallas import gdn as kernels
from ray_tpu.ops.pallas import kda as kda_kernels

B, S, HK, HV, D = 1, 8192, 16, 32, 128
PREFIX = 2048
NAMES = ("dq", "dk", "dv", "dg", "dbeta")


def inputs(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = unit(jax.nn.silu(jax.random.normal(ks[0], (B, S, HK, D)))) * D ** -0.5
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (B, S, HK, D))))
    v = jax.nn.silu(jax.random.normal(ks[2], (B, S, HV, D))).astype(jnp.bfloat16)
    a = jax.random.uniform(ks[3], (HV,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (HV,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    bias = dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus
    g = -a * jax.nn.softplus(0.5 * jax.random.normal(ks[5], (B, S, HV)) + bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (B, S, HV)))
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


def per_channel(q, k, g):
    """q, k, g as the layer handed them to `kda_chunked` before: repeated, broadcast."""
    q, k = jnp.repeat(q, HV // HK, axis=2), jnp.repeat(k, HV // HK, axis=2)
    return q, k, jnp.broadcast_to(g[..., None], k.shape)


def both(q, k, v, g, beta, probe):
    """((o, the five cotangents) of the kernels, the same of the plain form)."""
    per = kda.per_segment(q.shape[1], kda.CHUNK)
    o, _, pairs = kernels.gdn_fwd(q, k, v, g, beta, per_segment=per, pair_states=True)
    kernel = (o, *kernels.gdn_bwd(q, k, v, g, beta, pairs, probe, per_segment=per))
    o, entering = gdn._plain_forward(q, k, v, g, beta, kda.CHUNK)
    return kernel, (o, *gdn._plain_backward(q, k, v, g, beta, entering, probe, kda.CHUNK))


def recurrent(q, k, v, g, beta, probe):
    def o(q, k, v, g, beta):
        q, k, g = per_channel(q, k, g)
        return kda.kda_recurrent(q, k, v, g, beta)

    out, pull = jax.vjp(o, q, k, v, g, beta)
    return (out, *pull(probe))


def kda_forward(q, k, v, g, beta):
    """The layer's call before: repeat and broadcast, then the per-channel op."""
    q, k, g = per_channel(q, k, g)
    return kda.kda_chunked(q, k, v, g, beta)


def kda_forward_backward(q, k, v, g, beta, probe):
    return jax.grad(lambda *a: jnp.sum(kda_forward(*a) * probe), argnums=range(5))(q, k, v, g, beta)


def gdn_forward_backward(q, k, v, g, beta, probe):
    return jax.grad(lambda *a: jnp.sum(gdn.gdn_chunked(*a) * probe), argnums=range(5))(q, k, v, g, beta)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's products", file=sys.stderr)
        return 1
    compare, reference = jax.jit(both), jax.jit(recurrent)
    ok = True
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        q, k, v, g, beta = inputs(seed)
        probe = jax.random.normal(jax.random.PRNGKey(seed + 1), v.shape, jnp.float32)
        kernel, plain = compare(q, k, v, g, beta, probe)
        line = {"seed": seed, "positions": S, "finite": all(bool(jnp.all(jnp.isfinite(x))) for x in kernel),
                "g_min_chunk_sum": float(jnp.min(jnp.sum(g.reshape(B, S // 64, 64, HV), axis=2)))}
        line.update({f"{name}_kernel_vs_plain": rel(a, b) for name, a, b in zip(("o", *NAMES), kernel, plain)})
        # a cotangent in bf16 (dv) is rounded once more, each side its own way: one ulp is 3.9e-3
        ok &= line["finite"] and all(line[f"{name}_kernel_vs_plain"] <= (3e-4 if a.dtype == jnp.float32 else 4e-3)
                                     for name, a in zip(("o", *NAMES), kernel))
        print(json.dumps(line), flush=True)
        # one segment alone: positions past it have no part in its cotangents when the probe ends with it
        head = tuple(x[:, :PREFIX] for x in (q, k, v, g, beta, probe))
        kernel, plain = compare(*head)
        want = reference(*head[:2], head[2].astype(jnp.float32), *head[3:])
        line = {"seed": seed, "positions": PREFIX}
        for name, a, b, w in zip(("o", *NAMES), kernel, plain, want):
            line[f"{name}_kernel_vs_recurrent"], line[f"{name}_plain_vs_recurrent"] = rel(a, w), rel(b, w)
            ok &= line[f"{name}_kernel_vs_recurrent"] <= 1.1 * line[f"{name}_plain_vs_recurrent"] + (1e-6 if a.dtype == jnp.float32 else 4e-3)
        print(json.dumps(line), flush=True)

    per = kda.per_segment(S, kda.CHUNK)
    pairs = jax.jit(functools.partial(kernels.gdn_fwd, per_segment=per, pair_states=True))(q, k, v, g, beta)[2]
    qr, kr, gr = jax.jit(per_channel)(q, k, g)
    blocks = jax.jit(lambda *xs: tuple(kda.segments(x, kda.CHUNK, per) for x in xs))(qr, kr, v, gr, probe)
    kda_pairs = jax.jit(functools.partial(kda_kernels.kda_fwd, pair_states=True))(*blocks[:4], beta)[2]
    calls = (
        ("gdn_fwd", functools.partial(kernels.gdn_fwd, per_segment=per), (q, k, v, g, beta)),
        ("gdn_fwd_with_pair_states", functools.partial(kernels.gdn_fwd, per_segment=per, pair_states=True), (q, k, v, g, beta)),
        ("gdn_bwd", functools.partial(kernels.gdn_bwd, per_segment=per), (q, k, v, g, beta, pairs, probe)),
        ("kda_fwd", kda_kernels.kda_fwd, (*blocks[:4], beta)),
        ("kda_fwd_with_pair_states", functools.partial(kda_kernels.kda_fwd, pair_states=True), (*blocks[:4], beta)),
        ("kda_bwd", kda_kernels.kda_bwd, (*blocks[:4], beta, kda_pairs, blocks[4])),
        ("gdn_chunked_forward", gdn.gdn_chunked, (q, k, v, g, beta)),
        ("kda_chunked_forward_repeated", kda_forward, (q, k, v, g, beta)),
        ("gdn_chunked_forward_and_backward", gdn_forward_backward, (q, k, v, g, beta, probe)),
        ("kda_chunked_forward_and_backward_repeated", kda_forward_backward, (q, k, v, g, beta, probe)),
    )
    heads_and_pairs = B * HV * S // (2 * kda.CHUNK)
    for name, f, operands in calls:
        ms = timed(jax.jit(f), *operands)
        print(json.dumps({"one_layer": name, "ms": ms, "us_per_pair_and_head": ms * 1e3 / heads_and_pairs}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
