#!/usr/bin/env python3
"""The q|k mixing of ONE "cca" layer (`ray_tpu/models/mixers/cca.py` `qk_mixing`: the depthwise and the grouped causal
convolution over the latent, the q-k mean, the unit norm, tau, the rope on 64 of 128 dims) at ZAYA1-8B's published
widths (1 x 16,384 positions, 8 + 2 heads of 128) against the plain reference's (`benchmarks/lib/reference_zaya.py`
`qk_mixing`: explicit shifts, float32, precision "highest"), forward and gradients, on the chip or the CPU:

    chiprun -- python3 scripts/cca_mix_check.py [--seeds 3]
    python3 scripts/cca_mix_check.py --tokens 2048        # here, on the CPU

Per seed, one JSON line: q and k of the program's bf16 mixing against the reference's, the cotangents of the latent
and of the five leaves (`conv1_w`, `conv1_b`, `conv2_w`, `conv2_b`, `tau`) under a seeded probe, each as rms(got -
want) / rms(want); then the milliseconds of a forward and of a forward + backward, and the forward + backward's share
of the bytes the mixing NEEDS over the chip's HBM bandwidth (`builders/cca_moe_decoder.mix_bytes_per_layer`: what
`cca_mix_roofline` reads for the whole step).  The mixing is plain JAX today (no kernel: PERF.md section 7 says what
one would be worth); a later kernel PR holds its `KernelPair` to this script, in the form of
`scripts/delta_conv_check.py`.  Inputs have the statistics of the cell's own at initialisation: the latent a unit
normal in bf16 (a projection of a normed stream), the leaves as `init_params` draws them, constants redrawn.

Exit 1 if q or k differ from the reference's by more than 1% (one bf16 rounding of a value of the norm sqrt(128) and
the two roundings before it) or a cotangent by more than 3%."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.builders import cca_moe_decoder as builder
from benchmarks.lib import flops, reference_zaya as ref
from ray_tpu.models.mixers import cca
from ray_tpu.ops.rotary import Rope

f32, bf16 = jnp.float32, jnp.bfloat16
HEADS, KV_HEADS, D, TAPS, THETA, ROTARY = 8, 2, 128, 2, 5e6, 64
LIMITS = {"q": 0.01, "k": 0.01, "d_latent": 0.03, "d_conv1_w": 0.03, "d_conv1_b": 0.03, "d_conv2_w": 0.03, "d_conv2_b": 0.03, "d_tau": 0.03}


def inputs(seed: int, tokens: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    heads = HEADS + KV_HEADS
    latent = jax.random.normal(ks[0], (1, tokens, heads, D)).astype(bf16)
    leaves = {"conv1_w": jax.random.normal(ks[1], (TAPS, heads, D)) * TAPS ** -0.5, "conv1_b": 0.1 * jax.random.normal(ks[2], (heads, D)),
              "conv2_w": jax.random.normal(ks[3], (TAPS, heads, D, D)) * (TAPS * D) ** -0.5, "conv2_b": 0.1 * jax.random.normal(ks[4], (heads, D)),
              "tau": 1.0 + 0.2 * jax.random.normal(ks[5], (KV_HEADS,))}
    leaves = {k: v.astype(bf16).astype(f32) for k, v in leaves.items()}  # the values a bf16 parameter holds
    probe = (jax.random.normal(ks[6], (1, tokens, HEADS, D)), jax.random.normal(ks[7], (1, tokens, KV_HEADS, D)))
    return latent, leaves, probe


def program(latent, leaves, tokens):
    return cca.qk_mixing(latent, leaves, jnp.arange(tokens), Rope(THETA, rotary_dim=ROTARY), HEADS)


def reference(latent, leaves):
    q, k = ref.qk_mixing(latent[0].astype(f32), leaves, n_heads=HEADS, theta=THETA, rotary=ROTARY)
    return q[None], k[None]


def probed(f, probe):
    return lambda *a: sum(jnp.sum(out.astype(f32) * p) for out, p in zip(f(*a), probe))


def rel(got, want) -> float:
    got, want = got.astype(f32), want.astype(f32)
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want)) / jnp.mean(jnp.square(want))))


def timed(f, *args, reps: int = 5) -> float:
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=16384)
    args = ap.parse_args()
    device = jax.devices()[0]
    bad = False
    for seed in range(args.seeds):
        latent, leaves, probe = inputs(seed, args.tokens)
        forward = jax.jit(lambda x, w: program(x, w, args.tokens))
        both = jax.jit(jax.grad(probed(lambda x, w: program(x, w, args.tokens), probe), argnums=(0, 1)))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(latent, leaves)
            d_want = jax.jit(jax.grad(probed(reference, probe), argnums=(0, 1)))(latent.astype(f32), leaves)
        got, d_got = forward(latent, leaves), both(latent, leaves)
        errors = {"q": rel(got[0], want[0]), "k": rel(got[1], want[1]), "d_latent": rel(d_got[0], d_want[0]),
                  **{"d_" + name: rel(d_got[1][name], d_want[1][name]) for name in leaves}}
        over = {name: e for name, e in errors.items() if not e <= LIMITS[name]}
        bad = bad or bool(over)
        line = {"seed": seed, "device": device.device_kind, "tokens": args.tokens, "errors": errors, "over_the_limits": over,
                "forward_ms": timed(forward, latent, leaves), "forward_backward_ms": timed(both, latent, leaves)}
        if device.platform == "tpu":
            config = {"num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS, "head_dim": D}
            needed_s = builder.mix_bytes_per_layer(config) * args.tokens / flops.load_peaks(device.device_kind)["hbm_bytes_per_s"]
            line["forward_backward_share_of_needed_bytes_pct"] = 100.0 * needed_s / (line["forward_backward_ms"] / 1e3)
        print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
