#!/usr/bin/env python3
"""On the chip: the selective scan's two kernels
(`ray_tpu/ops/pallas/selective_scan.py`) beside the plain chunked form
(`ray_tpu/ops/selective_scan.py`) at the Phi-4-mini-flash cell's shapes (1 x
8,192 positions, 5,120 channels, N 16, dt_rank 160), each against
`selective_scan_recurrent` (x's values typed float32 there, so that y leaves
unrounded; the bf16 outputs beside it) and, on the first 2,048 positions, the
six cotangents of both backwards against JAX's gradient of that recurrence
(x, B and C typed float32 there, so that no cotangent is rounded), and the
time of one layer's forward, backward and both, both ways.

    chiprun -- python3 scripts/s6_kernel_check.py [--seeds 3] [--blocks 512x256 1024x128 ...]

Inputs have the statistics of the cell's own weights at initialisation
(`models/mixers/s6.py`): x the SiLU of a normal in bf16, `[dt_low | B | C] =
x W_x` in bf16 with `W_x` normal at inner^-0.5, `dt = softplus(dt_low W_dt +
b_dt)` in float32 with `W_dt` normal at rank^-0.5 and `softplus(b_dt)`
log-uniform in [1e-3, 1e-1], A = -(1..N) in every channel, D = 1.

Timings, one line each: the scan ALONE on arrays in their default layouts
(`plain`, `kernel`), and the scan with the projection that makes dt inside the
same jit (`plain_from_dt_low`, `kernel_from_dt_low`), where XLA chooses dt's
layout into the scan as it does in the step (PERF.md section 6, PR 42: what the
step's optimized HLO shows of it).  `--blocks` times the kernel at other
channels x positions a program, both directions.  Exit 1 if the kernel's error
against the recurrence exceeds the plain form's by more than a tenth, in y or
in any cotangent (beyond one rounding of float32, 1e-7, for a cotangent: both
forms' sums over 5,120 channels or 2,048 positions stand at 1-4e-7 from the
recurrence's, in another order each)."""

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

from ray_tpu.ops import selective_scan as op
from ray_tpu.ops.pallas import selective_scan as kernels

B, S, INNER, N, RANK = 1, 8192, 5120, 16, 160
GRADIENT_PREFIX = 2048  # positions the recurrence's own gradient is taken on: it keeps every state
COTANGENTS = ("x", "dt", "A", "B", "C", "D")


def weights(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    w_x = (jax.random.normal(ks[0], (INNER, RANK + 2 * N)) * INNER ** -0.5).astype(jnp.bfloat16)
    w_dt = (jax.random.normal(ks[1], (RANK, INNER)) * RANK ** -0.5).astype(jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(ks[2], (INNER,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    bias = dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (INNER, N))
    return w_x, w_dt, bias, A, jnp.ones((INNER,), jnp.float32)


def step_of(low, w_dt, bias):
    """dt as `mixers/s6.py` `mix` makes it."""
    step = jnp.einsum("bsr,rf->bsf", low[..., :RANK], w_dt, preferred_element_type=jnp.float32)
    return jax.nn.softplus(step + bias)


@jax.jit
def inputs(seed):
    w_x, w_dt, bias, A, D = weights(seed)
    x = jax.nn.silu(jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, INNER))).astype(jnp.bfloat16)
    low = jnp.einsum("bsf,fr->bsr", x, w_x)
    return (x, step_of(low, w_dt, bias), A, low[..., RANK: RANK + N], low[..., RANK + N:], D), (low, w_dt, bias)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def timed(f, *args, n: int = 10) -> float:
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def forms(**blocks):
    """(plain, kernel): each (x, dt, A, B, C, D) -> (y, the state that enters each chunk).
    A new function object per setting: `jax.jit` caches on the function."""
    def plain(x, dt, A, Bm, Cm, D):
        return op._plain_forward(x, dt, A.T, Bm, Cm, D, op.CHUNK)

    def kernel(x, dt, A, Bm, Cm, D):
        return kernels.s6_scan_fwd(x, dt, A.T, Bm, Cm, D, chunk=op.CHUNK, **blocks)

    return plain, kernel


def backward_forms(**blocks):
    """(plain, kernel): each (x, dt, A, B, C, D, entering, dy) -> the six cotangents, A's as [N, channels]."""
    def plain(x, dt, A, Bm, Cm, D, entering, dy):
        return op._plain_backward(x, dt, A.T, Bm, Cm, D, entering, dy, op.CHUNK)

    def kernel(x, dt, A, Bm, Cm, D, entering, dy):
        return kernels.s6_scan_bwd(x, dt, A.T, Bm, Cm, D, entering, dy, chunk=op.CHUNK, **blocks)

    return plain, kernel


def layer(forward, backward):
    """One layer's scan as a step runs it: forward, then backward from the states the forward wrote."""
    def run(x, dt, A, Bm, Cm, D, dy):
        y, entering = forward(x, dt, A, Bm, Cm, D)
        return y, backward(x, dt, A, Bm, Cm, D, entering, dy)

    return run


@jax.jit
def recurrence_cotangents(x, dt, A, Bm, Cm, D, dy):
    """JAX's gradient of the token-by-token recurrence: what both backwards are held to."""
    return jax.vjp(lambda *a: op.selective_scan_recurrent(*a)[0], x, dt, A, Bm, Cm, D)[1](dy)


def cotangent_distances(scan_args, dy, forward, backwards) -> dict:
    """Each backward's six cotangents on the first `GRADIENT_PREFIX` positions against `jax.vjp` of the recurrence, every
    input typed float32 (the same values)."""
    x, dt, A, Bm, Cm, D = scan_args
    f32 = jnp.float32
    exact = (x[:, :GRADIENT_PREFIX].astype(f32), dt[:, :GRADIENT_PREFIX], A, Bm[:, :GRADIENT_PREFIX].astype(f32),
             Cm[:, :GRADIENT_PREFIX].astype(f32), D)
    dy = dy[:, :GRADIENT_PREFIX].astype(f32)
    want = recurrence_cotangents(*exact, dy)
    entering = forward(*exact)[1]
    out = {}
    for name, backward in backwards.items():
        got = backward(*exact, entering, dy)
        for which, g, w in zip(COTANGENTS, got, want):
            out[f"d{which}_{name}_vs_recurrent"] = rel(g.T if which == "A" else g, w)
            out["finite"] = out.get("finite", True) and bool(jnp.all(jnp.isfinite(g)))
    return out


def from_dt_low(form):
    def run(x, low, w_dt, bias, A, D):
        return form(x, step_of(low, w_dt, bias), A, low[..., RANK: RANK + N], low[..., RANK + N:], D)

    return run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--blocks", nargs="*", default=[], help="channels x positions a program to time as well, e.g. 1024x128")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's arithmetic or time", file=sys.stderr)
        return 1
    plain, kernel = (jax.jit(f) for f in forms())
    plain_bwd, kernel_bwd = (jax.jit(f) for f in backward_forms())
    recurrent = jax.jit(lambda *a: op.selective_scan_recurrent(*a)[0])
    ok = True
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        scan_args, (low, w_dt, bias) = inputs(seed)
        want = recurrent(*scan_args)
        # the same values with x typed float32, so that y leaves unrounded: bf16's own rounding (1.6e-3) would hide the rest
        exact = (scan_args[0].astype(jnp.float32), *scan_args[1:])
        y_kernel, s_kernel = kernel(*exact)
        y_plain, s_plain = plain(*exact)
        y_kernel_bf16, y_plain_bf16 = kernel(*scan_args)[0], plain(*scan_args)[0]
        exponent = scan_args[1][..., None] * scan_args[2]
        line = {
            "seed": seed,
            "kernel_vs_recurrent": rel(y_kernel, want),
            "plain_vs_recurrent": rel(y_plain, want),
            "kernel_vs_plain": rel(y_kernel, y_plain),
            "states_kernel_vs_plain": rel(s_kernel[1:], s_plain[1:]),
            "bf16_y_kernel_vs_recurrent": rel(y_kernel_bf16, want),
            "bf16_y_plain_vs_recurrent": rel(y_plain_bf16, want),
            "bf16_y_share_that_differs": float(jnp.mean(y_kernel_bf16 != y_plain_bf16)),
            "finite": bool(jnp.all(jnp.isfinite(y_kernel))),
            "dt_mean": float(jnp.mean(scan_args[1])),
            "exponent_min_chunk_sum": float(jnp.min(jnp.sum(exponent.reshape(B, S // op.CHUNK, op.CHUNK, INNER, N), axis=2))),
        }
        ok &= line["finite"] and line["kernel_vs_recurrent"] <= 1.1 * line["plain_vs_recurrent"] + 1e-8
        print(json.dumps(line), flush=True)
        dy = jax.random.normal(jax.random.PRNGKey(seed + 2), (B, S, INNER)).astype(jnp.bfloat16)
        line = {"seed": seed, **cotangent_distances(scan_args, dy, kernel, {"kernel": kernel_bwd, "plain": plain_bwd})}
        ok &= line["finite"] and all(
            line[f"d{c}_kernel_vs_recurrent"] <= 1.1 * line[f"d{c}_plain_vs_recurrent"] + 1e-7 for c in COTANGENTS)
        got, want = kernel_bwd(*scan_args, s_kernel, dy), plain_bwd(*scan_args, s_kernel, dy)  # bf16 as the step has them
        line.update({f"bf16_d{c}_kernel_vs_plain": rel(g, w) for c, g, w in zip(COTANGENTS, got, want)})
        print(json.dumps(line), flush=True)
    x, dt, A, _, _, D = scan_args
    for name, f in (("plain", plain), ("kernel", kernel)):
        print(json.dumps({"forward": name, "ms": timed(f, *scan_args)}), flush=True)
        ms = timed(jax.jit(from_dt_low(f)), x, low, w_dt, bias, A, D)
        print(json.dumps({"forward": name + "_from_dt_low", "ms": ms}), flush=True)
    for name, f, b in (("plain", plain, plain_bwd), ("kernel", kernel, kernel_bwd)):
        print(json.dumps({"backward": name, "ms": timed(b, *scan_args, s_kernel, dy)}), flush=True)
        print(json.dumps({"forward_and_backward": name, "ms": timed(jax.jit(layer(f, b)), *scan_args, dy)}), flush=True)
    for setting in args.blocks:
        block_c, block_s = map(int, setting.split("x"))
        blocks = dict(block_c=block_c, block_s=block_s)
        for direction, form, run_args, known in (("forward", forms(**blocks)[1], scan_args, (y_kernel_bf16,)),
                                                 ("backward", backward_forms(**blocks)[1], (*scan_args, s_kernel, dy), got)):
            try:
                f = jax.jit(form)
                same = bool(jnp.all(jax.tree.leaves(f(*run_args))[0] == known[0]))  # y, or dx: what a block's size cannot move
                print(json.dumps({direction: "kernel", "blocks": setting, "ms": timed(f, *run_args), "same": same}), flush=True)
            except Exception as e:  # noqa: BLE001: a setting Mosaic refuses is a line of the sweep
                print(json.dumps({direction: "kernel", "blocks": setting, "error": f"{type(e).__name__}: {str(e)[:300]}"}),
                      flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
