#!/usr/bin/env python3
"""On the chip: the grouped-matmul kernels (`ray_tpu/ops/pallas/grouped_matmul.py`)
at the shapes of `nemotron3-nano-ep8-1chip.seq8k`'s expert layer, d 2688 and
expert width 1856 (no multiple of 128), 49,152 sorted rows of which the 16 held
groups own a part:

    chiprun -- python3 scripts/grouped_matmul_check.py [--reps 10] [--routings uniform collapsed] [--products gmm_up ...]

Three routings: `uniform` (16 groups of 384 rows, a uniform router's
expectation at 6 of 128), `collapsed` (one group of 8,192: every token of the
layer chose that expert) and `deployed` (16 groups of 3,072: what 8
expert-parallel chips would send).  For each of the six products of a layer's
forward and backward (`moe_gmm` up and down, both with `transpose_rhs`,
`moe_tgmm` up and down) every candidate tiling is checked against a dense loop
over the groups in float32 at `highest` and timed; then the whole
`grouped_matmul` forward + backward at the rule's tiles against
`jax.lax.ragged_dot`.  One JSON line each, on
stdout and in `chiprun_out/grouped_matmul_check.jsonl`.  Exit
1 if a kernel that compiled differs from the dense loop by more than bf16's
rounding of a float32-accumulated product (rel. rms 5e-3).

A candidate the compiler refuses is reported with its message, not fatal:
that is what the table in PERF.md is for."""

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

from ray_tpu.ops.pallas import grouped_matmul as kernels

M, D, F, G = 49152, 2688, 1856, 16
ROUTINGS = {"uniform": [384] * G, "collapsed": [8192] + [0] * (G - 1), "deployed": [3072] * G}
# (tm, tk, tn) per product, beside the rule's own (None).  k or n = 1856 only goes whole.
CANDIDATES = {
    "gmm_up": [None, (256, 896, 1856), (512, 384, 1856), (512, 128, 1856), (512, 2688, 1856), (1024, 896, 1856)],
    "gmm_down": [None, (256, 1856, 896), (512, 1856, 384), (512, 1856, 128), (512, 1856, 2688), (1024, 1856, 896)],
    "gmm_up_T": [None, (256, 1856, 896), (512, 1856, 384), (512, 1856, 2688)],
    "gmm_down_T": [None, (256, 896, 1856), (512, 384, 1856), (512, 2688, 1856)],
    "tgmm_up": [None, (256, 896, 1856), (512, 384, 1856), (512, 128, 1856), (1024, 896, 1856)],
    "tgmm_down": [None, (256, 1856, 896), (512, 1856, 384), (512, 1856, 128), (1024, 1856, 896)],
}
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out", "grouped_matmul_check.jsonl")


def say(line) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(text + "\n")


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / max(np.mean(b ** 2), 1e-30)))


def timed(f, *args, n: int) -> float:
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def dense_gmm(lhs, rhs, sizes, transpose_rhs=False):
    """Group by group in float32 at `highest`; rows behind the last group zero."""
    out, start = [], 0
    for g, size in enumerate(sizes):
        w = rhs[g].T if transpose_rhs else rhs[g]
        out.append(jnp.dot(lhs[start:start + size].astype(jnp.float32), w.astype(jnp.float32), precision="highest"))
        start += size
    out.append(jnp.zeros((lhs.shape[0] - start, out[0].shape[1]), jnp.float32))
    return jnp.concatenate(out)


def dense_tgmm(lhs, rhs, sizes):
    out, start = [], 0
    for size in sizes:
        out.append(jnp.dot(lhs[start:start + size].astype(jnp.float32).T, rhs[start:start + size].astype(jnp.float32),
                           precision="highest"))
        start += size
    return jnp.stack(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--routings", nargs="*", default=list(ROUTINGS))
    ap.add_argument("--products", nargs="*", default=list(CANDIDATES))
    ap.add_argument("--few", action="store_true", help="the rule's tiles and the first other candidate only")
    args = ap.parse_args()
    say({"device": jax.devices()[0].device_kind, "m": M, "d": D, "f": F, "groups": G})
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    bf = jnp.bfloat16
    x_d = jax.random.normal(ks[0], (M, D), bf)  # rows as they enter up, cotangent as it leaves down
    x_f = jax.random.normal(ks[1], (M, F), bf)  # rows as they enter down, cotangent as it leaves up
    w_up = (jax.random.normal(ks[2], (G, D, F), jnp.float32) * D ** -0.5).astype(bf)
    w_down = (jax.random.normal(ks[3], (G, F, D), jnp.float32) * F ** -0.5).astype(bf)
    # (kernel(lhs, rhs, sizes, tiles), the dense loop, its two operands): operands go in as ARGUMENTS, a
    # closed-over array is a constant of the program (720 MB an executable, a minute a compile)
    products = {
        "gmm_up": (lambda l, r, s, t: kernels.moe_gmm(l, r, s, tiles=t), dense_gmm, (x_d, w_up)),
        "gmm_down": (lambda l, r, s, t: kernels.moe_gmm(l, r, s, tiles=t), dense_gmm, (x_f, w_down)),
        "gmm_up_T": (lambda l, r, s, t: kernels.moe_gmm(l, r, s, transpose_rhs=True, tiles=t),
                     functools.partial(dense_gmm, transpose_rhs=True), (x_f, w_up)),
        "gmm_down_T": (lambda l, r, s, t: kernels.moe_gmm(l, r, s, transpose_rhs=True, tiles=t),
                       functools.partial(dense_gmm, transpose_rhs=True), (x_d, w_down)),
        "tgmm_up": (lambda l, r, s, t: kernels.moe_tgmm(l, r, s, tiles=t), dense_tgmm, (x_d, x_f)),
        "tgmm_down": (lambda l, r, s, t: kernels.moe_tgmm(l, r, s, tiles=t), dense_tgmm, (x_f, x_d)),
    }
    ok = True
    for routing in args.routings:
        sizes = ROUTINGS[routing]
        gs = jnp.asarray(sizes, jnp.int32)
        held = sum(sizes)
        for name in args.products:
            kernel, dense, operands = products[name]
            want = jax.jit(functools.partial(dense, sizes=sizes))(*operands)
            for tiles in CANDIDATES[name][:2 if args.few else None]:
                line = {"routing": routing, "product": name, "tiles": tiles, "rows": held,
                        "flops": 2 * held * D * F}
                try:
                    f = jax.jit(functools.partial(kernel, t=tiles))
                    got = f(*operands, gs)
                    if name.startswith("gmm"):  # rows behind the last group are not defined
                        got = jnp.where((jnp.arange(M) < held)[:, None], got, 0)
                    line["rel_rms"] = rel(got, want)
                    line["ms"] = timed(f, *operands, gs, n=args.reps)
                    line["tflops"] = line["flops"] / line["ms"] / 1e9
                    if not line["rel_rms"] < 5e-3:
                        ok = False
                        line["WRONG"] = True
                except Exception as e:  # noqa: BLE001: the compiler's refusal is the finding
                    line["refused"] = f"{type(e).__name__}: {e}"[-400:]
                say(line)

        # one layer's two grouped matmuls, forward + backward, three ways
        def layer(mm, x, w_up, w_down, gs):
            return jnp.sum(mm(jnp.square(jax.nn.relu(mm(x, w_up, gs))), w_down, gs).astype(jnp.float32))

        # (the XLA form is no candidate here: it gathers one [2688, 1856] matrix a visit, 7.8 GB at 783 visits)
        ways = {"kernels": kernels.grouped_matmul, "ragged_dot": lambda l, r, s: jax.lax.ragged_dot(l, r, s)}
        for way, mm in ways.items():
            line = {"routing": routing, "layer_fwd_bwd": way, "rows": held, "flops": 3 * 2 * 2 * held * D * F}
            try:
                f = jax.jit(jax.grad(functools.partial(layer, mm), argnums=(0, 1, 2)))
                line["ms"] = timed(f, x_d, w_up, w_down, gs, n=args.reps)
                line["tflops"] = line["flops"] / line["ms"] / 1e9
            except Exception as e:  # noqa: BLE001
                line["refused"] = f"{type(e).__name__}: {e}"[-400:]
            say(line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
