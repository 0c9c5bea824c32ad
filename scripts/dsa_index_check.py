#!/usr/bin/env python3
"""On the chip: the indexer's score kernels (`ray_tpu/ops/pallas/sparse_attention.py`
`index_fwd` / `index_bwd`) at the dots3-note cell's shapes (1 x 8,192, 64 heads
of 128, one key a position), beside the plain form they replace in a step
lowered for TPU (`ops/sparse_attention.py` `_plain_scores`: 32 blocks of 256
queries, each a `[256, 64, 8192]` float32 block of products through HBM).

    chiprun -- python3 scripts/dsa_index_check.py [--seeds 3] [--tiles 128x512 256x256 ...]

Per seed, one JSON line: the scores of the two forms (relative RMS and largest
difference; the largest magnitude above the diagonal, 0.0 in both), the keys
of 2,048 a query whose side of the selection differs between the forms and
how many of those lie further than float32 rounding (1e-5 of the threshold)
from the query's 2,048th score, and the three gradients `dq`, `dk`, `dw` for a
cotangent that is zero off the selection.  Then the milliseconds of the forward
and of forward + backward of each form, and the kernels' share of the bf16 peak
at the needed operations (6 x causal pairs x 64 x 128, of which the forward is
a third); with `--tiles`, the kernels' milliseconds again at each (query tile x
key tile) given, `index_tiles` replaced.

q and k are unit normals rounded to bf16 (the key is a LayerNorm's output), w a
normal times (64 x 128)^-0.5 in float32, as `models/mixers/dsa.py` hands them.
Exit 1 if the scores differ by more than 1e-5, `dw` (float32) by more than
1e-4, `dq` or `dk` (bf16: each form rounds what it hands the MXU to bf16, the
plain form `dI w [z > 0]` and the kernels `dI [z > 0]` for `dq`, whose weight
they apply to the float32 sum, and each rounds its sum once more) by more
than 8e-3, a key flips further than rounding from its threshold, or the flips
exceed 1e-5 of the selected pairs."""

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

from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.pallas import sparse_attention as kernels

B, S, J, D, TOPK = 1, 8192, 64, 128, 2048
PEAK = 197e12  # bf16 FLOP/s of a v5e chip (Google Cloud documentation, "TPU v5e")


def inputs(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, J, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, D)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[2], (B, S, J)) * (J * D) ** -0.5
    return k, q, w, ks[3]


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


def selections(kernel, plain):
    """(the plain form's mask, the keys that change sides, those of them further than rounding from the threshold)."""
    mask, other = sa.select_topk(plain, TOPK), sa.select_topk(kernel, TOPK)
    threshold = jnp.min(jnp.where(mask != 0, plain, jnp.inf), axis=-1, keepdims=True)
    flipped = mask != other
    far = flipped & (jnp.abs(plain - threshold) > 1e-5 * jnp.abs(threshold))
    return mask, jnp.sum(flipped), jnp.sum(far)


def kernel_forward_backward(k, q, w, d):
    return kernels.index_fwd(k, q, w), kernels.index_bwd(k, q, w, d)


def plain_forward_backward(k, q, w, d):
    out, pull = jax.vjp(sa._plain_scores, k, q, w)
    return out, pull(d)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--tiles", nargs="*", default=[], help="QxK tilings to time beside the one in use")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's products", file=sys.stderr)
        return 1
    fwd_kernel, fwd_plain = jax.jit(kernels.index_fwd), jax.jit(sa._plain_scores)
    bwd_kernel, bwd_plain = jax.jit(kernels.index_bwd), jax.jit(sa._plain_scores_backward)
    select = jax.jit(selections)
    ok, above = True, np.triu(np.ones((S, S), bool), 1)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        k, q, w, key = inputs(seed)
        kernel, plain = fwd_kernel(k, q, w), fwd_plain(k, q, w)
        mask, flipped, far = select(kernel, plain)
        d = jax.random.normal(key, (B, S, S)) * (mask != 0) / S  # zero off the selection, as `index_kl`'s gradient is
        line = {"seed": seed, "positions": S, "scores_kernel_vs_plain": rel(kernel, plain),
                "scores_max_abs_diff": float(jnp.max(jnp.abs(kernel - plain))),
                "above_diagonal_max": [float(np.abs(np.asarray(x[0])[above]).max()) for x in (kernel, plain)],
                "selected_pairs": int(jnp.sum(mask != 0)), "keys_changing_sides": int(flipped),
                "keys_changing_sides_beyond_rounding": int(far)}
        got, want = bwd_kernel(k, q, w, d), bwd_plain(k, q, w, d)
        line.update({f"{name}_kernel_vs_plain": rel(a, b) for name, a, b in zip(("dk", "dq", "dw"), got, want)})
        line["finite"] = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in (kernel, *got))
        ok &= (line["finite"] and line["scores_kernel_vs_plain"] <= 1e-5 and max(line["above_diagonal_max"]) == 0.0
               and line["keys_changing_sides_beyond_rounding"] == 0
               and line["keys_changing_sides"] <= 1e-5 * line["selected_pairs"]
               and line["dw_kernel_vs_plain"] <= 1e-4 and max(line["dk_kernel_vs_plain"], line["dq_kernel_vs_plain"]) <= 8e-3)
        print(json.dumps(line), flush=True)

    needed = 6 * (S * (S + 1) // 2) * J * D  # forward 2, backward 4 a pair, head and dim
    for name, f, operands, share in (
        ("index_fwd", fwd_kernel, (k, q, w), 1 / 3),
        ("plain_forward", fwd_plain, (k, q, w), 1 / 3),
        ("index_bwd", bwd_kernel, (k, q, w, d), 2 / 3),
        ("plain_backward", bwd_plain, (k, q, w, d), 2 / 3),
        ("index_forward_and_backward", jax.jit(kernel_forward_backward), (k, q, w, d), 1.0),
        ("plain_forward_and_backward", jax.jit(plain_forward_backward), (k, q, w, d), 1.0),
    ):
        ms = timed(f, *operands)
        print(json.dumps({"one_layer": name, "ms": ms, "share_of_bf16_peak_pct": 100 * share * needed / PEAK / (ms * 1e-3)}), flush=True)
    for tiling in args.tiles:
        tq, ts = map(int, tiling.split("x"))
        kernels.index_tiles = lambda s, tiles=(tq, ts): tiles
        for name, f, operands, share in (("index_fwd", kernels.index_fwd, (k, q, w), 1 / 3),
                                         ("index_bwd", kernels.index_bwd, (k, q, w, d), 2 / 3)):
            ms = timed(jax.jit(f), *operands)
            print(json.dumps({"one_layer": name, "tiles": tiling, "ms": ms,
                              "share_of_bf16_peak_pct": 100 * share * needed / PEAK / (ms * 1e-3)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
