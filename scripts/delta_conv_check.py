#!/usr/bin/env python3
"""On the chip: the positions-major convolution kernels of a delta layer
(`ray_tpu/ops/pallas/delta_conv.py`) at both cells' shapes (Kimi Linear:
1 x 16,384 x 12,288, 32 + 32 + 32 heads of 128; Qwen3-Next: 1 x 8,192, the
first 8,192 of 12,288 columns, 16 + 16 key heads and 32 value heads), beside
the path the layers took until PR 60: `ssm.causal_conv1d_silu` (on TPU
`swapaxes` + `ssm_conv_fwd` / `ssm_conv_bwd` + `swapaxes`) + `jnp.split` + the
L2 norm, differentiated by JAX.

    chiprun -- python3 scripts/delta_conv_check.py [--seeds 3] [--cells kimi qwen3_next] [--blocks 512x512 256x1024 ...]

Per cell and seed: q, k, v and the cotangents dx, dw of a seeded probe, the
new op against the old path, and both against the plain form with the norm's
cotangent kept in float32 (the old path rounds it to bf16 on its way into the
convolution's backward: one rounding the kernel does not make).  Then the
milliseconds of a forward and of a forward + backward each way, and with
`--blocks` of the two kernels alone at other block sizes (positions x
channels).  One JSON line each.  Inputs have the statistics of the cells' own
at initialisation: x a unit normal in bf16 (a projection of a normed stream),
the taps normal at K^-0.5.

Exit 1 if the kernel's q, k or v differ from the old path's by more than a
128-term float32 sum's own reordering and an odd bf16 last place of y (1e-5
of q and k, 1e-4 of v), or its cotangents from the float32 reference by more
than the old path's do."""

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

from ray_tpu.ops import delta_conv as op
from ray_tpu.ops import ssm
from ray_tpu.ops.pallas import delta_conv as kernels

f32, bf16 = jnp.float32, jnp.bfloat16
D, K = 128, 4
# positions, columns of x, q heads, k heads, v channels
CELLS = {"kimi": (16384, 12288, 32, 32, 4096), "qwen3_next": (8192, 12288, 16, 16, 4096)}
OUT_LIMITS = {"q": 1e-5, "k": 1e-5, "v": 1e-4}


def inputs(seed: int, s, cx, hq, hk, cv):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (1, s, cx)).astype(bf16)
    wq, wk = (jax.random.normal(k, (h, D, K)) * K ** -0.5 for k, h in zip(ks[1:3], (hq, hk)))
    wv = jax.random.normal(ks[3], (cv, K)) * K ** -0.5
    probe = (jax.random.normal(ks[4], (1, s, hq, D)), jax.random.normal(ks[5], (1, s, hk, D)),
             jax.random.normal(ks[6], (1, s, cv)).astype(bf16))
    return (x, wq, wk, wv), probe


def old(x, wq, wk, wv):
    """The layers' path until PR 60, on the same arguments."""
    xc, w, b = op._convolution(x, wq, wk, wv)
    y = ssm.causal_conv1d_silu(xc, w, b)
    q, k, v = op._normed(y, wq, wk)
    return q.reshape(*q.shape[:2], *wq.shape[:2]), k.reshape(*k.shape[:2], *wk.shape[:2]), v


def reference(x, wq, wk, wv, probe):
    """(dx, dw) of the plain form under the probe, with the norm's cotangent left in float32."""
    conv = op._convolution(x, wq, wk, wv)
    y = ssm._conv_silu_plain(*conv)
    out, through_norm = jax.vjp(lambda y: op._normed(y, wq, wk), y.astype(f32))
    dy, = through_norm(tuple(p.reshape(o.shape).astype(f32) for p, o in zip(probe, out)))
    dx, dw, _ = ssm._conv_silu_bwd_plain(*conv, dy)
    return dx, dw


def cotangents(f, args, probe):
    """(the outputs, dx over the convolved columns, dw [C, K]) of `f` under the probe."""
    out, vjp = jax.vjp(f, *args)
    dx, dwq, dwk, dwv = vjp(probe)
    return out, dx[..., : op._weights(*args[1:]).shape[0]], op._weights(dwq, dwk, dwv)


def rel(a, b) -> float:
    a, b = a.astype(f32), b.astype(f32)
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


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
    ap.add_argument("--cells", nargs="+", default=list(CELLS), choices=list(CELLS))
    ap.add_argument("--blocks", nargs="*", default=[], help="positions x channels, e.g. 256x1024")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: off TPU both paths are the plain form, and a CPU run would compare it with itself",
              file=sys.stderr)
        return 1
    print(json.dumps({"device": jax.devices()[0].device_kind, "backend": jax.default_backend()}), flush=True)
    ok = True
    for cell in args.cells:
        new_c = jax.jit(functools.partial(cotangents, op.delta_conv))
        old_c = jax.jit(functools.partial(cotangents, old))
        ref_c = jax.jit(reference)
        for seed in range(args.seeds):
            a, probe = inputs(seed, *CELLS[cell])
            (out_n, dx_n, dw_n), (out_o, dx_o, dw_o), (dx_r, dw_r) = new_c(a, probe), old_c(a, probe), ref_c(*a, probe)
            line = {"cell": cell, "seed": seed}
            for name, n, o in zip("qkv", out_n, out_o):
                line[name] = rel(n, o)
                ok &= line[name] <= OUT_LIMITS[name]
            for name, n, o, r in (("dx", dx_n, dx_o, dx_r), ("dw", dw_n, dw_o, dw_r)):
                line[name] = {"kernel_vs_float32": rel(n, r), "old_vs_float32": rel(o, r), "kernel_vs_old": rel(n, o)}
                ok &= line[name]["kernel_vs_float32"] <= max(line[name]["old_vs_float32"], 1e-5)
            print(json.dumps(line), flush=True)
            del out_n, out_o, dx_n, dx_o, dx_r
        a, probe = inputs(0, *CELLS[cell])
        forward = {"new": jax.jit(op.delta_conv), "old": jax.jit(old)}
        both = {"new": new_c, "old": old_c}
        print(json.dumps({"cell": cell, "ms": {
            **{f"forward_{k}": timed(f, *a) for k, f in forward.items()},
            **{f"forward_backward_{k}": timed(f, a, probe) for k, f in both.items()}}}), flush=True)
        flat = tuple(p.reshape(*p.shape[:2], -1) for p in probe)
        for blocks in ["default", *args.blocks]:
            size = {} if blocks == "default" else dict(zip(("rows", "lanes"), map(int, blocks.split("x"))))
            size.update(q_heads=a[1].shape[0], k_heads=a[2].shape[0])
            fwd = jax.jit(functools.partial(kernels.conv_fwd, **size))
            bwd = jax.jit(functools.partial(kernels.conv_bwd, **size))
            x, w = a[0], op._weights(*a[1:])
            print(json.dumps({"cell": cell, "blocks": blocks, "ms": {
                "delta_conv_fwd": timed(fwd, x, w), "delta_conv_bwd": timed(bwd, x, w, *flat)}}), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
