#!/usr/bin/env python3
"""On the chip: one layer's Mamba-2 scan alone, the Mosaic kernels
(`ray_tpu/ops/pallas/ssd.py`) beside the plain chunked form
(`ray_tpu/ops/ssm.py` `_plain_forward`, differentiated by JAX) at the shapes of
the two cells that run it (1 x 8,192 positions, 64 heads of 64, state 128,
chunk 256; `granite`: one group of B and C, `nemotron`: 8 groups).

    chiprun -- python3 scripts/ssd_check.py [--seeds 2] [--heads 4 8 16]

Per shape and seed, one line: y and the six cotangents of each form against
the plain form on the SAME values typed float32 at `highest` matmul precision
(relative RMS and, for y, the largest difference), and whether everything is
finite.  Then ms a call, forward and forward + backward, both forms.
`--heads` times the kernels at other numbers of heads a program.  First, what
XLA's default precision does to the float32 operand of the read-out product
`bctn,bchpn->bchtp`: its distance from the same product with that operand
rounded to bf16 (one pass of the MXU) and from `highest`.

Inputs have the statistics of the cells' weights at initialisation
(`models/mixers/mamba2.py`): x, B, C the SiLU of normals in bf16, dt the
softplus of a normal around a step log-uniform in [1e-3, 1e-1], A = -(1..H),
D = 1.  Exit 1 if a kernel's y or a cotangent is further from the float32 form
than the plain form's by more than a tenth (plus 1e-4)."""

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

from ray_tpu.ops import kernel_pair, ssm as op
from ray_tpu.ops.pallas import ssd as kernels

B, S, H, P, N = 1, 8192, 64, 64, 128
SHAPES = {"granite": None, "nemotron": 8}  # groups of B and C
NAMES = ("x", "dt", "A", "B", "C", "D")


@functools.partial(jax.jit, static_argnums=(1,))
def inputs(seed, groups):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf16 = jnp.bfloat16
    x = jax.nn.silu(jax.random.normal(ks[0], (B, S, H, P))).astype(bf16)
    step = jnp.exp(jax.random.uniform(ks[1], (H,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    bias = step + jnp.log(-jnp.expm1(-step))  # the inverse of softplus
    dt = jax.nn.softplus(0.5 * jax.random.normal(ks[2], (B, S, H)) + bias)
    shape = (B, S, N) if groups is None else (B, S, groups, N)
    Bm, Cm = (jax.nn.silu(jax.random.normal(k, shape)).astype(bf16) for k in ks[3:5])
    probe = jax.random.normal(ks[5], (B, S, H, P)).astype(bf16)
    return (x, dt, -jnp.arange(1, H + 1, dtype=jnp.float32), Bm, Cm, jnp.ones((H,), jnp.float32)), probe


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


def timed(f, *args, n: int = 10) -> float:
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def plain(*args):
    return op._plain_forward(*args, op.CHUNK)


def kernel(*args):
    return kernel_pair.vjp(op.SCAN)(op.CHUNK, *args)


def both_ways(form):
    """(forward, forward + backward) of a form, jitted: the second returns y's probe sum's six gradients."""
    def loss(probe, *args):
        return jnp.sum(form(*args).astype(jnp.float32) * probe.astype(jnp.float32))

    return jax.jit(form), jax.jit(jax.grad(loss, argnums=range(1, 7)))


def kernels_alone():
    """The kernels alone, outside `ops/ssm.py`'s jits (which are traced once): new functions, so new traces."""
    def forward(x, dt, A, Bm, Cm, D):
        dtc, cum = op._running_sums(dt, A, op.CHUNK)
        return kernels.ssd_fwd(x, dtc.reshape(dt.shape), cum.reshape(dt.shape), Bm, Cm, D, chunk=op.CHUNK)

    def backward(x, dt, A, Bm, Cm, D, entering, dy):
        dtc, cum = op._running_sums(dt, A, op.CHUNK)
        return kernels.ssd_bwd(x, dtc.reshape(dt.shape), cum.reshape(dt.shape), Bm, Cm, D, entering, dy, chunk=op.CHUNK)

    return jax.jit(forward), jax.jit(backward)


def read_out_passes(seed: int) -> dict:
    """What XLA's default precision makes of the read-out's float32 operand."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    Cc = jax.random.normal(ks[0], (1, 4, 256, N)).astype(jnp.bfloat16)
    entering = jax.random.normal(ks[1], (1, 4, H, P, N), jnp.float32)
    product = lambda c, e, **kw: jnp.einsum("bctn,bchpn->bchtp", c.astype(jnp.float32), e,
                                            preferred_element_type=jnp.float32, **kw)
    default = jax.jit(product)(Cc, entering)
    one_pass = jax.jit(functools.partial(product, precision="highest"))(Cc, entering.astype(jnp.bfloat16).astype(jnp.float32))
    exact = jax.jit(functools.partial(product, precision="highest"))(Cc, entering)
    return {"read_out_default_vs_one_bf16_pass": rel(default, one_pass), "read_out_default_vs_highest": rel(default, exact)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--heads", type=int, nargs="*", default=[], help="heads a program to time as well")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this check needs the chip: a CPU run says nothing about Mosaic's arithmetic or time", file=sys.stderr)
        return 1
    print(json.dumps(read_out_passes(args.first_seed)), flush=True)
    ok = True
    forms = {"plain": both_ways(plain), "kernel": both_ways(kernel)}
    for shape, groups in SHAPES.items():
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            scan_args, probe = inputs(seed, groups)
            typed = tuple(a.astype(jnp.float32) for a in scan_args)
            with jax.default_matmul_precision("highest"):
                exact_fwd, exact_bwd = both_ways(plain)
                want_y, want_d = exact_fwd(*typed), exact_bwd(probe, *typed)
            line = {"shape": shape, "seed": seed}
            far = {}
            for name, (fwd, bwd) in forms.items():
                y, d = fwd(*scan_args), bwd(probe, *scan_args)
                far[name] = [rel(y, want_y)] + [rel(a, w) for a, w in zip(d, want_d)]
                line[name] = dict(zip(("y",) + tuple("d" + n for n in NAMES), (float(f"{v:.3g}") for v in far[name])))
                line[name]["y_max_abs"] = float(jnp.max(jnp.abs(y.astype(jnp.float32) - want_y)))
                line[name]["finite"] = all(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))) for a in (y, *d))
                ok &= line[name]["finite"]
            ok &= all(k <= 1.1 * p + 1e-4 for k, p in zip(far["kernel"], far["plain"]))
            print(json.dumps(line), flush=True)
        for name, (fwd, bwd) in forms.items():
            print(json.dumps({"shape": shape, "form": name, "forward_ms": timed(fwd, *scan_args),
                              "forward_backward_ms": timed(bwd, probe, *scan_args)}), flush=True)
        for heads in args.heads:
            usual, kernels._HEADS = kernels._HEADS, heads  # read when a jit traces: at the first call below
            try:
                fwd, bwd = kernels_alone()
                y, entering = fwd(*scan_args)
                jax.block_until_ready(bwd(*scan_args, entering, probe))
                print(json.dumps({"shape": shape, "form": "kernel", "heads": kernels.head_block(H // (groups or 1), P),
                                  "forward_ms": timed(fwd, *scan_args),
                                  "backward_ms": timed(bwd, *scan_args, entering, probe)}), flush=True)
            except Exception as e:  # noqa: BLE001: a setting Mosaic refuses is a line of the sweep
                print(json.dumps({"shape": shape, "heads": heads, "error": f"{type(e).__name__}: {str(e)[:300]}"}), flush=True)
            finally:
                kernels._HEADS = usual
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
