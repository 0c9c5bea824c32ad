#!/usr/bin/env python3
"""The three flash kernels ALONE, this tree's beside the parent's of PR 70 (commit 73f4f12), in ONE process: ms a call
of each kernel and whether out, lse, dq, dk, dv are BIT-EQUAL.  Since PR 70 the kernels' innermost grid axis walks a
table of the visible tile pairs (`flash_attention.tile_pairs`); the parent's walked a rectangle (a causal call) or the
longest row of visible tiles (a windowed or a block-diffusion call) and predicated the other steps off.  The pairs that
compute, their order and the bodies are the same, so every output has to be the same bits.

    chiprun -- python3 scripts/flash_pairs_check.py --parent _chip/parent_flash.py [--heads 32] [--reps 12] [--seed 70]
    python3 scripts/flash_pairs_check.py --cpu-toy          # here: interpret mode at small shapes, bits alone

The parent's module is the text of `git show 73f4f12:ray_tpu/ops/pallas/flash_attention.py` executed as a module of its
own; the chip's copy of the repo has no `.git`, so there `--parent` names a file that holds that text (`_chip/` is
ignored by git and copied to the chip).  On the chip, bf16, one sequence, the tiles `flash_attention` gives the call:
16,384 causal positions (`mistral7b-1chip.seq16k`), 2 x 8,192 block-diffusion rows in blocks of 4
(`sdar-ep8-1chip.seq8k`), 1,024 causal positions (`seq1k`: one query tile a head, the same grid steps in both trees), a
window of 1,024 over 16,384 (`mellum2`), 8,192 causal at heads of 256 / 256 (`glm47`), a window of 513 over 8,192 at
heads of 192 / 128 (`dots3-note`), and 2,048 queries over 4,096 keys.  dq and dkv are timed alone (`_flash_bwd` with
the other output dropped, so XLA removes its kernel; `delta`'s elementwise pass rides both).  Each kernel is timed
parent, change, change, parent; the line gives the lower median of each.  It took the place of
`scripts/flash_off_steps_check.py` (PR 55: what an off step cost, PERF.md section 6), whose subject no longer exists.
PERF.md section 6, PR 70, holds the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import BlockDiffusion
from ray_tpu.ops.pallas import flash_attention as fa

PARENT = "73f4f1289f9c8a810b47691e2880d35ec2a46344"
# (name, queries, keys, q/k head size, v head size, window, block-diffusion mask): causal unless the last is given
CHIP = (("causal-16384", 16384, 16384, 128, 128, None, None),
        ("diffusion-2x8192-b4", 16384, 16384, 128, 128, None, BlockDiffusion(4, 8192)),
        ("causal-1024", 1024, 1024, 128, 128, None, None),
        ("window-1024-of-16384", 16384, 16384, 128, 128, 1024, None),
        ("causal-8192-d256", 8192, 8192, 256, 256, None, None),
        ("window-513-of-8192-d192", 8192, 8192, 192, 128, 513, None),
        ("causal-2048-of-4096-keys", 2048, 4096, 128, 128, None, None))
TOY = (("causal-512", 512, 512, 64, 64, None, None), ("diffusion-2x256-b4", 512, 512, 64, 64, None, BlockDiffusion(4, 256)),
       ("diffusion-256-one-copy", 256, 256, 64, 64, None, BlockDiffusion(4, 0)), ("causal-128", 128, 128, 64, 64, None, None),
       ("window-200-of-640", 640, 640, 64, 128, 200, None), ("window-129-of-384", 384, 384, 192, 128, 129, None),
       ("causal-256-of-512-keys", 256, 512, 64, 64, None, None), ("causal-512-of-256-keys", 512, 256, 64, 64, None, None))


def parents_module(path):
    """The parent's `flash_attention.py` as a module of its own, from a file or from git."""
    if path:
        text = open(path).read()
    else:
        text = subprocess.run(["git", "show", f"{PARENT}:ray_tpu/ops/pallas/flash_attention.py"], cwd=ROOT, check=True,
                              capture_output=True, text=True).stdout
    module = types.ModuleType("parents_flash_attention")
    module.__file__ = path or f"{PARENT}:ray_tpu/ops/pallas/flash_attention.py"
    sys.modules[module.__name__] = module
    exec(compile(text, module.__file__, "exec"), module.__dict__)
    assert hasattr(module, "_inner_tile") and not hasattr(module, "tile_pairs"), "that is not the parent's module"
    return module


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a file that holds the parent's flash_attention.py (default: git show)")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=70)
    ap.add_argument("--cpu-toy", action="store_true", help="interpret mode at small shapes: the bits alone, no times")
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.cpu_toy:
        print("this check needs the chip: off it the kernels run interpreted and their times say nothing", file=sys.stderr)
        return 1
    parent = parents_module(args.parent)
    print(json.dumps({"device": jax.devices()[0].device_kind, "seed": args.seed, "parent": parent.__file__}), flush=True)
    h, dtype = (args.heads, jnp.bfloat16) if on_chip else (2, jnp.float32)
    every_bit = True
    for name, sq, sk, d, dv, window, bd in (CHIP if on_chip else TOY):
        blocks = fa.DEFAULT_BLOCKS if on_chip else (128, 128, 128, 64)
        blocks = fa._head_blocks(d, dv, blocks if window is None else fa._window_blocks(window, blocks))
        tiles = fa._diffusion_blocks(sq, bd, blocks) if bd is not None else tuple(
            fa._fit_block(s, b) for s, b in zip((sq, sk, sq, sk), blocks))
        mask = dict(causal=bd is None, scale=d ** -0.5, window=window, diffusion=bd)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, g = jax.random.normal(keys[0], (1, sq, h, d), dtype), jax.random.normal(keys[1], (1, sq, h, dv), dtype)
        k, v = jax.random.normal(keys[2], (1, sk, h, d), dtype), jax.random.normal(keys[3], (1, sk, h, dv), dtype)

        def programs(module):  # jitted HERE, once a module: a jitted function shared by both would run the first one's kernels
            fwd = jax.jit(lambda q, k, v: module._flash_fwd(q, k, v, block_q=tiles[0], block_k=tiles[1], **mask))
            bwd = lambda pick: jax.jit(lambda q, k, v, o, l, g: pick(module._flash_bwd(  # noqa: E731
                q, k, v, o, l, g, block_q=tiles[2], block_k=tiles[3], **mask)))
            return {"fwd": fwd, "dq": bwd(lambda r: r[0]), "dkv": bwd(lambda r: r[1:]), "all": bwd(lambda r: r)}

        trees = {"parent": programs(parent), "change": programs(fa)}
        results = {}
        for tree, fns in trees.items():
            o, lse = fns["fwd"](q, k, v)
            results[tree] = [np.asarray(x.astype(jnp.float32)) for x in (o, lse, *fns["all"](q, k, v, o, lse, g))]
        same = [bool(np.array_equal(a, b)) for a, b in zip(results["parent"], results["change"])]
        every_bit &= all(same)
        n_q, n_k, n_bq, n_bk = sq // tiles[0], sk // tiles[1], sq // tiles[2], sk // tiles[3]
        line = {"shape": name, "tiles": tiles, "heads": h, "bit_equal out lse dq dk dv": same,
                "pairs_a_head fwd dq dkv": [len(fa.tile_pairs(sq, sk, *t, bd is None, window, bd, keys).own)
                                            for t, keys in ((tiles[:2], True), (tiles[2:], True), (tiles[2:], False))],
                "parents_steps_a_head fwd dq dkv": [
                    n_q * parent._inner_tile(n_q, n_k, tiles[0], tiles[1], window, keys=True, causal=bd is None, diffusion=bd)[0],
                    n_bq * parent._inner_tile(n_bq, n_bk, tiles[2], tiles[3], window, keys=True, causal=bd is None, diffusion=bd)[0],
                    n_bk * parent._inner_tile(n_bk, n_bq, tiles[3], tiles[2], window, keys=False, causal=bd is None, diffusion=bd)[0]]}
        if on_chip:
            o, lse = trees["parent"]["fwd"](q, k, v)
            for kernel in ("fwd", "dq", "dkv"):
                operands = (q, k, v) if kernel == "fwd" else (q, k, v, o, lse, g)
                ms = {tree: [] for tree in trees}
                for tree in ("parent", "change", "change", "parent"):
                    ms[tree].append(timed(trees[tree][kernel], operands, args.reps))
                line[kernel] = {"ms_parent": round(min(ms["parent"]), 4), "ms_change": round(min(ms["change"]), 4),
                                "change_pct": round(100 * (min(ms["change"]) / min(ms["parent"]) - 1), 2),
                                "ms_all_runs": {t: [round(x, 4) for x in xs] for t, xs in ms.items()}}
        print(json.dumps(line), flush=True)
    print(json.dumps({"every output bit-equal to the parent's": every_bit}), flush=True)
    return 0 if every_bit else 1


if __name__ == "__main__":
    sys.exit(main())
