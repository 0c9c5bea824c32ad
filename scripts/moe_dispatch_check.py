#!/usr/bin/env python3
"""On the chip: the expert layer's two ROW MOVEMENTS alone (`ray_tpu/models/moe.py`),
at the shapes of the three cells that hold a share of their experts:

    chiprun -- python3 scripts/moe_dispatch_check.py [--reps 20] [--cells kimi nemotron mellum]

`kimi` is `kimi-linear-ep16-1chip.seq16k` (16,384 tokens of d 2304, 8 choices of
256 experts, 16 held: T*K = 131,072 assignments), `nemotron`
`nemotron3-nano-ep8-1chip.seq8k` (8,192 tokens of d 2688, 6 of 128, 16 held: 49,152),
`mellum` `mellum2-ep4-1chip.seq16k` (16,384 tokens of d 2304, 8 of 64, 16 held: 131,072,
a uniform share of 32,768 on the rung of 40,960 rows since PR 53).
For 0, a uniform router's, a whole lowest rung's and ALL T*K rows held, and for the
uniform router's once more on the NEXT rung (what a rung's step costs a call: 40,960
against 65,536 rows in `mellum`), each movement is timed in the form the layer had
before PR 48 (every assignment moved: a T*K-row gather in, a T*K-row gather by
`inverse` and the sum over K out) and in
the forms a rung of R rows can take (`moe._rungs` names the sizes; the rung is the
smallest that holds the count):

- in: the tokens of the first R assignments in expert order, R rows (`moe._rows_of_tokens`);
- out, `scatter`: `zeros.at[token_of_row].add(rows)` as the rows lie (expert order);
- out, `sorted_scatter`: the R token ids sorted, the rows gathered into token order,
  the same add with `indices_are_sorted`;
- out, `sorted_gather`: rows gathered into token order, a token's (at most K)
  neighbours added by doubling, and each token's sum fetched by a T-row gather
  (`moe._tokens_of_rows`: what the layer runs).

Every form is checked against the old one in float32 (bf16's rounding of a sum of K
rows apart).  One JSON line each, on stdout and in `chiprun_out/moe_dispatch_check.jsonl`."""

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

from ray_tpu.models import moe

# tokens, d_model, choices a token, experts, experts held
CELLS = {"kimi": (16384, 2304, 8, 256, 16), "nemotron": (8192, 2688, 6, 128, 16), "mellum": (16384, 2304, 8, 64, 16)}
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out", "moe_dispatch_check.jsonl")


def say(line) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(text + "\n")


def timed(f, *args, n: int) -> float:
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / max(np.mean(b ** 2), 1e-30)))


def routing(rng, tokens: int, k: int, experts: int, held: int, rows_held: int):
    """expert_idx [T, K] with exactly `rows_held` assignments to experts below `held`."""
    flat = rng.integers(held, experts, size=tokens * k)
    flat[rng.permutation(tokens * k)[:rows_held]] = rng.integers(0, held, size=rows_held)
    return jnp.asarray(flat.reshape(tokens, k), jnp.int32)


# -- the movements before PR 48: every assignment, whatever is held -----------------


@functools.partial(jax.jit, static_argnums=(3,))
def old_in(tokens, order, count, k):
    rows = tokens[order // k]
    return jnp.where((jnp.arange(rows.shape[0]) < count)[:, None], rows, 0)


@functools.partial(jax.jit, static_argnums=(3,))
def old_out(rows, inverse, count, k):
    rows = jnp.where((jnp.arange(rows.shape[0]) < count)[:, None], rows, 0)
    return rows[inverse].reshape(-1, k, rows.shape[-1]).sum(axis=1)


# -- a rung's: R rows ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(4, 5))
def new_in(tokens, order, inverse, count, k, r):
    return moe._rows_of_tokens(tokens, order, inverse, count, k, r)


def _token_of_row(rows, order, count, k):
    """The token each of the R rows belongs to; T (out of range: dropped) behind the count."""
    r = rows.shape[0]
    return jnp.where(jnp.arange(r, dtype=jnp.int32) < count, order[:r] // k, order.shape[0] // k)


@functools.partial(jax.jit, static_argnums=(4,))
def out_scatter(rows, order, inverse, count, k):
    tokens = order.shape[0] // k
    return jnp.zeros((tokens, rows.shape[1]), rows.dtype).at[_token_of_row(rows, order, count, k)].add(rows, mode="drop")


@functools.partial(jax.jit, static_argnums=(4,))
def out_sorted_scatter(rows, order, inverse, count, k):
    tokens = order.shape[0] // k
    ids, perm = jax.lax.sort((_token_of_row(rows, order, count, k), jnp.arange(rows.shape[0], dtype=jnp.int32)), num_keys=1)
    return jnp.zeros((tokens, rows.shape[1]), rows.dtype).at[ids].add(rows[perm], mode="drop", indices_are_sorted=True)


@functools.partial(jax.jit, static_argnums=(4,))
def out_sorted_gather(rows, order, inverse, count, k):
    return moe._tokens_of_rows(rows, order, inverse, count, k, rows.shape[0])


def check_cell(name: str, reps: int) -> bool:
    tokens, d, k, experts, held = CELLS[name]
    rungs = moe._rungs(tokens * k, held, experts, k)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.bfloat16)
    ok = True
    uniform = tokens * k * held // experts
    for label, rows_held, up in (("none", 0, 0), ("uniform", uniform, 0), ("uniform, a rung up", uniform, 1), ("rung", rungs[0], 0),
                                 ("all", tokens * k, 0)):
        idx = routing(rng, tokens, k, experts, held, rows_held)
        order, inverse, sizes = jax.jit(moe._by_expert, static_argnums=(1,))(idx.reshape(-1), held)
        count = int(jnp.sum(sizes))
        assert count == rows_held, (count, rows_held)
        R = rungs[min(next(i for i, r in enumerate(rungs) if r >= count) + up, len(rungs) - 1)]
        y_old = jnp.asarray(rng.standard_normal((tokens * k, d)), jnp.bfloat16)  # what the experts wrote, expert order
        line = {"cell": name, "held": label, "rows_held": count, "rung": R, "rungs": list(rungs), "assignments": tokens * k,
                "in_old_ms": timed(old_in, x, order, count, k, n=reps),
                "in_new_ms": timed(new_in, x, order, inverse, count, k, R, n=reps),
                "out_old_ms": timed(old_out, y_old, inverse, count, k, n=reps)}
        want_in = old_in(x, order, count, k)[:R]
        line["in_equal"] = bool(jnp.array_equal(new_in(x, order, inverse, count, k, R), want_in))
        want_out = old_out(y_old, inverse, count, k)
        for form, f in (("scatter", out_scatter), ("sorted_scatter", out_sorted_scatter), ("sorted_gather", out_sorted_gather)):
            try:
                line[f"out_{form}_ms"] = timed(f, y_old[:R], order, inverse, count, k, n=reps)
                got = f(y_old[:R], order, inverse, count, k)
                # (nothing held: the largest element, which has to be 0)
                line[f"out_{form}_rel"] = rel(got, want_out) if count else float(jnp.max(jnp.abs(got.astype(jnp.float32))))
            except Exception as e:  # a form the compiler refuses is a line of the table, not the end of it
                line[f"out_{form}_error"] = f"{type(e).__name__}: {str(e)[:300]}"
        ok = ok and line["in_equal"] and line.get("out_sorted_gather_rel", 1.0) < 1e-2
        say(line)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cells", nargs="+", default=list(CELLS), choices=list(CELLS))
    args = ap.parse_args()
    say({"device": jax.devices()[0].device_kind, "platform": jax.devices()[0].platform})
    ok = all([check_cell(c, args.reps) for c in args.cells])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
