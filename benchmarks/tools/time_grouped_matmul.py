#!/usr/bin/env python3
"""Time the two candidates for the expert layer's grouped (ragged) matmul on
the chip, at the shapes of `olmoe-1chip.seq4k` (ISSUE 26):

    chiprun -- python3 benchmarks/tools/time_grouped_matmul.py

65,536 rows (8,192 tokens x top-8) sorted by expert, 64 groups whose sizes
come from a real router on the cell's seeded stream (layer 0: embedding ->
RMSNorm -> router -> softmax -> top-8, seeded normal weights as `init_params`
draws them), `[2048 -> 1024]` twice (gate, up), `silu(gate) * up`,
`[1024 -> 2048]` once (down); forward alone, and forward + backward for both
operands of every matmul.  Candidates: `jax.lax.ragged_dot` and the megablox
Pallas kernels that ship with jax (`gmm` / `tgmm`) at several tilings.  A
dense batched einsum over 64 EQUAL groups of 1,024 rows is the ceiling.

`--aot` compiles every candidate for a described v5e here, with no chip, and
times nothing.  Not part of the benchmark: no cell and no metric reads it; it
is the record of how PERF.md section 6 (PR 26) got its two timings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOKENS, TOP_K, EXPERTS, D, F, VOCAB = 8192, 8, 64, 2048, 1024, 50304
# (m, k, n) tiles.  512x2048x1024, 1024x1024x1024 and 256x2048x1024 do not fit VMEM (v5e AOT, PR 26).
TILINGS = [(512, 1024, 1024), (512, 512, 1024), (256, 1024, 1024), (512, 1024, 512),
           (512, 512, 512), (256, 512, 1024), (128, 128, 128)]


def group_sizes_from_router(seed: int):
    """Layer-0 routing of one seeded batch of the cell's stream, on the host."""
    import numpy as np

    from benchmarks.lib import datagen

    with open(os.path.join(ROOT, "benchmarks", "traffic", "seq4k.json")) as f:
        traffic = json.load(f)
    stream = datagen.PackedStream(seed, VOCAB, traffic["stream"])
    tokens = stream.next_batch(traffic["seqs_per_chip"], traffic["seq_len"])["tokens"].reshape(-1)
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((VOCAB, D), np.float32) * D ** -0.5
    router = rng.standard_normal((D, EXPERTS), np.float32) * D ** -0.5
    x = table[tokens]
    x = x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    logits = x @ router
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :TOP_K]
    return np.bincount(top.reshape(-1), minlength=EXPERTS).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--aot", action="store_true", help="compile for a described v5e, time nothing")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    if args.aot:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    sizes = group_sizes_from_router(args.seed)
    rows = int(sizes.sum())
    assert rows == TOKENS * TOP_K
    print("[gmm] group sizes " + json.dumps({
        "min": int(sizes.min()), "median": float(np.median(sizes)), "max": int(sizes.max()),
        "max_over_mean": float(sizes.max() / sizes.mean()), "sizes": sizes.tolist()}), flush=True)

    def ffn(mm):
        def f(x, wg, wu, wd, gs):
            return mm(jax.nn.silu(mm(x, wg, gs)) * mm(x, wu, gs), wd, gs)
        return f

    def ragged(a, w, gs):
        return jax.lax.ragged_dot(a, w, gs)

    def pallas(tiling):
        def mm(a, w, gs):
            return megablox.gmm(a, w, gs, jnp.bfloat16, tiling)
        return mm

    def dense(x, wg, wu, wd, gs):
        xe = x.reshape(EXPERTS, rows // EXPERTS, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum("ecd,edf->ecf", xe, wu)
        return jnp.einsum("ecf,efd->ecd", h, wd).reshape(rows, D)

    candidates = [("dense_equal_groups", dense), ("ragged_dot", ffn(ragged))]
    candidates += [(f"megablox_{tm}x{tk}x{tn}", ffn(pallas((tm, tk, tn)))) for tm, tk, tn in TILINGS]

    shapes = [((rows, D), jnp.bfloat16), ((EXPERTS, D, F), jnp.bfloat16), ((EXPERTS, D, F), jnp.bfloat16),
              ((EXPERTS, F, D), jnp.bfloat16), ((rows, D), jnp.bfloat16)]
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        operands = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
        gs = jax.ShapeDtypeStruct((EXPERTS,), jnp.int32, sharding=one)
    else:
        dev = jax.devices()[0]
        print("[gmm] device " + json.dumps({"platform": dev.platform, "kind": dev.device_kind}), flush=True)
        if dev.platform != "tpu":
            print("[gmm] no TPU: a timing here would be the CPU's", flush=True)
            return 1
        keys = jax.random.split(jax.random.PRNGKey(args.seed), len(shapes))
        operands = [(jax.random.normal(k, s, jnp.float32) * (s[-2] ** -0.5 if len(s) == 3 else 1.0)).astype(d)
                    for k, (s, d) in zip(keys, shapes)]
        gs = jnp.asarray(sizes)

    flops_fwd = 2.0 * rows * D * F * 3
    results = {}
    for name, f in candidates:
        fwd = jax.jit(f)

        def loss(x, wg, wu, wd, ct, gs, f=f):
            return jnp.sum(f(x, wg, wu, wd, gs).astype(jnp.float32) * ct.astype(jnp.float32))

        both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
        try:
            if args.aot:
                t0 = time.perf_counter()
                c1 = fwd.lower(*operands[:4], gs).compile()
                c2 = both.lower(*operands, gs).compile()
                results[name] = {"compiles": True, "compile_s": round(time.perf_counter() - t0, 1),
                                 "fwd_temp_gb": round(c1.memory_analysis().temp_size_in_bytes / 1e9, 3),
                                 "both_temp_gb": round(c2.memory_analysis().temp_size_in_bytes / 1e9, 3)}
                print(f"[gmm] {name} " + json.dumps(results[name]), flush=True)
                continue
            out = {}
            for label, fn, ops in (("fwd", fwd, (*operands[:4], gs)), ("fwd_bwd", both, (*operands, gs))):
                jax.block_until_ready(fn(*ops))  # compile
                jax.block_until_ready(fn(*ops))
                times = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(args.iters):
                        r = fn(*ops)
                    jax.block_until_ready(r)
                    times.append((time.perf_counter() - t0) / args.iters)
                out[label + "_ms"] = 1e3 * min(times)
            out["bwd_ms"] = out["fwd_bwd_ms"] - out["fwd_ms"]
            out["fwd_pct_of_peak"] = 100 * flops_fwd / 197e12 / (out["fwd_ms"] / 1e3)
            out["fwd_bwd_pct_of_peak"] = 100 * 3 * flops_fwd / 197e12 / (out["fwd_bwd_ms"] / 1e3)
            results[name] = out
        except Exception as e:  # noqa: BLE001: a candidate the compiler refuses is a finding
            results[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(f"[gmm] {name} " + json.dumps(results[name]), flush=True)

    if not args.aot:
        # Same numbers from both, on the real group sizes (bf16 against bf16).
        a = jax.jit(ffn(ragged))(*operands[:4], gs).astype(jnp.float32)
        b = jax.jit(ffn(pallas(TILINGS[0])))(*operands[:4], gs).astype(jnp.float32)
        results["agreement_rel_rms"] = float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(a ** 2)))
        timed = {k: v["fwd_bwd_ms"] for k, v in results.items() if isinstance(v, dict) and "fwd_bwd_ms" in v}
        best_pallas = min((k for k in timed if k.startswith("megablox")), key=timed.get, default=None)
        if best_pallas and "ragged_dot" in timed:
            results["verdict"] = {
                "ragged_dot_fwd_bwd_ms": timed["ragged_dot"], "best_pallas": best_pallas,
                "best_pallas_fwd_bwd_ms": timed[best_pallas],
                "pallas_faster_by_pct": 100 * (timed["ragged_dot"] / timed[best_pallas] - 1)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "grouped_matmul_timing.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
