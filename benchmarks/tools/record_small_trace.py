#!/usr/bin/env python3
"""Record the small trace that `benchmarks/tests/test_trace_reduce.py` keeps:
`python benchmarks/tools/record_small_trace.py --chips 4 --out <dir>` on a
machine with the chips.  A narrow 2-layer decoder of the same model code
under fsdp, two steps under `jax.profiler` with the loop's own span names, so
the file holds what a real trace holds (device planes, the `XLA Ops` line,
Mosaic calls, collectives) at a size git can carry.  This process owns the
chips; it is a recording tool, not a measurement."""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SMALL = {
    "kind": "dense_decoder", "hidden_size": 512, "intermediate_size": 1024,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "vocab_size": 2048, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "tie_word_embeddings": False, "hidden_act": "silu",
}
SEQ, STEPS = 512, 2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks.builders import dense_decoder
    from benchmarks.loops.train_steps import HOST_SPANS, STEP_SPAN, hlo_facts

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        raise SystemExit(f"needs {args.chips} TPU chip(s), jax gave {devices}")
    mesh = {"data": 1} if args.chips == 1 else {"data": 1, "fsdp": args.chips}
    config = dict(SMALL, train={
        "chips": args.chips, "mesh": mesh, "strategy": "dp" if args.chips == 1 else "fsdp",
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "optimizer": "default_optimizer", "remat_policy": "qkv_attn"})
    _, ctx = dense_decoder.build(config, SEQ, devices)
    state = ctx.init_state(seed=0)
    rng = np.random.default_rng(0)

    def step():
        nonlocal state
        with TraceAnnotation(STEP_SPAN):
            with TraceAnnotation(HOST_SPANS[0]):
                toks = rng.integers(0, SMALL["vocab_size"], (args.chips, SEQ + 1), dtype=np.int32)
                batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
            with TraceAnnotation(HOST_SPANS[1]):
                state, metrics = ctx.train_step(state, batch)
            with TraceAnnotation(HOST_SPANS[2]):
                loss = float(metrics["loss"])
            with TraceAnnotation(HOST_SPANS[3]):
                pass
        return batch, loss

    batch, _ = step()
    step()
    with ctx.mesh:
        text = ctx._train_step.lower(state, ctx.make_batch(batch)).compile().as_text()
    facts = hlo_facts(text)
    trace_dir = os.path.join(args.out, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(STEPS):
        step()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    name = f"v5e_{args.chips}chip_small"
    size = os.path.getsize(path)
    with open(path, "rb") as src, gzip.open(os.path.join(args.out, name + ".xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(args.out, name + ".facts.json"), "w") as f:
        json.dump({"chips": args.chips, "steps": STEPS, "kernel_ops": facts["kernel_ops"],
                   "collectives": facts["collectives"], "device_kind": devices[0].device_kind,
                   "bytes": size}, f, indent=1)
    shutil.rmtree(trace_dir)
    print(json.dumps({"recorded": name, "bytes": size, **facts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
