#!/usr/bin/env python3
"""Record the trace that `benchmarks/tests/test_block_diffusion_moe_decoder.py`
keeps: `chiprun -- python3 benchmarks/tools/record_sdar_trace.py` on a machine
with one chip.  The `block_diffusion_moe_decoder` kind at the harness's
rehearsal width (d 256, two q heads and one k/v head of the published 128, 16
of 128 experts of 768 held) with two layers, at 1 x 1,024 tokens (2,048 rows
`[x_t | x_0]`, blocks of 4), TWO steps under `jax.profiler` with the loop's
own span names and the run record's step counters beside them: what
`trace_sdar`'s readers read of the real cell (the three flash kernels under
`attn/block_diffusion`, `diffusion/noise`, the four `moe/*`), small enough to
keep.  This process owns the chip; it is a recording tool, not a measurement."""

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

NAME = "v5e_one_chip_sdar"  # sorts behind the four-chip traces: `test_trace_reduce.py` takes the first of the directory
SEQ = 1024
STEPS = 2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sdar_trace"))
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks import run as harness
    from benchmarks.builders import block_diffusion_moe_decoder
    from benchmarks.loops.train_steps import HOST_SPANS, STEP_SPAN, hlo_facts
    from benchmarks.tools.record_scoped_trace import without_planes

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU chip, jax gave {devices}")
    _, config, _ = harness.load_cell("sdar-ep8-1chip.seq8k")
    config = {**config, **harness.REHEARSAL_CONFIG}
    _, ctx = block_diffusion_moe_decoder.build(config, SEQ, devices)
    state = ctx.init_state(seed=0)
    rng = np.random.default_rng(0)
    counters = []

    def step():
        nonlocal state
        with TraceAnnotation(STEP_SPAN):
            with TraceAnnotation(HOST_SPANS[0]):
                toks = rng.integers(0, config["vocab_size"], (1, SEQ + 1), dtype=np.int32)
                batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
            with TraceAnnotation(HOST_SPANS[1]):
                state, metrics = ctx.train_step(state, batch)
            with TraceAnnotation(HOST_SPANS[2]):
                loss = float(metrics["loss"])
            with TraceAnnotation(HOST_SPANS[3]):
                pass
        counters.append({k: float(v) for k, v in metrics.items() if k.startswith(("moe_", "ce_", "diffusion_", "attn_"))})
        return batch, loss

    batch, _ = step()
    step()
    with ctx.mesh:
        text = ctx._train_step.lower(state, ctx.make_batch(batch)).compile().as_text()
    facts = hlo_facts(text)
    os.makedirs(args.out, exist_ok=True)
    trace_dir = os.path.join(args.out, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(STEPS):
        step()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    size = os.path.getsize(path)
    with open(path, "rb") as src, gzip.open(os.path.join(args.out, NAME + ".xplane.pb.gz"), "wb") as dst:
        dst.write(without_planes(src.read()))
    with open(os.path.join(args.out, NAME + ".facts.json"), "w") as f:
        json.dump({"chips": 1, "steps": STEPS, "seq_len": SEQ, "tokens_per_step": SEQ, "config": config,
                   "kernel_ops": facts["kernel_ops"], "device_kind": devices[0].device_kind, "bytes": size,
                   "step_counters": counters[-STEPS:]}, f, indent=1)
    shutil.rmtree(trace_dir)
    print(json.dumps({"recorded": NAME, "bytes": size,
                      "gz_bytes": os.path.getsize(os.path.join(args.out, NAME + ".xplane.pb.gz")),
                      "tpu_custom_calls": facts["tpu_custom_calls"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
