#!/usr/bin/env python3
"""Record the trace that `benchmarks/tests/test_step_rows.py` keeps:
`chiprun -- python3 benchmarks/tools/record_steps_trace.py` on a machine with
one chip.  A 2-layer dense decoder at 2 x 1,024 tokens, a few steps under
`jax.profiler` with the loop's own span names and `train.report` called
through a `TrainSession`, as the loop calls it: what `trace_idle` reads of a
real cell (the device's gaps, the program's `train_step/make_batch`,
`train_step/dispatch` and `train/report` beside them), at a size where the
host is most of a step, so every span has idle under it.  The facts file
holds the run record's `steps` of the same steps.  This process owns the chip;
it is a recording tool, not a measurement."""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = "v5e_one_chip_steps"  # sorts behind the four-chip traces: `test_trace_reduce.py` takes the first of the directory
SEQ = 1024
SEQS = 2
STEPS = 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "steps_trace"))
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks.builders import dense_decoder
    from benchmarks.loops.train_steps import HOST_SPANS, STEP_SPAN, hlo_facts
    from benchmarks.tools.record_scoped_trace import small_config, without_planes
    from ray_tpu import train
    from ray_tpu.train import run_record
    from ray_tpu.train.session import init_session

    devices = jax.devices()[:1]
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU chip, jax gave {devices}")
    config = small_config(argparse.Namespace(d_model=512, d_ff=2048))
    config["train"] = {**config["train"], "chips": 1, "mesh": {"data": 1}, "strategy": "dp"}
    _, ctx = dense_decoder.build(config, SEQ, devices)
    state = ctx.init_state(seed=0)
    rng = np.random.default_rng(0)
    init_session(rank=0, world_size=1)

    def step():
        nonlocal state
        with TraceAnnotation(STEP_SPAN):
            with TraceAnnotation(HOST_SPANS[0]):
                toks = rng.integers(0, config["vocab_size"], (SEQS, SEQ + 1), dtype=np.int32)
                batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
            with TraceAnnotation(HOST_SPANS[1]):
                state, metrics = ctx.train_step(state, batch)
            with TraceAnnotation(HOST_SPANS[2]):
                loss = float(metrics["loss"])
            with TraceAnnotation(HOST_SPANS[3]):
                train.report({"phase": "step", "loss": loss})
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
    run_record.drain_step_rows()
    t_window = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(STEPS):
        step()
    jax.profiler.stop_trace()
    step()  # closes the last traced step's period
    record = run_record.RunRecord({"trace_id": "recorded", "span_id": "0"})
    record.add_poll(0, {"reports": [], "step_rows": run_record.drain_step_rows(),
                        "tokens_per_step": run_record.set_step_gauges(0)})
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    size = os.path.getsize(path)
    with open(path, "rb") as src, gzip.open(os.path.join(args.out, NAME + ".xplane.pb.gz"), "wb") as dst:
        dst.write(without_planes(src.read()))
    with open(os.path.join(args.out, NAME + ".facts.json"), "w") as f:
        json.dump({"chips": 1, "steps": STEPS, "seq_len": SEQ, "tokens_per_step": SEQS * SEQ, "config": config,
                   "kernel_ops": facts["kernel_ops"], "device_kind": devices[0].device_kind, "bytes": size,
                   "t_window": t_window, "record_steps": record.to_dict()["steps"]}, f, indent=1)
    shutil.rmtree(trace_dir)
    print(json.dumps({"recorded": NAME, "bytes": size,
                      "gz_bytes": os.path.getsize(os.path.join(args.out, NAME + ".xplane.pb.gz")),
                      "tpu_custom_calls": facts["tpu_custom_calls"],
                      "steps_summary": record.to_dict()["steps"]["summary"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
