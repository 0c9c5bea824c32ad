#!/usr/bin/env python3
"""Record the trace that `benchmarks/tests/test_trace_scopes.py` keeps:
`python benchmarks/tools/record_scoped_trace.py --out <dir>` on a machine with
four chips.  A 2-layer decoder of the same model code under `fsdp=4`, ONE step
under `jax.profiler` with the loop's own span names, so the file holds what
the real four-chip cell's trace holds and the small recorded one does not:
the program's scopes and kernel names, its `train_step/*` spans, and the
windowed-einsum `collective-permute-start/-done` pairs.

What makes XLA:TPU emit those pairs is not the width but the tokens per chip
(found by compiling for a described v5e:2x2, PR 24: none at 1 x 1024 per chip
at any width tried, 69 static pairs per step from 1 x 1536 on, at d 256 as at
d 4096), so the default is a narrow model at 1 x 2048 per chip.  `--probe`
compiles the same step for the described topology, with no chip, and prints
the collectives: the way to find that size again.  This process owns the
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

CHIPS = 4
VOCAB = 4096
NAME = "v5e_4chip_scoped"


def small_config(args) -> dict:
    return {
        "kind": "dense_decoder", "hidden_size": args.d_model, "intermediate_size": args.d_ff,
        "num_hidden_layers": 2, "num_attention_heads": args.d_model // 128,
        "num_key_value_heads": max(1, args.d_model // 256), "vocab_size": VOCAB,
        "rms_norm_eps": 1e-5, "rope_theta": 1e6, "tie_word_embeddings": False, "hidden_act": "silu",
        "train": {"chips": CHIPS, "mesh": {"data": 1, "fsdp": CHIPS}, "strategy": "fsdp",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "optimizer": "default_optimizer", "remat_policy": "qkv_attn"},
    }


def without_planes(data: bytes, drop=("/host:metadata",)) -> bytes:
    """The serialized XSpace without the planes named in `drop`: the step's
    HLO proto (`/host:metadata`, two fifths of the file) is read by neither
    reduction.  Every top-level field of an XSpace is length-delimited, so
    the others are copied byte for byte."""
    from benchmarks.lib.trace_scopes import _fields, _text

    def varint(n: int) -> bytes:
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    kept = bytearray()
    for field, value in _fields(memoryview(data)):
        name = next((_text(v) for f, v in _fields(value) if f == 2), "") if field == 1 else ""
        if name not in drop:
            kept += varint(field << 3 | 2) + varint(len(value)) + bytes(value)
    return bytes(kept)


def probe(config: dict, seq: int) -> int:
    """Compile the step for a described v5e:2x2 and print its collectives."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks.builders import dense_decoder
    from benchmarks.loops.train_steps import hlo_facts

    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2", chip_config_name="default",
        chips_per_host_bounds=(2, 2, 1), num_slices=1)
    _, ctx = dense_decoder.build(config, seq, topo.devices)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((CHIPS, seq), jnp.int32, sharding=ctx.batch_sharding)
    with ctx.mesh:
        text = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile().as_text()
    print(json.dumps({"rehearsal": "aot", "seq": seq, **hlo_facts(text)}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "scoped_trace"))
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--probe", action="store_true", help="compile for a described v5e:2x2, no chip")
    args = ap.parse_args()
    config = small_config(args)
    if args.probe:
        return probe(config, args.seq)

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks.builders import dense_decoder
    from benchmarks.loops.train_steps import HOST_SPANS, STEP_SPAN, hlo_facts

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < CHIPS:
        raise SystemExit(f"needs {CHIPS} TPU chips, jax gave {devices}")
    _, ctx = dense_decoder.build(config, args.seq, devices)
    state = ctx.init_state(seed=0)
    rng = np.random.default_rng(0)

    def step():
        nonlocal state
        with TraceAnnotation(STEP_SPAN):
            with TraceAnnotation(HOST_SPANS[0]):
                toks = rng.integers(0, VOCAB, (CHIPS, args.seq + 1), dtype=np.int32)
                batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
            with TraceAnnotation(HOST_SPANS[1]):
                state, metrics = ctx.train_step(state, batch)  # a host batch: the program's own spans
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
    os.makedirs(args.out, exist_ok=True)
    trace_dir = os.path.join(args.out, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    step()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    size = os.path.getsize(path)
    with open(path, "rb") as src, gzip.open(os.path.join(args.out, NAME + ".xplane.pb.gz"), "wb") as dst:
        dst.write(without_planes(src.read()))
    with open(os.path.join(args.out, NAME + ".facts.json"), "w") as f:
        json.dump({"chips": CHIPS, "steps": 1, "seq_len": args.seq, "tokens_per_step": CHIPS * args.seq,
                   "config": config, "kernel_ops": facts["kernel_ops"], "collectives": facts["collectives"],
                   "device_kind": devices[0].device_kind, "bytes": size}, f, indent=1)
    shutil.rmtree(trace_dir)
    print(json.dumps({"recorded": NAME, "bytes": size,
                      "gz_bytes": os.path.getsize(os.path.join(args.out, NAME + ".xplane.pb.gz")), **facts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
