#!/usr/bin/env python3
"""Compile a cell's real train step for a described TPU topology, here, with
no chip: `python benchmarks/tools/aot.py --workload <cell> [--layers N ...]`.

A REHEARSAL, never a result: nothing runs, so it gives no time.  libtpu
enforces HBM, so a depth that cannot fit is refused before chip time is
spent, and `memory_analysis()` says how full a chip would be.  `--layers`
tries several depths in one process (the topology can be described once).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--layers", type=int, nargs="*", help="depths to try (default: the file's)")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks import run as harness
    from benchmarks.lib import flops

    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    cell, config, traffic = harness.load_cell(args.workload)
    builder = harness.load_plugin("builders", config["kind"])
    bounds = tuple(int(x) for x in args.topology.partition(":")[2].split("x"))
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology, chip_config_name="default",
        chips_per_host_bounds=bounds + (1,) * (3 - len(bounds)), num_slices=1,
    )
    peaks = flops.load_peaks(topo.devices[0].device_kind)
    seq, batch = traffic["seq_len"], traffic["seqs_per_chip"] * cell["chips"]
    for layers in args.layers or [config["num_hidden_layers"]]:
        cfg_l = dict(config, num_hidden_layers=layers)
        _, ctx = builder.build(cfg_l, seq, topo.devices)
        state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=ctx.batch_sharding)
        t0 = time.perf_counter()
        out = {"rehearsal": "aot", "cell": cell["name"], "topology": args.topology,
               "layers": layers, "params": flops.total_params(cfg_l)}
        try:
            with ctx.mesh:
                compiled = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile()
        except Exception as e:  # noqa: BLE001: the compiler's refusal IS the answer
            out.update(fits=False, error=f"{type(e).__name__}: {str(e)[:400]}")
        else:
            mem = compiled.memory_analysis()
            live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            text = compiled.as_text()
            out.update(
                fits=True, argument_gb=round(mem.argument_size_in_bytes / 1e9, 3),
                output_gb=round(mem.output_size_in_bytes / 1e9, 3),
                temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
                alias_gb=round(mem.alias_size_in_bytes / 1e9, 3),
                sum_gb=round(live / 1e9, 3),
                peak_memory_gb=round(mem.peak_memory_in_bytes / 1e9, 3),
                hbm_share=round(mem.peak_memory_in_bytes / peaks["hbm_bytes"], 3),
                tpu_custom_calls=text.count("tpu_custom_call"),
            )
        out["compile_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
