#!/usr/bin/env python3
"""What would libtpu's rematerialization pass duplicate in a cell's step?  Here, with no chip:

    TPU_STDERR_LOG_LEVEL=0 TPU_MIN_LOG_LEVEL=0 TPU_VMODULE=hlo_rematerialization=1 \\
        python scripts/remat_duplicates.py <tree root> <cell> [<cell> ...] 2> remat.log

compiles each cell's real train step from the tree at <tree root> for a
described v5e:2x2 as `benchmarks/tools/aot.py` does and prints the compiled
peak (the chip's `step_hbm_gb`, to the byte), the kernel count and every
instruction of the optimized HLO the pass cloned (`<name>.remat`), with its
shape and kind: a `kind=kOutput` fusion or a `convolution_*` is a matmul that
runs twice.  The variables in front make libtpu log, on stderr, the pass's own
limit for the cell and its own estimate of the step, which is NOT
`step_hbm_gb` (PR 64: Kimi's step read 14.47 GiB of a limit of 14.63 where
`step_hbm_gb` was 11.80 GiB): `grep -a hlo_rematerialization remat.log`.  Read
both before sizing a saved residual against the memory a cell has left."""
import json, os, re, sys
root, cells = sys.argv[1], sys.argv[2:]
sys.path.insert(0, root)
os.chdir(root)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from benchmarks import run as harness
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2", chip_config_name="default",
                                    chips_per_host_bounds=(2, 2, 1), num_slices=1)
CLONE = re.compile(r"^\s*%?[\w.\-]*\.remat[\w.]* = ")
for name in cells:
    cell, config, traffic = harness.load_cell(name)
    builder = harness.load_plugin("builders", config["kind"])
    seq, batch = traffic["seq_len"], traffic["seqs_per_chip"] * cell["chips"]
    _, ctx = builder.build(config, seq, topo.devices)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=ctx.batch_sharding)
    with ctx.mesh:
        compiled = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile()
    text = compiled.as_text()
    clones = [re.sub(r", (metadata|backend_config)=\{.*", "", line.strip()) for line in text.splitlines() if CLONE.match(line)]
    print(json.dumps({"cell": name, "peak_gb": compiled.memory_analysis().peak_memory_in_bytes / 1e9,
                      "tpu_custom_calls": text.count("tpu_custom_call"), "duplicated": len(clones)}), flush=True)
    for line in clones:
        print("  " + line[:300], flush=True)
    del compiled
