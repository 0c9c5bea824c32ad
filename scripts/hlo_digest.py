#!/usr/bin/env python3
"""Is a cell's compiled step still the parent's?  Here, with no chip:

    python scripts/hlo_digest.py <tree root> <out.json> <cell> [<cell> ...]

compiles each cell's real train step from the tree at <tree root> (a checkout,
or `git archive <parent> | tar -x -C _chip/parent`) for a described v5e:2x2 as
`benchmarks/tools/aot.py` does, and digests the optimized HLO after dropping
what moves with every edit of a traced file: `metadata={...}`, the
`FileNames` / `FunctionNames` / `FileLocations` / `StackFrames` tables,
`stack_frame_id=`, `%region` numbers, and the bodies of the `tpu_custom_call`s
(Mosaic serialises debug locations into each kernel).  Two trees whose lines,
kernels, `peak` and both digests agree in a cell run the same program there
(.claude/skills/verify/SKILL.md item 3).  All nine accepted cells take ~7 min
a tree in one process; run the two trees one after the other."""
import hashlib, json, os, re, sys, time
root, out_path, cells = sys.argv[1], sys.argv[2], sys.argv[3:]
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
META = re.compile(r", metadata=\{[^}]*\}")
FRAME = re.compile(r",? ?stack_frame_id=\d+")
def strip(text):
    keep = []
    skipping = False
    for line in text.splitlines():
        s = line.strip()
        if s.startswith(("FileNames", "FunctionNames", "FileLocations", "StackFrames")):
            skipping = True
            continue
        if skipping:
            if re.match(r"^\d+ ", s) or s == "" or s.startswith("{") or s.startswith("}"):
                continue
            skipping = False
        line = FRAME.sub("", META.sub("", line))
        keep.append(line)
    return keep
result = {}
for name in cells:
    cell, config, traffic = harness.load_cell(name)
    builder = harness.load_plugin("builders", config["kind"])
    seq, batch = traffic["seq_len"], traffic["seqs_per_chip"] * cell["chips"]
    t0 = time.time()
    _, ctx = builder.build(config, seq, topo.devices)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=ctx.batch_sharding)
    with ctx.mesh:
        compiled = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile()
    lines = strip(compiled.as_text())
    plain = [re.sub(r"%region_\d+\.\d+|region_\d+\.\d+", "region", l) for l in lines if "tpu_custom_call" not in l]
    kernels = [l for l in lines if "tpu_custom_call" in l]
    mem = compiled.memory_analysis()
    result[name] = {"lines": len(lines), "kernels": len(kernels), "peak": mem.peak_memory_in_bytes,
                    "digest_without_kernels": hashlib.sha256("\n".join(plain).encode()).hexdigest()[:16],
                    "digest_kernel_heads": hashlib.sha256("\n".join(re.sub(r"backend_config=.*", "", k) for k in kernels).encode()).hexdigest()[:16],
                    "s": round(time.time() - t0, 1)}
    with open(os.path.join(os.path.dirname(out_path), os.path.basename(out_path) + "." + name + ".txt"), "w") as f:
        f.write("\n".join(plain))
    print(name, result[name], flush=True)
    del compiled
json.dump(result, open(out_path, "w"), indent=1)
