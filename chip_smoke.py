#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, the way a user would: `ray_tpu.init()`, then
`JaxTrainer.fit()` with ONE train worker that owns the host's chips.  Inside
the worker the loop builds a 0.94B decoder at its full width (d_model
2048, 16 layers, 16 heads of 128, d_ff 5504, vocab 32000, bf16 params,
remat_policy "qkv_attn"; weights random from a seed), an `LMTrainContext` on a
mesh of the worker's chips, and takes a compile step plus a few steady steps on
a seeded HOST batch of 16 x 1024 tokens per chip, calling `train.report` every
step.

    python chip_smoke.py                       one chip, MeshSpec(data=1), "dp"
    python chip_smoke.py --chips 4             one worker, fsdp=4, 64 x 1024 tokens
    python chip_smoke.py --chips 4 --aot v5e:2x2
        no chip needed: compile the same step against a deviceless topology
        and run the same checks on its HLO (a pre-flight before chip time)

A chip belongs to one process at a time, so this driver process never touches
JAX: everything it knows about the device the worker told it through
`train.report`.  The run fails (non-zero exit, the reasons on the last lines
of stdout, the tail of the workers' stderr logs above them) unless the worker
saw the expected platform, `Result.error` is None, every loss is finite and the
last is below the first, the compiled step holds the Mosaic kernels, the chip
was held by the train worker alone, the compile cache sat where it should, and
after `ray_tpu.shutdown()` no child of this process is left alive.

On success the last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1100  # the contract allows 1200 s, compilation included

# The dtype is a name here and becomes a jnp dtype inside the worker: the
# driver builds nothing from jax.
BENCH_MODEL: Dict[str, Any] = dict(
    vocab_size=32000, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=16,
    d_ff=5504, max_seq_len=1024, param_dtype="bfloat16", remat=True,
    remat_policy="qkv_attn",
)
BATCH_PER_CHIP = 16
STEADY_STEPS = 6  # after the compile step; the contract wants at least 5


def make_plan(
    chips: int = 1,
    platform: str = "tpu",
    model: Optional[Dict[str, Any]] = None,
    batch_per_chip: int = BATCH_PER_CHIP,
) -> Dict[str, Any]:
    """Everything the loop needs, as plain data (it crosses a pickle)."""
    model = dict(model or BENCH_MODEL)
    return {
        "platform": platform,
        "chips": chips,
        "model": model,
        # One chip is plain data parallel of size 1; more chips shard the
        # parameters (ZeRO-3) so the same tokens per chip fit.
        "mesh": {"data": 1} if chips == 1 else {"data": 1, "fsdp": chips},
        "strategy": "dp" if chips == 1 else "fsdp",
        "batch": batch_per_chip * chips,
        "seq": model["max_seq_len"],
        "steps": 1 + STEADY_STEPS,
    }


# -- worker side -------------------------------------------------------------


def build_context(plan: Dict[str, Any], devices):
    """The (config, mesh, LMTrainContext) the plan describes on `devices`."""
    import jax.numpy as jnp

    from ray_tpu.models import LMTrainContext, TransformerConfig
    from ray_tpu.parallel import MeshSpec, build_mesh

    model = dict(plan["model"])
    for key in ("dtype", "param_dtype"):
        if key in model:
            model[key] = jnp.dtype(model[key])
    cfg = TransformerConfig(**model)
    mesh = build_mesh(MeshSpec(**plan["mesh"]), devices=devices[: plan["chips"]])
    return cfg, LMTrainContext(cfg, mesh=mesh, strategy=plan["strategy"])


_KERNEL_OPERAND = re.compile(r"operand_layout_constraints=\{\w+\[(\d+),")
_COLLECTIVES = (
    "all-gather", "reduce-scatter", "all-reduce", "collective-permute",
    "all-to-all",
)


def hlo_facts(text: str) -> Dict[str, Any]:
    """What the optimized HLO of the step says about kernels and collectives.

    `kernel_batch` is dim 0 of each Mosaic call's first operand, which in the
    kernels' [B, H, S, D] layout is the batch one device works on."""
    kernel_lines = [l for l in text.splitlines() if "tpu_custom_call" in l]
    batches = []
    for line in kernel_lines:
        m = _KERNEL_OPERAND.search(line)
        batches.append(int(m.group(1)) if m else None)
    return {
        "tpu_custom_calls": len(kernel_lines),
        "kernel_batch": batches,
        "collectives": {
            op: len(re.findall(rf" {op}(?:-start)?\(", text)) for op in _COLLECTIVES
        },
    }


def chip_holders() -> Dict[int, str]:
    """pid -> command of every process with an accelerator device file open
    (/dev/accel<N> on older TPU VMs, /dev/vfio/<group> on v5e)."""
    holders: Dict[int, str] = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if re.match(r"/dev/(accel\d|vfio/\d)", target):
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
                except OSError:
                    cmd = "?"
                holders[int(pid)] = cmd.strip()[:120]
                break
    return holders


def train_loop(plan: Dict[str, Any]) -> None:
    """Runs in the TrainWorker, the one process that opens the chip."""
    import resource

    import jax
    import numpy as np

    from ray_tpu import train

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    cache_dir = jax.config.jax_compilation_cache_dir

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    train.report({
        "phase": "device", "device": device, "pid": os.getpid(),
        "worker_id": os.environ.get("RAY_TPU_WORKER_ID"),
    })
    if device["platform"] != plan["platform"]:
        raise RuntimeError(
            f"worker expected platform {plan['platform']!r}, jax gave {device}"
        )

    cfg, ctx = build_context(plan, devices)
    t0 = time.perf_counter()
    state = ctx.init_state(seed=0)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0

    rng = np.random.default_rng(1)
    toks = rng.integers(
        0, cfg.vocab_size, (plan["batch"], plan["seq"] + 1), dtype=np.int32
    )
    # A HOST batch, the same one every step: make_batch is on the path, and
    # a model that learns anything drives the loss on it down.
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    holders: Dict[int, str] = {}
    for step in range(plan["steps"]):
        t0 = time.perf_counter()
        state, metrics = ctx.train_step(state, batch)
        loss = float(metrics["loss"])  # the host fetch is the sync
        step_s = time.perf_counter() - t0
        if step == 2:
            holders = chip_holders()
        train.report({"phase": "step", "step": step, "loss": loss, "step_s": step_s})

    # The step as compiled for this device: a second lower+compile of what
    # train_step just ran (a hit in the persistent cache when there is one).
    t0 = time.perf_counter()
    with ctx.mesh:
        compiled = ctx._train_step.lower(state, ctx.make_batch(batch)).compile()
    inspect_s = time.perf_counter() - t0
    facts = hlo_facts(compiled.as_text())
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in ctx.mesh.devices.flat
    ]
    train.report({
        "phase": "summary",
        **facts,
        "peak_bytes_in_use": peaks,
        "init_s": init_s,
        "inspect_compile_s": inspect_s,
        "chip_holders": holders,
        "max_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": [entries_before, cache_entries()],
    })


# -- driver side: never touches jax ------------------------------------------


def check(result, plan: Dict[str, Any]) -> List[str]:
    """Every reason this run is not a pass (empty list = pass)."""
    bad: List[str] = []
    if result.error is not None:
        # fit() RETURNS a failed run; it does not raise.
        bad.append(f"Result.error: {type(result.error).__name__}: {result.error}")
    history = result.metrics_history or []
    by_phase: Dict[str, List[Dict]] = {}
    for rep in history:
        by_phase.setdefault(rep.get("phase"), []).append(rep)

    dev = (by_phase.get("device") or [{}])[0].get("device")
    if dev is None:
        return bad + ["the worker never reported its device"]
    if dev["platform"] != plan["platform"]:
        bad.append(f"platform is {dev['platform']!r}, expected {plan['platform']!r}")
    if dev["count"] < plan["chips"]:
        bad.append(f"{dev['count']} devices, need {plan['chips']}")

    losses = [r["loss"] for r in by_phase.get("step", [])]
    if len(losses) < plan["steps"]:
        bad.append(f"{len(losses)} steps reported, expected {plan['steps']}")
    if not all(math.isfinite(l) for l in losses):
        bad.append(f"non-finite loss in {losses}")
    elif len(losses) >= 2 and not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: first {losses[0]} last {losses[-1]}")

    summary = (by_phase.get("summary") or [None])[0]
    if summary is None:
        return bad + ["the worker never reported its summary"]
    on_tpu = plan["platform"] == "tpu"
    kernels = summary["tpu_custom_calls"]
    if on_tpu and kernels < 3:
        # forward + two backward kernels; fewer means a quiet dispatch to the
        # XLA attention forms
        bad.append(f"{kernels} tpu_custom_call in the compiled step, need >= 3")
    if not on_tpu and kernels:
        bad.append(f"{kernels} tpu_custom_call in a {plan['platform']} step")
    if on_tpu:
        want = plan["batch"] // plan["chips"]
        if any(b != want for b in summary["kernel_batch"]):
            bad.append(
                f"kernel per-device batch {summary['kernel_batch']}, expected "
                f"{want} (global {plan['batch']} over {plan['chips']} chips)"
            )
        pid = by_phase["device"][0]["pid"]
        holders = {int(k): v for k, v in summary["chip_holders"].items()}
        if set(holders) != {pid}:
            bad.append(
                f"chip device files held by {holders}, expected only the "
                f"train worker pid {pid}"
            )
        peaks = summary["peak_bytes_in_use"]
        if None in peaks or min(peaks) <= 0:
            bad.append(f"peak_bytes_in_use not reported: {peaks}")
        elif max(peaks) > 2 * min(peaks):
            bad.append(f"peak_bytes_in_use differs across devices: {peaks}")
    if on_tpu and plan["chips"] > 1:
        coll = summary["collectives"]
        # ZeRO-3: parameters are gathered and gradients reduced across the
        # chips.  XLA:TPU writes the reduction as reduce-scatter at small
        # widths and as windowed einsums (collective-permute rings) at
        # bench width, so either counts.
        if not coll["all-gather"] or not (
            coll["reduce-scatter"] + coll["collective-permute"]
        ):
            bad.append(f"sharded step lacks its collectives: {coll}")
    want_dir = os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO_ROOT, ".jax_cache")
    )
    if summary["compile_cache_dir"] != want_dir:
        bad.append(
            f"compile cache at {summary['compile_cache_dir']!r}, expected {want_dir!r}"
        )
    return bad


def observations(result, plan: Dict[str, Any]) -> Dict[str, Any]:
    """One run's numbers, as observations (never a claim)."""
    history = result.metrics_history or []
    steps = [r for r in history if r.get("phase") == "step"]
    summary = next((r for r in history if r.get("phase") == "summary"), {})
    steady = [r["step_s"] for r in steps[1:]]
    return {
        "chips": plan["chips"],
        "strategy": plan["strategy"],
        "tokens_per_step": plan["batch"] * plan["seq"],
        "first_step_s": round(steps[0]["step_s"], 3) if steps else None,
        "steady_step_s_median": round(statistics.median(steady), 4) if steady else None,
        "losses": [round(r["loss"], 4) for r in steps],
        **{k: v for k, v in summary.items() if k != "phase"},
    }


def _live_processes() -> Dict[int, tuple]:
    """pid -> (ppid, command) of every process that is not a zombie (one
    that exited and only waits to be reaped holds no chip)."""
    table: Dict[int, tuple] = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        # "pid (comm) state ppid ..."; comm may hold spaces and parentheses
        comm_end = stat.rindex(")")
        state, ppid = stat[comm_end + 2:].split()[:2]
        if state != "Z":
            table[int(pid)] = (int(ppid), stat[stat.index("(") + 1: comm_end])
    return table


def _descendants(root: int) -> Dict[int, str]:
    """Live processes below `root` in the process tree: pid -> command."""
    table = _live_processes()
    out: Dict[int, str] = {}
    frontier = [root]
    while frontier:
        p = frontier.pop()
        for pid, (ppid, comm) in table.items():
            if ppid == p and pid not in out:
                out[pid] = comm
                frontier.append(pid)
    return out


def _tail_worker_logs(log_dir: str, lines: int = 60) -> None:
    """A dead worker's libtpu error is in its stderr log, and the chip tool
    shows only the end of this process's own output."""
    try:
        names = sorted(n for n in os.listdir(log_dir) if n.endswith(".err"))
    except OSError as e:
        print(f"[smoke] no worker logs: {e}")
        return
    for name in names:
        path = os.path.join(log_dir, name)
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            print(f"[smoke] ---- tail of {path}")
            sys.stdout.writelines(tail)


def _fit(plan: Dict[str, Any]):
    """JaxTrainer.fit on a live runtime, and the checks on what came back.
    Returns (result-or-None, observations, failures)."""
    import ray_tpu
    from ray_tpu._private.runtime import get_runtime
    from ray_tpu.train import JaxConfig, JaxTrainer, ScalingConfig

    on_tpu = plan["platform"] == "tpu"
    runtime = get_runtime()
    obs: Dict[str, Any] = {
        "object_store": "native_arena" if runtime.store.shm.arena is not None else "files"
    }
    pinned = os.environ.get("JAX_PLATFORMS")
    if on_tpu and pinned and "tpu" not in pinned.split(","):
        # JaxConfig(platform="tpu") would override it inside the worker; an
        # environment that says "no accelerator" gets no result instead.
        return None, obs, [f"JAX_PLATFORMS={pinned} rules out the TPU"]
    registered = int(ray_tpu.cluster_resources().get("TPU", 0))
    if on_tpu and registered < plan["chips"]:
        # Without the resource the worker is unplaceable and the first sign
        # would be a 60 s timeout out of the backend's on_start.
        return None, obs, [
            f"the runtime found {registered} TPU chip(s) on this host "
            f"(/dev/accel*, /dev/vfio/<group>); the smoke needs {plan['chips']}"
        ]
    trainer = JaxTrainer(
        train_loop,
        train_loop_config=plan,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_tpu,
            chips_per_worker=plan["chips"] if on_tpu else 0,
        ),
        backend_config=JaxConfig(platform=plan["platform"]),
    )
    try:
        result = trainer.fit()
    except Exception as e:  # noqa: BLE001: a boundary; reported below
        result, bad = None, [f"fit() raised {type(e).__name__}: {e}"]
    else:
        bad = check(result, plan)
        obs.update(observations(result, plan))
    if bad:
        _tail_worker_logs(runtime.log_dir)
    return result, obs, bad


def run(plan: Dict[str, Any]):
    """The smoke itself.  Returns (result-or-None, observations, failures)."""
    import ray_tpu

    ray_tpu.init()
    try:
        result, obs, bad = _fit(plan)
    finally:
        # Listed while the zygote lives: the workers it forked are
        # re-parented when it dies and would drop out of this tree.
        started = _descendants(os.getpid())
        ray_tpu.shutdown()

    # A leaked worker still owns the chip, and the next command on this
    # machine then fails: nothing this process started may outlive shutdown.
    # A process that held the chip may take a moment to die, so wait for
    # it, bounded.
    t0 = time.monotonic()
    while (left := started.keys() & _live_processes().keys()) and (
        time.monotonic() - t0 < 60.0
    ):
        time.sleep(0.2)
    obs["exit_wait_s"] = round(time.monotonic() - t0, 1)
    if left:
        bad.append(
            "processes alive after ray_tpu.shutdown(): "
            f"{ {pid: started[pid] for pid in left} }"
        )
        for pid in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass

    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            bad.append("the driver process initialised a JAX backend")
    return result, obs, bad


def aot_preflight(plan: Dict[str, Any], topology: str) -> int:
    """Compile the plan's step for TPU against a deviceless topology, in
    this process, executing nothing.  libtpu enforces HBM here, so a plan
    that cannot fit is refused before any chip time is spent."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu._private import compile_cache

    compile_cache.apply_default()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    bounds = tuple(int(x) for x in topology.partition(":")[2].split("x"))
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology, chip_config_name="default",
        chips_per_host_bounds=bounds + (1,) * (3 - len(bounds)), num_slices=1,
    )
    _, ctx = build_context(plan, topo.devices)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct(
        (plan["batch"], plan["seq"]), jnp.int32, sharding=ctx.batch_sharding
    )
    t0 = time.perf_counter()
    with ctx.mesh:
        compiled = ctx._train_step.lower(
            state, {"tokens": toks, "targets": toks}
        ).compile()
    facts = hlo_facts(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({
        "aot": topology, "device_kind": topo.devices[0].device_kind,
        "chips": plan["chips"], "compile_s": round(time.perf_counter() - t0, 1),
        **facts,
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 2),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 2),
    }))
    want = plan["batch"] // plan["chips"]
    ok = facts["tpu_custom_calls"] >= 3 and all(
        b == want for b in facts["kernel_batch"]
    )
    return 0 if ok else 1


def _on_deadline(signum, frame):
    raise TimeoutError(f"chip_smoke exceeded {DEADLINE_S} s")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="chips the one train worker owns (default 1)")
    ap.add_argument("--aot", metavar="TOPOLOGY",
                    help="compile only, against e.g. v5e:2x2; needs no chip")
    args = ap.parse_args(argv)
    plan = make_plan(chips=args.chips)
    if args.aot:
        return aot_preflight(plan, args.aot)

    signal.signal(signal.SIGALRM, _on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        result, obs, bad = run(plan)
    finally:
        signal.alarm(0)
    print("[smoke] observations " + json.dumps(obs))
    if bad:
        for reason in bad:
            print(f"[smoke] FAILED: {reason}")
        return 1
    device = result.metrics_history[0]["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
