#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in a fresh process tree:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The path measured is the library call a user makes: `ray_tpu.init()` ->
`JaxTrainer(loop, ScalingConfig(num_workers=1, use_tpu=True,
chips_per_worker=<chips>), JaxConfig(platform="tpu")).fit()` ->
`ray_tpu.shutdown()`.  This driver process never imports JAX (a chip has one
owner, the `TrainWorker`); every number reaches it through `train.report`.

Driven by data: there is no table of cells, configurations, mixes or metrics
in this file.  A cell is an entry of `workloads` in BENCHMARK.json and names
`benchmarks/configs/<config>.json` and `benchmarks/traffic/<traffic>.json`;
the configuration's `kind` names `benchmarks/builders/<kind>.py`, the
traffic's `kind` names `benchmarks/loops/<kind>.py`, and every per-layer
metric is a reader `benchmarks/layer_metrics/<name>.py`.  A later PR adds
files and entries and edits none.

The last line of stdout is the contract's object (`correct`, `attempted`,
`failed`, `metrics`, `device`, and `breakdown` when traced) and nothing
else; the loss trajectory, step times, HLO facts and the set-up split go on
earlier lines and into `benchmarks/out/`.  No chip, too few chips, an
unknown `device_kind` or a failed run: non-zero exit and no result line.

`--rehearse` runs the same harness on the CPU at a toy size (the four-chip
cell on four virtual devices) to find wrong paths and arguments before chip
time is spent.  It prints no metric values on its last line: a rehearsal is
never a result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import signal
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # `benchmarks.*` and `ray_tpu` resolve from this checkout
OUT_DIR = os.path.join(BENCH_DIR, "out")
DEADLINE_S = 1150  # the contract gives a compiling first run 1200 s, a warm one 360 s

# What --rehearse overrides in a configuration (widths a CPU can step) and in
# a traffic file.  Only the rehearsal reads these.
REHEARSAL_CONFIG = {
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2,
    "num_key_value_heads": 1, "vocab_size": 512, "num_hidden_layers": 2,
}
REHEARSAL_SEQ = 256


# -- data: cells, configurations, mixes, plug-ins ------------------------------


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None):
    """(cell, configuration, traffic) of the workload `name`."""
    bench = bench or load_benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if config["train"]["chips"] != cell["chips"]:
        raise SystemExit(f"cell {name!r} asks {cell['chips']} chip(s), its configuration "
                         f"is laid out for {config['train']['chips']}")
    return cell, config, traffic


def load_plugin(group: str, name: str):
    """`benchmarks/<group>/<name>.py`, found by name."""
    return importlib.import_module(f"benchmarks.{group}.{name}")


def layer_metric_readers() -> Dict[str, Any]:
    """Every reader in benchmarks/layer_metrics/, by metric name."""
    names = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, "layer_metrics"))
                   if f.endswith(".py") and not f.startswith("_"))
    return {n: load_plugin("layer_metrics", n) for n in names}


def metrics_of_cell(entries: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


# -- the worker's entry ---------------------------------------------------------


def _worker_entry(plan: Dict[str, Any]) -> None:
    """Runs in the TrainWorker.  Pickled by value (see `run_cell`), so it
    needs nothing importable there but the checkout it is told about."""
    import importlib
    import sys

    if plan["root"] not in sys.path:
        sys.path.insert(0, plan["root"])
    importlib.import_module(f"benchmarks.loops.{plan['loop']}").run(plan)


# -- driver side: never touches jax ----------------------------------------------


def _live_processes() -> Dict[int, Tuple[int, str]]:
    """pid -> (ppid, command) of every process that is not a zombie."""
    table: Dict[int, Tuple[int, str]] = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        comm_end = stat.rindex(")")  # "pid (comm) state ppid ..."; comm may hold ")"
        state, ppid = stat[comm_end + 2:].split()[:2]
        if state != "Z":
            table[int(pid)] = (int(ppid), stat[stat.index("(") + 1: comm_end])
    return table


def _descendants(root: int) -> Dict[int, str]:
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
    try:
        names = sorted(n for n in os.listdir(log_dir) if n.endswith(".err"))
    except OSError as e:
        print(f"[bench] no worker logs: {e}")
        return
    for name in names:
        with open(os.path.join(log_dir, name), errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            print(f"[bench] ---- tail of {os.path.join(log_dir, name)}")
            sys.stdout.writelines(tail)


def run_cell(plan: Dict[str, Any]) -> Tuple[Optional[Any], List[str], Dict[str, float]]:
    """init -> fit -> shutdown.  Returns (Result or None, failures, driver clocks)."""
    import cloudpickle

    import ray_tpu
    from ray_tpu._private.runtime import get_runtime
    from ray_tpu.train import JaxConfig, JaxTrainer, ScalingConfig

    on_tpu = plan["platform"] == "tpu"
    pinned = os.environ.get("JAX_PLATFORMS")
    if on_tpu and pinned and "tpu" not in pinned.split(","):
        # JaxConfig(platform="tpu") would override it inside the worker; an
        # environment that says "no accelerator" gets no result instead.
        return None, [f"JAX_PLATFORMS={pinned} rules out the TPU"], {}
    if __name__ != "__main__":
        cloudpickle.register_pickle_by_value(sys.modules[__name__])
    clocks: Dict[str, float] = {}
    bad: List[str] = []
    result = None
    t0 = time.time()
    ray_tpu.init()
    clocks["init_s"] = time.time() - t0
    try:
        runtime = get_runtime()
        registered = int(ray_tpu.cluster_resources().get("TPU", 0))
        if on_tpu and registered < plan["chips"]:
            bad.append(f"the runtime found {registered} TPU chip(s) on this host; "
                       f"the cell needs {plan['chips']}")
        else:
            trainer = JaxTrainer(
                _worker_entry, train_loop_config=plan,
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=on_tpu,
                    chips_per_worker=plan["chips"] if on_tpu else 0),
                backend_config=JaxConfig(platform=plan["platform"]),
            )
            clocks["t_fit"] = time.time()
            try:
                result = trainer.fit()
            except Exception as e:  # noqa: BLE001: a boundary; reported below
                bad.append(f"fit() raised {type(e).__name__}: {e}")
            clocks["fit_s"] = time.time() - clocks["t_fit"]
            if result is not None and result.error is not None:
                # fit() RETURNS a failed run; it does not raise.
                bad.append(f"Result.error: {type(result.error).__name__}: {result.error}")
            if bad:
                _tail_worker_logs(runtime.log_dir)
    finally:
        # Listed while the zygote lives: the workers it forked are
        # re-parented when it dies and would drop out of this tree.
        started = _descendants(os.getpid())
        t0 = time.time()
        ray_tpu.shutdown()
        # Nothing this process started may outlive it: a leaked worker still
        # owns the chip.  A chip's owner may take a moment to die.
        while (left := started.keys() & _live_processes().keys()) and time.time() - t0 < 60.0:
            time.sleep(0.1)
        clocks["shutdown_s"] = time.time() - t0
        for pid in left:
            bad.append(f"process {pid} ({started[pid]}) alive after shutdown; killed")
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            bad.append("the driver process initialised a JAX backend")
    return result, bad, clocks


def by_phase(result) -> Dict[str, List[Dict[str, Any]]]:
    phases: Dict[str, List[Dict[str, Any]]] = {}
    for rep in (result.metrics_history or []) if result is not None else []:
        phases.setdefault(rep.get("phase"), []).append(rep)
    return phases


def check_correct(plan: Dict[str, Any], phases: Dict[str, List[Dict[str, Any]]]) -> List[str]:
    """Every reason the run's outputs are not correct (empty = correct)."""
    bad: List[str] = []
    setup, summary = phases["setup"][0], phases["summary"][0]
    traffic, on_tpu = plan["traffic"], plan["platform"] == "tpu"
    ref = setup["reference"]
    if not ref["ok"]:  # (a)
        bad.append(f"logits differ from the plain reference: rel rms error "
                   f"{ref['rel_rms_error']} > tolerance {ref['tolerance']}")
    losses = summary["losses"]
    if not all(math.isfinite(l) for l in losses):  # (b)
        bad.append("a loss was not finite")
    elif len(losses) < 10:
        bad.append(f"only {len(losses)} steps: too few to judge the loss")
    else:  # (c)
        first, last = statistics.fmean(losses[:5]), statistics.fmean(losses[-5:])
        if not last < first - traffic["loss_margin"]:
            bad.append(f"loss fell from {first:.4f} (first 5) to {last:.4f} (last 5), "
                       f"less than the margin {traffic['loss_margin']}")
    device = phases["device"][0]["device"]  # (d)
    if device["platform"] != plan["platform"] or device["count"] != plan["chips"]:
        bad.append(f"device {device}, the cell needs {plan['chips']} x {plan['platform']}")
    kernels = summary["facts"]["tpu_custom_calls"]
    if on_tpu and kernels < 1:
        bad.append("the compiled step holds no Mosaic kernel (tpu_custom_call)")
    if on_tpu:
        holders = {int(k) for k in summary["chip_holders"]}
        if holders != {summary["pid"]}:
            bad.append(f"chip held by pids {sorted(holders)}, expected only the "
                       f"TrainWorker {summary['pid']}")
    if summary["compiles_in_window"]:
        bad.append(f"{summary['compiles_in_window']} program(s) lowered or compiled inside the window")
    entries = summary["compile_cache"]["entries"]
    if entries[2] != entries[1]:
        bad.append(f"compile cache grew inside the window: {entries[1]} -> {entries[2]} entries")
    return bad


def step_seconds(summary: Dict[str, Any]) -> List[float]:
    """Host-clock seconds of each step of the window, loss fetch to loss fetch."""
    ends = summary["step_ends"]
    return [b - a for a, b in zip([0.0] + ends, ends)]


def end_to_end(plan: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, float]:
    """The end-to-end metrics, from the worker's window and the driver's clock.

    Throughput is tokens per step over the MEDIAN step time of the window,
    not steps over elapsed time: one run in twelve on the chip machine had a
    single 3.2 s step among 1.54 s ones (a 5% loss by elapsed time, nothing
    the program did), and a metric that swings with it could not hold a 1%
    bound.  What the median hides is reported as `window_stall_pct`.

    `setup_s` is the set-up inside the worker, from the first line of the
    loop to the window's first step: `init_state`, the reference check, the
    compile step, warm-up, the HLO facts.  It leaves out the runtime's own
    start (`driver_init_s`, `fit_to_loop_s`: `ray_tpu.init()`, the worker's
    spawn, `import jax`, libtpu opening the chip): those 10-28 s vary by
    +-3 s from run to run on the same code (measured, PR 22), so that the
    medians of two sets of six runs of the WHOLE set-up differed by 9.9% on
    one chip and 6.7% on four, against a bound of 10%.  What is kept repeats
    within 1.4% between sets.  The two are reported as per-layer metrics."""
    summary = run["summary"]
    tokens_per_s_per_chip = (summary["tokens_per_step"] / statistics.median(step_seconds(summary))
                             / plan["chips"])
    out = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
           "setup_s": run["setup"]["t_window"] - run["start"]["t_loop"]}
    if plan["platform"] == "tpu":
        from benchmarks.lib import flops

        builder = load_plugin("builders", plan["config"]["kind"])
        needed = builder.needed_flops_per_token(plan["config"], plan["traffic"]["seq_len"])
        peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
        out["mfu_pct"] = 100.0 * tokens_per_s_per_chip * needed / peak
    return out


def main(argv: Optional[List[str]] = None) -> int:
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None, help="default: BENCHMARK.json run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy size, no metric values: finds faults, measures nothing")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell, config, traffic = load_cell(args.workload, bench)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    if args.rehearse:
        config = dict(config, **REHEARSAL_CONFIG)
        traffic = dict(traffic, seq_len=min(traffic["seq_len"], REHEARSAL_SEQ),
                       seqs_per_chip=min(traffic["seqs_per_chip"], 2))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={cell['chips']}").strip()
    tag = f"{cell['name']}.seed{args.seed}.trace{args.trace}" + (".rehearsal" if args.rehearse else "")
    plan = {
        "root": ROOT, "loop": traffic["kind"], "cell": cell["name"], "chips": cell["chips"],
        "platform": "cpu" if args.rehearse else "tpu", "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
        "trace_dir": os.path.join(OUT_DIR, "traces", tag),
    }
    load_plugin("builders", config["kind"]).model_kwargs(config, traffic["seq_len"])  # refuse early
    os.makedirs(OUT_DIR, exist_ok=True)

    signal.signal(signal.SIGALRM, _on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        result, bad, clocks = run_cell(plan)
    finally:
        signal.alarm(0)
    phases = by_phase(result)
    for phase in ("start", "device", "setup", "summary"):
        if not bad and phase not in phases:
            bad.append(f"the worker never reported its {phase!r}")
    if bad:
        for reason in bad:
            print(f"[bench] FAILED: {reason}")
        return 1

    run = {
        "plan": {k: v for k, v in plan.items() if k not in ("config", "traffic")},
        "cell": cell, "config": config, "traffic": traffic, "clocks": clocks,
        "start": phases["start"][0], "device": phases["device"][0]["device"],
        "setup": phases["setup"][0], "summary": phases["summary"][0],
        "trace": phases["summary"][0].get("trace"),
    }
    summary = run["summary"]
    not_correct = check_correct(plan, phases)
    values = end_to_end(plan, run) if not args.trace else {}
    readers = layer_metric_readers() if args.trace else {}
    declared = metrics_of_cell(bench["per_layer" if args.trace else "end_to_end"], cell["name"])
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in declared:
        if args.trace:
            reader = readers.get(m["name"])
            value = reader.read(run) if reader is not None else None
        else:
            value = values.get(m["name"])
        if value is not None:  # a reader with nothing to read returns nothing
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(run["device"])
    device["memory_peak_bytes"] = max((p or 0) for p in summary["peak_bytes_in_use"])
    line: Dict[str, Any] = {
        "correct": not not_correct, "attempted": summary["attempted"], "failed": summary["failed"],
        "metrics": metrics, "device": device,
    }
    trace = run["trace"]
    if args.trace and trace is not None:
        device["busy_s"] = statistics.fmean(d["busy_s"] for d in trace["devices"])
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}

    step_s = step_seconds(summary)
    setup = run["setup"]
    print("[bench] cell " + json.dumps({"cell": cell["name"], "seed": args.seed, "seconds": seconds,
                                       "trace": args.trace, "rehearsal": args.rehearse}))
    print("[bench] setup split " + json.dumps({
        "driver_init_s": clocks["init_s"], "fit_to_loop_s": run["start"]["t_loop"] - clocks["t_fit"],
        **{k: setup[k] for k in ("init_state_s", "reference_s", "first_step_s", "warmup_s",
                                 "inspect_s")},
        "shutdown_s": clocks["shutdown_s"], "total_s": time.time() - t_start}))
    print("[bench] reference " + json.dumps(setup["reference"]))
    print("[bench] window " + json.dumps({
        "window_s": summary["window_s"], "steps": summary["steps_completed"],
        "tokens_per_step": summary["tokens_per_step"],
        "step_s_median": statistics.median(step_s) if step_s else None,
        "step_s_min_max": [min(step_s), max(step_s)] if step_s else None,
        "compiles_in_window": summary["compiles_in_window"],
        "compile_cache": summary["compile_cache"], "max_rss_mb": summary["max_rss_mb"]}))
    print("[bench] losses " + json.dumps([round(l, 4) for l in summary["losses"]]))
    print("[bench] facts " + json.dumps(summary["facts"]))
    if trace is not None:
        print("[bench] trace " + json.dumps({k: v for k, v in trace.items() if k != "host_span_s"}))
    for reason in not_correct:
        print(f"[bench] NOT CORRECT: {reason}")
    with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
        json.dump({"line": line, "run": run, "values": values}, f, indent=1)

    if args.rehearse:
        print("[bench] rehearsal values (CPU, toy size: NOT measurements) " + json.dumps(metrics))
        print(json.dumps({"rehearsal": True, "correct": line["correct"], "attempted": line["attempted"],
                          "failed": line["failed"], "metric_names": sorted(metrics),
                          "device": run["device"]}))
        return 0 if line["correct"] else 1
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


def _on_deadline(signum, frame):
    raise TimeoutError(f"benchmarks/run.py exceeded {DEADLINE_S} s")


if __name__ == "__main__":
    sys.exit(main())
