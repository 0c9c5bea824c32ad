"""kind = "train_steps": the loop that runs inside the one `TrainWorker`, the
only process that opens the chip.  Everything the driver learns it learns
through `train.report`.

Per step, as a user's loop would: draw a NEW host batch from the seeded
stream, `ctx.train_step` (so `make_batch` is on the path), fetch the loss
(the sync), `train.report`.  Set-up warms exactly the cell's one shape
(compile step + `warmup_steps`), checks the program's logits against the
plain reference on the chip, and reads the compiled step's HLO facts; then
steps are started for `seconds` seconds.  With `trace` on, `trace_steps`
consecutive steps in the middle of the window run under `jax.profiler`, the
host spans below written as `TraceAnnotation`s so they sit on the device
trace's clock, and the trace is reduced here (only the chip's owner can).
"""

from __future__ import annotations

import glob
import math
import os
import re
import resource
import shutil
import time
from typing import Any, Dict, List

HOST_SPANS = ("data_next", "make_batch+dispatch", "loss_fetch", "report")
STEP_SPAN = "bench_step"
_COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute", "all-to-all")
_KERNEL_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target=\"tpu_custom_call\"", re.M)


def hlo_facts(text: str) -> Dict[str, Any]:
    """Kernels and collectives in the optimized HLO of the compiled step."""
    kernel_ops = _KERNEL_NAME.findall(text)
    return {
        "tpu_custom_calls": len(kernel_ops),
        "kernel_ops": kernel_ops,
        "collectives": {op: len(re.findall(rf" {op}(?:-start)?\(", text)) for op in _COLLECTIVES},
    }


def chip_holders() -> Dict[int, str]:
    """pid -> command of every process with a chip's device file open
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


class _CompileCounter:
    """Counts programs lowered or compiled, through jax.monitoring: the
    window must see none."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1


def _reference_check(plan, builder, ctx, params, n_devices: int) -> Dict[str, Any]:
    """Program logits (ctx.apply, on the chip, seeded weights) against the
    plain reference on `reference_seqs` seeded sequences: all positions up to
    1024, the last 256 query positions of longer sequences."""
    import numpy as np

    from benchmarks.lib import datagen, reference

    config, traffic = plan["config"], plan["traffic"]
    seq, n_ref = traffic["seq_len"], traffic["reference_seqs"]
    last = seq if seq <= 1024 else 256
    group = n_devices  # apply takes a batch the mesh's batch axes divide
    stream = datagen.PackedStream(plan["seed"] + 1_000_003, config["vocab_size"], traffic["stream"])
    tokens = stream.next_batch(group * math.ceil(n_ref / group), seq)["tokens"]
    want = builder.reference_logits(config, params, tokens[:n_ref], last)
    errors: List[float] = []
    for g in range(0, n_ref, group):
        got = ctx.apply(params, tokens[g: g + group])
        for i in range(g, min(g + group, n_ref)):
            errors.append(reference.rel_rms_error(got[i - g, -last:], want[i]))
        del got
    tol = reference.tolerance(config["num_hidden_layers"])
    return {"rel_rms_error": errors, "tolerance": tol, "positions": last, "seqs": n_ref,
            "ok": bool(np.all(np.isfinite(errors)) and max(errors) <= tol)}


def run(plan: Dict[str, Any]) -> None:
    t_loop = time.time()
    from ray_tpu import train

    train.report({"phase": "start", "t_loop": t_loop, "pid": os.getpid()})
    # `JaxConfig`'s on_start has already imported jax and opened the chip in
    # this process: both cost nothing here and sit inside `fit_to_loop_s`.
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks import run as harness
    from benchmarks.lib import datagen, flops, trace_reduce

    # Small programs (the reference's blocks, apply) are cached too, so that
    # only the first run in a checkout compiles anything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    train.report({"phase": "device", "device": device})
    if device["platform"] != plan["platform"] or device["count"] != plan["chips"]:
        raise RuntimeError(f"the cell needs {plan['chips']} {plan['platform']} device(s), jax gave {device}")
    if device["platform"] == "tpu":
        flops.load_peaks(device["kind"])  # a kind without peaks on record is an error, now

    config, traffic = plan["config"], plan["traffic"]
    builder = harness.load_plugin("builders", config["kind"])
    cache_dir = jax.config.jax_compilation_cache_dir

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0

    entries_at_start = cache_entries()
    compiles = _CompileCounter()
    seq, batch = traffic["seq_len"], traffic["seqs_per_chip"] * plan["chips"]
    cfg, ctx = builder.build(config, seq, devices)

    t0 = time.perf_counter()
    state = ctx.init_state(seed=plan["seed"])
    jax.block_until_ready(state)
    init_state_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref = _reference_check(plan, builder, ctx, state["params"], len(devices))
    reference_s = time.perf_counter() - t0

    stream = datagen.PackedStream(plan["seed"], config["vocab_size"], traffic["stream"])
    losses: List[float] = []

    def step(record=None):
        """One step as the user's loop runs it; `record` collects the host
        spans' seconds."""
        nonlocal state
        with TraceAnnotation(STEP_SPAN):
            marks = [time.perf_counter()]
            with TraceAnnotation(HOST_SPANS[0]):
                host_batch = stream.next_batch(batch, seq)
            marks.append(time.perf_counter())
            with TraceAnnotation(HOST_SPANS[1]):
                state, metrics = ctx.train_step(state, host_batch)
            marks.append(time.perf_counter())
            with TraceAnnotation(HOST_SPANS[2]):
                loss = float(metrics["loss"])  # the host fetch is the sync
            marks.append(time.perf_counter())
            with TraceAnnotation(HOST_SPANS[3]):
                train.report({"phase": "step", "step": len(losses), "loss": loss})
            marks.append(time.perf_counter())
        losses.append(loss)
        if record is not None:
            record.append([b - a for a, b in zip(marks, marks[1:])])
        return loss

    t0 = time.perf_counter()
    step()
    first_step_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(traffic["warmup_steps"]):
        step()
    warmup_s = time.perf_counter() - t0
    holders = chip_holders()

    # The step as compiled for this device: a second lower+compile of what
    # train_step just ran, a hit in the persistent cache.
    t0 = time.perf_counter()
    zeros = np.zeros((batch, seq), np.int32)  # only the shape is used
    shaped = ctx.make_batch({"tokens": zeros, "targets": zeros})
    with ctx.mesh:
        compiled = ctx._train_step.lower(state, shaped).compile()
    facts = hlo_facts(compiled.as_text())
    mem = compiled.memory_analysis()
    # The buffer assignment's high-water mark, arguments included.  (The sum
    # arguments + outputs + temporaries - aliased reads 18.7 GB for a step
    # that compiles into 15.75 GiB: temporaries are not all live at once.)
    facts["step_hbm_bytes"] = mem.peak_memory_in_bytes
    facts["memory_analysis"] = {k: getattr(mem, f"{k}_size_in_bytes")
                                for k in ("argument", "output", "temp", "alias")}
    del compiled, shaped
    inspect_s = time.perf_counter() - t0

    entries_before = cache_entries()
    compiles_before = compiles.count
    train.report({
        "phase": "setup", "init_state_s": init_state_s, "reference_s": reference_s, "first_step_s": first_step_s,
        "warmup_s": warmup_s, "inspect_s": inspect_s, "reference": ref, "t_window": time.time(),
    })

    # -- the window ---------------------------------------------------------
    seconds, trace_steps = plan["seconds"], traffic["trace_steps"] if plan["trace"] else 0
    trace_dir = plan["trace_dir"]
    spans: List[List[float]] = []
    step_ends: List[float] = []
    failed = attempted = 0
    tracing_left, traced_from = 0, None
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < seconds:
        if trace_steps and traced_from is None and time.perf_counter() - w0 >= 0.4 * seconds:
            shutil.rmtree(trace_dir, ignore_errors=True)  # one trace per tag, the newest
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans come from TraceAnnotation alone
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing_left, traced_from = trace_steps, len(spans)
        attempted += 1
        try:
            if not math.isfinite(step(spans)):
                failed += 1
        except Exception as e:  # noqa: BLE001: counted, and the run is then not correct
            failed += 1
            train.report({"phase": "step_error", "error": f"{type(e).__name__}: {e}"[:500]})
            break
        step_ends.append(time.perf_counter() - w0)
        if tracing_left:
            tracing_left -= 1
            if not tracing_left:
                jax.profiler.stop_trace()
    if tracing_left:
        jax.profiler.stop_trace()
    window_s = step_ends[-1] if step_ends else time.perf_counter() - w0

    reduced = None
    if traced_from is not None:
        t0 = time.perf_counter()
        paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if paths:
            reduced = trace_reduce.reduce(
                trace_reduce.load(paths[-1]), window_span=STEP_SPAN, span_names=HOST_SPANS,
                kernel_ops=facts["kernel_ops"])
            if reduced is not None:
                reduced["path"] = paths[-1]
                reduced["bytes"] = os.path.getsize(paths[-1])
                reduced["reduce_s"] = time.perf_counter() - t0
                reduced["steps"] = [traced_from, traced_from + trace_steps - tracing_left]

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in ctx.mesh.devices.flat]
    train.report({
        "phase": "summary", "window_s": window_s, "attempted": attempted, "failed": failed,
        "steps_completed": len(step_ends), "tokens_per_step": batch * seq, "step_ends": step_ends,
        "host_spans": spans, "host_span_names": list(HOST_SPANS), "losses": losses,
        "facts": facts, "trace": reduced, "peak_bytes_in_use": peaks,
        "chip_holders": holders, "pid": os.getpid(),
        "compiles_in_window": compiles.count - compiles_before,
        "compile_cache": {"dir": cache_dir, "entries": [entries_at_start, entries_before, cache_entries()]},
        "max_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024,
    })

