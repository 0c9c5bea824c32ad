"""What `tracing.scope`'s accounting costs where it runs: the trace of one cell's step with the
table on against `scope` = `jax.named_scope`, alternating, in one process on the chip's host,
and an entry of each form timed alone.

    chiprun -- python3 scripts/scope_cost.py [--workload kimi-linear-ep16-1chip.seq16k] [--pairs 6]

`--cpu-toy` runs the same script here at the rehearsal's widths (it measures nothing).
"""
import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the checkout: `benchmarks.*`, `ray_tpu`


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="kimi-linear-ep16-1chip.seq16k")
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--cpu-toy", action="store_true")
    args = ap.parse_args()
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmarks import run as harness
    from ray_tpu.train import run_record
    from ray_tpu.util import tracing

    cell, config, traffic = harness.load_cell(args.workload)
    seq, batch = traffic["seq_len"], traffic["seqs_per_chip"] * cell["chips"]
    if args.cpu_toy:
        config, seq, batch = dict(config, **harness.REHEARSAL_CONFIG), harness.REHEARSAL_SEQ, 2
    builder = harness.load_plugin("builders", config["kind"])
    devices = jax.devices()[: cell["chips"]]
    run_record.install_jax_listener()
    table_scope = tracing.scope

    def named_only(name, *, kernel=False, host_only=False):
        return contextlib.nullcontext() if host_only else jax.named_scope(name)

    forms = {"table": table_scope, "named": named_only}

    def trace_once(form):
        """(seconds of `trace()`, of the step's `jax::trace` span, scope entries) of a fresh step."""
        tracing.scope = forms[form]
        try:
            jax.clear_caches()
            _, ctx = builder.build(config, seq, devices)
            state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
            toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=ctx.batch_sharding)
            run_record.flush_traces()
            before = len(tracing.lifecycle_spans())
            t0 = time.perf_counter()
            with ctx.mesh:
                ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
            seconds = time.perf_counter() - t0
            run_record.flush_traces()
            span = [s for s in tracing.lifecycle_spans()[before:] if s["attrs"].get("fun_name") == "_train_step"][-1]
            entries = sum(row[1] for row in span["attrs"].get("scopes", {}).values())
            return seconds, span["end"] - span["start"], entries
        finally:
            tracing.scope = table_scope

    def per_entry(form, n=200_000):
        make = forms[form]
        t0 = time.perf_counter()
        for _ in range(n):
            with make("layer/attn_proj"):
                pass
        seconds = (time.perf_counter() - t0) / n
        tracing.take_scopes(0.0, time.time() + 1.0)
        return seconds

    print("[cost] warm-up (a process's first trace imports, and fills jax's own caches)", trace_once("named"), flush=True)
    rows = {form: [] for form in forms}
    for pair in range(args.pairs):
        for form in (("table", "named") if pair % 2 == 0 else ("named", "table")):
            got = trace_once(form)
            rows[form].append(got)
            print(f"[cost] pair {pair} {form}: trace() {got[0]:.3f} s, span {got[1]:.3f} s, entries {got[2]}", flush=True)
    med = {form: statistics.median(r[1] for r in rows[form]) for form in rows}
    entry_us = {form: 1e6 * min(per_entry(form) for _ in range(3)) for form in rows}
    entries = rows["table"][0][2]
    print("[cost] " + json.dumps({
        "workload": args.workload, "device": jax.devices()[0].device_kind, "pairs": args.pairs,
        "step_trace_s_table": [round(r[1], 3) for r in rows["table"]],
        "step_trace_s_named": [round(r[1], 3) for r in rows["named"]],
        "median_table_s": med["table"], "median_named_s": med["named"], "difference_s": med["table"] - med["named"],
        "difference_pct_of_step_trace_s": 100.0 * (med["table"] - med["named"]) / med["table"],
        "entries": entries, "per_entry_us": entry_us,
        "entries_times_per_entry_difference_s": entries * (entry_us["table"] - entry_us["named"]) / 1e6}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
