"""Needed causal attention FLOPs (forward + backward, `benchmarks/lib/flops.py`) of
the traced steps on one chip, over the chip's bf16 peak, over the summed kernel
time.  Compute-bound: at head size 128 and these lengths the kernels' FLOPs over
peak exceed their bytes over bandwidth.  The recomputed forward call counts as
time, not as needed work."""

import importlib
import statistics

from benchmarks.lib import flops

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    kernel_s = statistics.fmean(d["kernel_s"] for d in trace["devices"])
    if kernel_s <= 0:
        return None
    builder = importlib.import_module("benchmarks.builders." + run["config"]["kind"])
    per_token = builder.attention_flops_per_token(run["config"], run["traffic"]["seq_len"])
    tokens = run["summary"]["tokens_per_step"] / run["cell"]["chips"] * trace["window_spans"]
    peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * tokens / peak / kernel_s
