"""Share of the traced window in device ops that are neither Mosaic kernels nor
collectives (self time on the `XLA Ops` line), mean over the devices: the matmul
fusions, norms, rope, loss and optimizer that XLA wrote."""

from benchmarks.lib.trace_reduce import mean_share_pct

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return mean_share_pct(run.get("trace"), "xla_compute_s")
