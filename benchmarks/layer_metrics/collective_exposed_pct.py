"""Share of the traced window in which a collective is in flight and no other op
runs on that device, mean over the devices: communication compute did not hide."""

from benchmarks.lib.trace_reduce import mean_share_pct

layer = "parallel"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mistral7b-fsdp4.seq4k"]


def read(run):
    return mean_share_pct(run.get("trace"), "collective_exposed_s")
