"""Share of the traced window in which a collective (all-gather, reduce-scatter,
all-reduce, collective-permute, all-to-all; an async pair from its -start to its
-done) is in flight, mean over the devices."""

from benchmarks.lib.trace_reduce import mean_share_pct

layer = "parallel"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mistral7b-fsdp4.seq4k"]


def read(run):
    return mean_share_pct(run.get("trace"), "collective_s")
