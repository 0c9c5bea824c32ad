"""Self time of the device ops whose innermost name is `dsa/topk`: the selection of each query's 2,048 best causal keys, a radix select over the float32
patterns of the [8192, 8192] scores (32 passes that fix one bit each) and the int8 mask, as % of the traced window. Inside `attn_core_time_pct`.
`benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "dsa/topk")
