"""Self time of the device ops whose innermost name is `moe/router`: the float32 router matmul at HIGHEST over 512 outputs, the softmax, top-10 and the loss statistics of
the eight expert blocks (every direction), as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "moe/router")
