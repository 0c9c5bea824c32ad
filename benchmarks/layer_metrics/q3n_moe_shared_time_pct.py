"""Self time of the device ops whose innermost name is `moe/shared`: the shared expert's SwiGLU (512) and its scalar sigmoid gate that every token goes through (forward,
backward and recompute), as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "moe/shared")
