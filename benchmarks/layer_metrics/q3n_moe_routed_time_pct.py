"""Self time under `moe/dispatch`, `moe/experts` and `moe/combine`: what the 32 held experts cost behind the router (the sorts of 81,920 assignments, the rows moved, the
grouped matmuls at k 2048 x n 512 and back), every direction, as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "moe/dispatch", "moe/experts", "moe/combine")
