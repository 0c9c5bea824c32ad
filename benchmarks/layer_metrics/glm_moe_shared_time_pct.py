"""Self time of the device ops whose innermost name is `moe/shared`, in the expert layers and in the module's block: the shared expert's SwiGLU (1536) that
every token goes through (forward, backward and recompute), as % of the traced window (`benchmarks/lib/trace_glm.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.share_pct(run, "moe/shared")
