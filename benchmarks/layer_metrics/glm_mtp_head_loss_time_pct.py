"""Self time of the device ops whose innermost name is `lm_head` or `loss` UNDER `mtp`: the module's pass through the model's own head (19,360 columns) and
its cross entropy, the second `head_cross_entropy` of the step (forward and backward), as % of the traced window (`benchmarks/lib/trace_glm.py`).  Inside
`lm_head_loss_time_pct` and inside `glm_mtp_time_pct`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.module_share_pct(run, "lm_head", "loss")
