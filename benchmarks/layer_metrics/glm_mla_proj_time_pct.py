"""Self time of the device ops whose innermost name is `mla/proj`, in the stack's layers and in the module's block: ln1, the low-rank q (768) with its norm, the
latent (512 + 64) with its norm, the rotation of the two 64-wide rope parts, the key's concatenation, `wo` and the residual add (forward, backward and
recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_glm.py`).  Inside `attn_proj_time_pct`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.share_pct(run, "mla/proj")
