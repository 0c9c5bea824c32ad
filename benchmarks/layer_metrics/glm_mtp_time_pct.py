"""Self time of every device op with `mtp` in its path: the multi-token-prediction module's embedding lookup, two norms and `W_eh`, its block (latent attention,
its flash kernels, router, held experts, shared expert), its norm, its pass through the head and its cross entropy (forward, backward and recompute), as %
of the traced window (`benchmarks/lib/trace_glm.py`).  16.5% of the needed FLOPs at seven layers."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.module_share_pct(run)
