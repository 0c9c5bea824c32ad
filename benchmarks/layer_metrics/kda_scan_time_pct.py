"""Self time of the device ops whose innermost name is `kda/scan`: the whole chunked gated delta rule of `ray_tpu/ops/kda.py` (decayed
[chunk, chunk] matrices, the triangular solve, the pass over chunk states, the outputs; forward, backward and recompute), as % of the traced
window, mean over the devices (`benchmarks/lib/trace_kimi.py`).  Inside `attn_core_time_pct`, beside the MLA layer's flash kernels."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.share_pct(run, "kda/scan")
