"""Self time of the two attention layers' mixers: the ops under `attn/gate` (the sigmoid gate on the core's output) and those under `layer/attn_proj` / `layer/attn_core`
that no `gdn/*` name reaches (q|gate, k, v and o projections, per-head norms, the rope on 64 of 256, the three flash kernels), every direction, as % of the traced window
(`benchmarks/lib/trace_qwen3_next.py`)."""

from benchmarks.lib import trace_qwen3_next

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "attn/gate", "attn/mixer")
