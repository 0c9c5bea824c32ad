"""Self time of the device ops whose innermost name is `gdn/scan`: the chunked gated delta rule of the six delta layers (`ops/kda.py` with the head's decay broadcast over
the key's channels; forward, the forward run again for the backward, and the backward), as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`)."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "gdn/scan")
