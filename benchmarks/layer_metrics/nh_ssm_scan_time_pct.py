"""Self time under `ssm/scan` (the whole chunked selective scan with 8 B/C groups: `C B^T` a group, decay masks a head, chunk states, the
serial pass over chunks) as % of the traced window.  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.share_pct(run, "ssm/scan")
