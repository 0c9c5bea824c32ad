"""Self time of the device ops whose innermost name is `s6/scan`: the chunked selective scan of `ray_tpu/ops/selective_scan.py`, three serial
scans of 8,192 positions a step (forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_sambay.py`).
Inside `attn_core_time_pct`."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.share_pct(run, "s6/scan")
