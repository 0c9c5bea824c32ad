"""The time the selective scan NEEDS in the traced window (its bytes, x z dt B C read and y written and twice that for the backward, over 819 GB/s:
the BYTES bound, 25 x its FLOPs over the bf16 peak) over the device time under `s6/scan` in every direction: recompute is time, not work.
`benchmarks/lib/trace_sambay.py`, `benchmarks/builders/sambay_decoder.py` `s6_scan_bytes_per_token`."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.s6_scan_roofline_pct(run)
