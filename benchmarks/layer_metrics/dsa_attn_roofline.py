"""The time the sparse core NEEDS in the traced window (the longer of its FLOPs over 197 TFLOP/s and its bytes over 819 GB/s, on the SELECTED pairs
alone: `min(t + 1, 2048)` keys a query, 16 heads of 192 | 128, forward + backward, two full layers;
`builders/sparse_mla_moe_decoder.selected_flops_per_layer` / `selected_bytes_per_layer`) over the device time under `dsa/attn` in every direction: the
unselected pairs a masked tile computes (56% of the causal pairs at 8,192), the products the backward kernels compute again and the transposes are
time, not work. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_dots3

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_dots3.attn_roofline_pct(run)
