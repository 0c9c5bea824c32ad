"""Executed causal FLOPs of the `flash_fwd` kernel's calls in the traced window, over the chip's
bf16 peak (197 TFLOP/s), over the calls' device time.  One call = 2 matmuls (QK^T, PV) x 2*D flops
per (query, key) pair x S*S/2 causal pairs x local heads x local batch: 2/6 of the layer's needed
forward + backward count in `benchmarks/lib/flops.py`.  The recomputed call in the backward pass
counts on both sides: its FLOPs as executed, and its time.  Compute-bound at head size 128."""

from benchmarks.lib import trace_scopes

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.kernel_roofline_pct(run, "flash_fwd")
