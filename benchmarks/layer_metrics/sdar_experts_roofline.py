"""The grouped matmuls' needed FLOPs (w_gate, w_up and w_down, forward + backward) AT THE ROWS THE TRACED STEPS GAVE the held experts
(`moe_held_rows_mean` of each traced step, the run record's `step_counter_series`; never the uniform expectation: both copies' rows are routed,
16,384 a layer), over the chip's bf16 peak, over the device time under `moe/experts`.  0 where they got no rows.  `benchmarks/lib/trace_sdar.py`."""

from benchmarks.lib import trace_sdar

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["sdar-ep8-1chip.seq8k"]


def read(run):
    return trace_sdar.experts_roofline_pct(run)
