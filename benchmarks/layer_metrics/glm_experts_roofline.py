"""The grouped matmuls' needed FLOPs (w_gate, w_up and w_down, forward + backward: `6 * rows * 3 * 2048 * 1536`) AT THE ROWS THE TRACED STEPS GAVE the held
experts of the expert layers and the module's block (`moe_held_rows_mean` of each traced step, the run record's `step_counter_series`; never the uniform
expectation), over the chip's bf16 peak, over the device time under `moe/experts`.  0 where they got no rows.  `benchmarks/lib/trace_glm.py`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.experts_roofline_pct(run)
