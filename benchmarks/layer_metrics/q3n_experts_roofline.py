"""The grouped matmuls' needed FLOPs (w_gate, w_up and w_down, forward + backward: `6 * rows * 3 * 2048 * 512`) AT THE ROWS THE TRACED STEPS GAVE the 32 held experts of the
eight layers (`moe_held_rows_mean` of each traced step, the run record's `step_counter_series`; never the uniform expectation), over the chip's bf16 peak, over the device time
under `moe/experts`.  0 where they got no rows.  `benchmarks/lib/trace_qwen3_next.py`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.experts_roofline_pct(run)
