"""Rows one held expert multiplied in a step: `moe_held_rows_mean` of the program's step metrics (mean over the 32 held experts of the eight layers), the newest value the
run's record keeps.  160 under a uniform router at 8,192 tokens, 10 of 512 (5,120 rows the share).  `benchmarks/lib/trace_qwen3_next.py`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "rows"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.counter(run, "moe_held_rows_mean")
