"""Rows one held expert multiplied in a step: `moe_held_rows_mean` of the program's step metrics (mean over the 16 held experts of the four
expert blocks), the newest value the run's record keeps. 384 under a uniform router at 8,192 tokens, 6 of 128; a collapsed router gives 0 or
multiples of 512 (8,192 / 16).  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "rows"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.counter(run, "moe_held_rows_mean")
