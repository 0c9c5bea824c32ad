"""Rows one held expert multiplied in a step: `moe_held_rows_mean` of the program's step metrics (mean over the 16 held experts of the expert layers and the
module's block), the newest value the run's record keeps.  512 under a uniform router at 8,192 tokens, 4 of 64, and 512 where the router's four blocks start
equal (one of a token's four choices a share).  `benchmarks/lib/trace_glm.py`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "rows"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.counter(run, "moe_held_rows_mean")
