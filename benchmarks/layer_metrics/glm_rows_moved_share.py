"""Rows the share MOVED over the T x K assignments it sorted: `moe_rows_moved_share` of the step metrics (the rung its row buffers took over 32,768, mean over
the expert layers and the module's block: `ray_tpu/models/moe.py` `_rungs`), the newest value the run's record keeps.  0.3125 is the lower of this cell's two
rungs (10,240 rows, 1.25x a uniform router's share of 8,192), 1.0 every assignment.  `benchmarks/lib/trace_glm.py`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.counter(run, "moe_rows_moved_share")
