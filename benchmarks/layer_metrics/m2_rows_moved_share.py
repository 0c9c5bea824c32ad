"""Rows the share MOVED over the T x K assignments it sorted: `moe_rows_moved_share` of the step metrics (the rung its row buffers took over
131,072, mean over the eight layers: `ray_tpu/models/moe.py` `_rungs`), the newest value the run's record keeps.  0.5 is the lower of this
cell's two rungs (65,536 rows, twice a uniform router's share), 1.0 every assignment.  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.counter(run, "moe_rows_moved_share")
