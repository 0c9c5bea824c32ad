"""Rows the share MOVED over the T x K assignments it sorted: `moe_rows_moved_share` of the step metrics (the rung its row buffers took over 81,920, mean over the eight
layers: `ray_tpu/models/moe.py` `_rungs`), the newest value the run's record keeps.  0.125 is the lowest of this cell's four rungs (10,240 rows, twice a uniform router's share
of 5,120), 1.0 every assignment.  `benchmarks/lib/trace_qwen3_next.py`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.counter(run, "moe_rows_moved_share")
