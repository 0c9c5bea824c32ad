"""Rows one held expert multiplied in a step: `moe_held_rows_mean` of the program's step metrics (mean over the held experts of every
expert layer), the newest value the run's record keeps (`ray_tpu/train/run_record.py`, `step_counters`).  512 under a uniform router at 16,384
tokens, 8 of 256; a deployment's 16 data-parallel chips would send each expert 16 x that.  `benchmarks/lib/trace_kimi.py`."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "rows"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.held_rows_per_expert(run)
