"""Median over the window's rows of `make_batch_s + dispatch_s + report_s` (the run record's `steps.rows`): the
host seconds a step spends INSIDE ray_tpu (sharding the batch, dispatching the step, `train.report`), every
step of the window.  The library's part of `host_turnaround_ms`; the rest of that is the caller's loop."""

from benchmarks.lib import step_rows

layer = "train step host side"
unit = "ms"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return step_rows.read(run, "library_ms")
