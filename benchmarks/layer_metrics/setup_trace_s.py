"""Seconds of `setup_s` (loop's first line to the window's first step) under the record's `jax::trace`
events: Python tracing of every program the set-up builds."""

from benchmarks.lib import run_record

layer = "model"
unit = "s"
source = "program_counter"
moves = "setup_s"


def read(run):
    return run_record.setup_s_under(run, "jax::trace")
