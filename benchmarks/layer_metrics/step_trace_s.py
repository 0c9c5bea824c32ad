"""Seconds of the step's `jax::trace` span(s) in set-up: Python tracing of `_train_step`, the whole of what the
five `step_trace_*_s` split."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run)
