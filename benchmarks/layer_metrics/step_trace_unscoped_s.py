"""Seconds of the step's trace under no scope (`attrs["unscoped_s"]`): the coverage guard of the four parts above;
the five add up to `step_trace_s`."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run, "unscoped")
