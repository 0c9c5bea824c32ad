"""How many times Python entered a `tracing.scope` while the step was traced (the sum of `entries`): it falls when
bodies are shared or a body stops being entered twice."""

from benchmarks.lib import setup_record

layer = "model"
unit = "count"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_scope_entries(run)
