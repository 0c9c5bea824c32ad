"""Median `report_s` of the window's rows of the run record's `steps` (`steps.rows[*].report_s`): the seconds
`TrainSession.report` counts around its own body and adds to the step that is open.  The inside twin of
`report_call_ms`, which times the call from the loop."""

from benchmarks.lib import step_rows

layer = "session and report"
unit = "ms"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return step_rows.read(run, "report_ms")
