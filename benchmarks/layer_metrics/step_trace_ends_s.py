"""Self seconds of the step's trace under a path with `embed`, `final_norm`, `lm_head`, `loss` or `optimizer` in it
(and no kernel's name innermost): the model's two ends and the optimizer."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run, "ends")
