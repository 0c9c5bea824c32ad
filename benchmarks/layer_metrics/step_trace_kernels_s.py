"""Self seconds of the step's trace under a path whose innermost name is a kernel's own (`attrs["kernels"]`: the
scopes entered with `kernel=True`): Python tracing of Mosaic kernels' bodies, forward and backward, both branches
of a `platform_dependent`."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run, "kernels")
