"""Idle seconds of the traced window that lie under the library's own host spans (`train_step/make_batch`,
`train_step/dispatch`, `train/report`: `tracing.annotate` in `LMTrainContext.train_step` and
`TrainSession.report`), mean over the devices ÷ window: the part of the idle share that is ray_tpu's.  The line
`[bench] idle by program span {...}` gives the three spans apart."""

from benchmarks.lib import trace_idle

layer = "device"
unit = "%"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_idle.in_library_pct(run)
