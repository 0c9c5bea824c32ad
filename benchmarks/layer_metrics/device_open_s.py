"""The span `train::backend::device_open` of the run's record: `jax.devices()` in the TrainWorker, libtpu
opening the chip(s).  Part of `fit_to_loop_s`."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.span_s(run, "train::backend::device_open")
