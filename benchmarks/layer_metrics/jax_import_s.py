"""The span `train::backend::import_jax` of the run's record: the `import jax` the TrainWorker really pays,
in whichever call reaches it first.  Part of `fit_to_loop_s`."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.span_s(run, "train::backend::import_jax")
