"""The span `train::backend::chip_wait` of the run's record: seconds the TrainWorker waited for another
process to release the chips before libtpu opens them (0 in a lone run).  Part of `fit_to_loop_s`."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.span_s(run, "train::backend::chip_wait")
