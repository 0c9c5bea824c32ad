"""Median seconds from `train.report` in the TrainWorker to the driver's `on_report`, over the run's reports
(the run's record): bounded by the executor's 50 ms poll."""

from benchmarks.lib import run_record

layer = "session and report"
unit = "ms"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return run_record.report_delivery_ms(run)
