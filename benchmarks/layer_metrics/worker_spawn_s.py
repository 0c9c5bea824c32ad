"""`fit()`'s first line to the end of `train::worker_group::spawn` (the worker group answered its first
call: placement, the actor's creation task, a fork or a warm worker, its boot), from the run's record.  The
first of the five parts of `fit_to_loop_s`; listed under `setup_s` as that is."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.worker_spawn_s(run)
