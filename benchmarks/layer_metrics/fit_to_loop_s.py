"""Driver clock at the `fit()` call to the first line of the loop in the TrainWorker
(both `time.time()` on one machine): placement group, worker spawn, and the
backend's `on_start`, which imports jax and opens the chip(s).  Set-up a run pays
BEFORE `setup_s` starts counting (it varies by +-3 s run to run, too much for a
bounded metric), so it is listed under `setup_s` and read beside it."""

layer = "entry and worker spawn"
unit = "s"
source = "host_clock"
moves = "setup_s"


def read(run):
    return run["start"]["t_loop"] - run["clocks"]["t_fit"]
