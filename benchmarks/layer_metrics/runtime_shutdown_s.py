"""The span `runtime::shutdown` of the run's record: `ray_tpu.shutdown()` from inside, the workers' exit
wait among its stages; the inside twin of the harness's `shutdown_s`."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.span_s(run, "runtime::shutdown")
