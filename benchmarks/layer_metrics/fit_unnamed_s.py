"""`fit()`'s first line to the loop's first line, less the union of the named spans inside it (the spawn,
`import_jax`, `chip_wait`, `device_open`): the coverage guard of the four metrics above, as `unscoped_time_pct`
is for the device.  The five add up to `fit_to_loop_s`."""

from benchmarks.lib import run_record

layer = "entry and worker spawn"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return run_record.fit_unnamed_s(run)
