"""Idle seconds of the traced window under NONE of the library's host spans (`train_step/make_batch`,
`train_step/dispatch`, `train/report`), mean over the devices ÷ window: the caller's loop (its data, its loss
fetch).  With `device_idle_in_library_pct` it adds up to the MEAN idle share; `device_idle_pct` is the worst
device's."""

from benchmarks.lib import trace_idle

layer = "device"
unit = "%"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_idle.outside_library_pct(run)
