"""Sum of `thread_cpu_s` ÷ sum of `period_s` over the window's rows of the run record's `steps` (the stalled
periods, over twice the median, set apart): the share of a step in which the stepping thread is ON a CPU.
Near 0 the host waits for the device; at 100 the device starves.  Read in every run, with no profiler: the
host-side twin of `device_idle_pct`.  Totals and not a median of the rows' ratios, because the chip host's
`time.thread_time` moves in ticks of 10 ms: a row reads 0, 0.01 or 0.02 s, and the median row 0."""

from benchmarks.lib import step_rows

layer = "train step host side"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return step_rows.read(run, "host_busy_pct")
