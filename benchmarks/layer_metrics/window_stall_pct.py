"""Share of the window lost to steps slower than the median step: what the
end-to-end throughput (tokens per step over the MEDIAN step time) does not
see.  0 when every step takes the median; one 3.2 s step among twenty of
1.54 s reads 5.4.  The steps the profiler was started and stopped in are
left out: that stall is the tracing's own."""

import statistics

layer = "train step host side"
unit = "%"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    ends = run["summary"]["step_ends"]
    steps = [b - a for a, b in zip([0.0] + ends, ends)]
    trace = run.get("trace")
    if trace:
        first, after = trace["steps"]  # start_trace ran in `first`, stop_trace in `after`
        steps = steps[:first] + steps[after + 1:]
    if len(steps) < 2:
        return None
    return 100.0 * (1.0 - len(steps) * statistics.median(steps) / sum(steps))
