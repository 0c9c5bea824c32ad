"""`memory_analysis()` of the step as compiled on the chip: arguments + outputs +
temporaries - aliased, per device (not `peak_bytes_in_use`, which leaves XLA's
temporaries out).  Mostly a guard on what still fits."""

layer = "device"
unit = "GB"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return run["summary"]["facts"]["step_hbm_bytes"] / 1e9
