"""Driver clock around `ray_tpu.init()`: the runtime, its object store and
zygote coming up.  Outside `setup_s`; bimodal on the four-chip host (0.17 s or
about 3 s on the same code, PR 22)."""

layer = "entry and worker spawn"
unit = "s"
source = "host_clock"
moves = "setup_s"


def read(run):
    return run["clocks"]["init_s"]
