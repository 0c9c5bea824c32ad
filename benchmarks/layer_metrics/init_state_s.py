"""Host clock around `ctx.init_state(seed)` + `block_until_ready`: weights and
optimizer state made on the device in one jitted call."""

layer = "model"
unit = "s"
source = "host_clock"
moves = "setup_s"


def read(run):
    return run["setup"]["init_state_s"]
