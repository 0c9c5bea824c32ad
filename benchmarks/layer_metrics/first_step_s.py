"""Host clock around the first `train_step` + loss fetch: tracing, lowering and the
compile (cold) or the persistent cache's load (warm), plus one step."""

layer = "model"
unit = "s"
source = "host_clock"
moves = "setup_s"


def read(run):
    return run["setup"]["first_step_s"]
