"""1 - union of device-op intervals over the traced window, on the worst device."""

layer = "device"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * max(d["idle_s"] for d in trace["devices"]) / trace["window_s"]
