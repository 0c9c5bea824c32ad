"""Self time of the device ops whose innermost `ssm/*` name is `ssm/scan`: the whole selective scan (within-chunk products, chunk states, the pass over chunks, state to output, `D*x`)
(forward, backward and recompute), as % of the traced window, mean over the devices
(`benchmarks/lib/trace_ssm.py`).  Inside `attn_core_time_pct`, which counts that half of both kinds of mixer."""

from benchmarks.lib import trace_ssm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["granite-h-micro-1chip.seq8k"]


def read(run):
    return trace_ssm.share_pct(run, "ssm/scan")
