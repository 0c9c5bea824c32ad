"""trace_reduce.py on intervals worked by hand: a synthetic trace written as
an XSpace text proto (exact arithmetic), and a small trace recorded on the
v5e (names and planes as libtpu really writes them)."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import trace_reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_measure_subtract_gaps():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert tr.measure(u) == 6
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert tr.gaps(u, 0, 10) == [(3, 5), (8, 10)]


def test_self_time_takes_enclosed_ops_out_of_a_while():
    events = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("custom-call.2", 4.0, 9.0),
              ("fusion.3", 11.0, 12.0)]
    got = {n: t for n, _, _, t in tr.self_times(events)}
    assert got == {"while.1": 2.0, "fusion.1": 3.0, "custom-call.2": 5.0, "fusion.3": 1.0}


def test_async_collective_pair_spans_start_to_done():
    events = [("all-gather-start.1", 0.0, 1.0), ("fusion.1", 1.0, 5.0), ("all-gather-done.1", 5.0, 7.0),
              ("collective-permute-start.2", 8.0, 8.5), ("collective-permute-done.2", 8.5, 9.0),
              ("all-reduce.3", 20.0, 21.0), ("fusion.all-gather-like", 30.0, 31.0)]
    assert tr.collective_intervals(events) == [(0.0, 7.0), (8.0, 9.0), (20.0, 21.0)]


def _xspace(devices):
    """One device plane per entry of `devices` (a list of (name, start_us,
    dur_us) ops) and a host plane with two steps and their spans."""
    planes = []
    for d, ops in enumerate(devices):
        names = sorted({n for n, _, _ in ops})
        meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}\n'
                       for i, n in enumerate(names))
        evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {int(s * 1e6)} "
                      f"duration_ps: {int(t * 1e6)} }}\n" for n, s, t in ops)
        planes.append(f'planes {{ id: {d + 1} name: "/device:TPU:{d}"\n'
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{evs}}}\n'
                      f'lines {{ id: 2 name: "Steps" timestamp_ns: 0 '
                      f"events {{ metadata_id: 1 offset_ps: 0 duration_ps: 99000000 }} }}\n{meta}}}\n")
    host = [("bench_step", 0, 100), ("data_next", 0, 10), ("make_batch+dispatch", 10, 10),
            ("loss_fetch", 20, 78), ("report", 98, 2),
            ("bench_step", 100, 100), ("data_next", 100, 10), ("make_batch+dispatch", 110, 10),
            ("loss_fetch", 120, 78), ("report", 198, 2)]
    names = sorted({n for n, _, _ in host})
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}\n'
                   for i, n in enumerate(names))
    evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {int(s * 1e6)} "
                  f"duration_ps: {int(t * 1e6)} }}\n" for n, s, t in host)
    planes.append(f'planes {{ id: 99 name: "/host:CPU"\nlines {{ id: 1 name: "python3" timestamp_ns: 0\n'
                  f"{evs}}}\n{meta}}}\n")
    return "".join(planes)


US = 1e-6
SPANS = ("data_next", "make_batch+dispatch", "loss_fetch", "report")


def test_reduce_on_a_hand_made_trace():
    from jax.profiler import ProfileData

    # Device 0, microseconds.  Step 1: busy 20..90; step 2: busy 125..190.
    #   while.1 20..90 encloses fusion.1 20..40, custom-call.7 40..60 (kernel),
    #   all-gather-start.1 60..62, fusion.2 62..80 (hides the gather), all-gather-done.1 80..90
    #   step 2: fusion.1 125..150, custom-call.7 150..170, all-reduce.5 170..190 (sync: all exposed)
    dev0 = [("while.1", 20, 70), ("fusion.1", 20, 20), ("custom-call.7", 40, 20),
            ("all-gather-start.1", 60, 2), ("fusion.2", 62, 18), ("all-gather-done.1", 80, 10),
            ("fusion.1", 125, 25), ("custom-call.7", 150, 20), ("all-reduce.5", 170, 20)]
    dev1 = [("fusion.1", 20, 60), ("fusion.1", 120, 60)]  # busy 120 of 200, no collectives
    profile = ProfileData.from_text_proto(_xspace([dev0, dev1]))
    r = tr.reduce(profile, window_span="bench_step", span_names=SPANS, kernel_ops=["%custom-call.7"])
    assert r["window_s"] == pytest.approx(200 * US)
    assert r["window_spans"] == 2
    d0, d1 = r["devices"]
    assert d0["busy_s"] == pytest.approx(135 * US)       # 70 + 65
    assert d0["idle_s"] == pytest.approx(65 * US)
    assert d0["kernel_s"] == pytest.approx(40 * US)
    assert d0["collective_s"] == pytest.approx(50 * US)  # 60..90 and 170..190
    # exposed: 60..62 and 80..90 of the pair (fusion.2 hides 62..80), all of the all-reduce
    assert d0["collective_exposed_s"] == pytest.approx(32 * US)
    assert d0["collective_op_s"] == pytest.approx(32 * US)
    assert d0["xla_compute_s"] == pytest.approx((135 - 40 - 32) * US)  # fusions 63, while self 0
    assert d1["busy_s"] == pytest.approx(120 * US)
    assert d1["collective_s"] == 0
    # top ops by self time, averaged over the two devices
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((45 + 120) / 2 * US)
    assert ops["custom-call.7"] == pytest.approx(20 * US)
    assert ops["while.1"] == pytest.approx(0.0, abs=1e-12)
    # the longest idle gap is device 1's 80..120 (loss_fetch 80..98 covers most of it), then
    # device 0's 90..125: report 98..100, data_next 100..110, dispatch 110..120,
    # loss_fetch 90..98 + 120..125 = 13 -> loss_fetch again
    assert [(w, round(d / US)) for w, d in r["idle_gaps"][:2]] == [("loss_fetch", 40), ("loss_fetch", 35)]
    assert sum(r["idle_by_span_s"].values()) == pytest.approx((65 + 80) / 2 * US)
    assert [round(x / US) for x in r["host_span_s"]["report"]] == [2, 2]


def test_reduce_returns_nothing_without_a_window_or_a_device():
    from jax.profiler import ProfileData

    profile = ProfileData.from_text_proto(_xspace([]))
    assert tr.reduce(profile, window_span="bench_step", span_names=SPANS) is None
    profile = ProfileData.from_text_proto(_xspace([[("fusion.1", 0, 5)]]))
    assert tr.reduce(profile, window_span="no_such_span", span_names=SPANS) is None


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace in benchmarks/tests/data")
def test_reduce_on_the_recorded_v5e_trace():
    """Recorded on 4 v5e chips by benchmarks/tools/record_small_trace.py:
    the planes, the op line, the Mosaic calls and the collectives must be
    found under the names libtpu gives them."""
    import json

    profile = tr.load(RECORDED[0])
    with open(RECORDED[0].replace(".xplane.pb.gz", ".facts.json")) as f:
        facts = json.load(f)
    r = tr.reduce(profile, window_span="bench_step", span_names=SPANS, kernel_ops=facts["kernel_ops"])
    assert r is not None and len(r["devices"]) == facts["chips"]
    assert r["window_spans"] == facts["steps"]
    for d in r["devices"]:
        assert 0 < d["busy_s"] <= r["window_s"]
        assert d["kernel_s"] > 0
        assert d["collective_s"] >= d["collective_exposed_s"] > 0
        assert d["busy_s"] == pytest.approx(
            d["kernel_s"] + d["collective_op_s"] + d["xla_compute_s"])
    # this libtpu writes the small model's collectives as sync ops and async-collective fusions
    assert {"all-gather", "all-reduce"} <= set(r["collective_names"])
    assert any(" custom-call " in label for label, _ in r["device_ops"])


def test_op_name_and_label_from_a_whole_hlo_instruction():
    text = ("%fusion.382 = bf16[2,4096,2048]{1,2,0:T(8,128)(2,1)} fusion(bf16[2048,8192]{1,0:T(8,128)(2,1)} "
            "%dynamic-slice_bitcast_fusion.19), kind=kOutput, calls=%fused_computation.74.clone.clone")
    assert tr.op_name(text) == "fusion.382"
    assert tr.op_label(text) == "fusion.382 fusion bf16[2,4096,2048]"
    kernel = ("%branch_0_fun.45 = (bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)}, bf16[2,16,4096,128]{3,2,1,0}) "
              "custom-call(bf16[2,16,4096,128]{3,2,1,0} %bitcast.561), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(kernel) == "branch_0_fun.45"
    assert tr.op_label(kernel) == "branch_0_fun.45 custom-call (bf16[2,16,4096,128], bf16[2,16,4096,128])"
    assert tr.op_name("fusion.1") == tr.op_label("fusion.1") == "fusion.1"
