"""Wire fuzzer tier-1 subset + the permanent regression corpus.

The fuzzer's contract (scripts/wire_fuzz.py): every byte string fed to
wire.decode_frames either decodes to a list or raises wire.ProtocolError
— never a hang, never another exception, never partial dispatch — and
the native codec and pickle fallback are interchangeable for every kind
the native table claims.

REGRESSION_CORPUS pins every frame (or minimal reconstruction of one)
that ever produced a non-ProtocolError outcome.  Entries never leave:
each is a decoder bug class that shipped once.
"""

import marshal
import os
import random
import sys
import time

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import wire_fuzz  # noqa: E402
from ray_tpu._private import wire, wire_native  # noqa: E402
from ray_tpu._private.wire import ProtocolError  # noqa: E402

# "RT" magic + protocol v3, little-endian — frozen bytes, deliberately
# NOT built from wire._HEADER: the corpus must keep meaning the exact
# frames that misbehaved even if framing constants move.
_HDR = bytes.fromhex("52540300")

# (name, frame) — every entry once produced a hang or a non-ProtocolError
# exception out of wire.decode_frames.
REGRESSION_CORPUS = [
    # marshal allocation bomb (fuzz seed 3, frame 3760): an 11-byte native
    # body — kind 13 (since retired), marshal v4, then tuple opcode '('
    # with a declared count of 0x20100000 — made marshal.loads zero out a
    # ~4 GB tuple before noticing the stream was empty.  58 s of kernel
    # time on the decode path from 11 bytes.  The second entry is the same
    # bomb under a kind that still decodes (14, reply), so the per-header
    # length check keeps its case.
    ("marshal-tuple-bomb", bytes.fromhex("525403000d042800100020")),
    ("marshal-tuple-bomb-live-kind",
     bytes.fromhex("525403000e042800100020")),
    # pickle BYTEARRAY8 bomb (fuzz seed 3, byte-flip class): declares a
    # 2^40-byte bytearray, which pickle.loads allocates AND zero-fills
    # before checking the buffer holds it.
    ("pickle-bytearray8-bomb",
     _HDR + b"\x80\x05\x96" + (1 << 40).to_bytes(8, "little")),
    # pickle BINBYTES8 bomb: same pre-allocation, unzeroed.
    ("pickle-binbytes8-bomb",
     _HDR + b"\x80\x05\x8e" + ((1 << 63) - 1).to_bytes(8, "little")),
    # pickle LONG_BINPUT memo bomb: the memo table is grown (zeroed) to
    # the declared index — 0x7fffffff entries from a 15-byte body.
    ("pickle-memo-bomb",
     _HDR + b"\x80\x05\x8c\x01ar" + (0x7FFFFFFF).to_bytes(4, "little")
     + b"\x2e"),
    # marshal nested-count bomb: every container count fits `remaining`
    # individually, but 60 nested headers sum to gigabytes — caught by
    # the cumulative allocation budget, not the per-header check.
    ("marshal-nested-bomb",
     _HDR + bytes([wire_native.KIND_IDS["reply"],
                   wire_native.MARSHAL_VERSION])
     + (b"(" + (500).to_bytes(4, "little")) * 60 + b"N" * 500),
    # native ids 12 and 13 are retired: a well-formed body under either
    # is an unknown kind, refused like any other.
    ("retired-native-kind-12",
     _HDR + bytes([12, wire_native.MARSHAL_VERSION])
     + marshal.dumps(("conn-1", [b"b1", b"b2"]), 2)),
    ("retired-native-kind-13",
     _HDR + bytes([13, wire_native.MARSHAL_VERSION])
     + marshal.dumps(("conn-1", b"payload"), 2)),
    # corrupt pickled bodies that once leaked UnpicklingError / EOFError /
    # AttributeError out of the recv loop instead of ProtocolError.
    ("pickle-garbage", _HDR + b"\x80\x05garbage"),
    ("pickle-missing-class", _HDR + b"\x80\x04cnot_a_module\nNoSuchClass\n."),
    ("pickle-truncated",
     _HDR + wire_fuzz.pickle.dumps(("heartbeat", 3), protocol=5)[:9]),
    ("pickle-empty-body", _HDR),
]


@pytest.mark.parametrize("name,frame", REGRESSION_CORPUS,
                         ids=[n for n, _ in REGRESSION_CORPUS])
def test_regression_corpus_rejects_cleanly(name, frame):
    """Each corpus frame must raise ProtocolError — and promptly.  The
    bombs originally took minutes of kernel time; anything over a couple
    of seconds means a pre-allocation guard regressed."""
    t0 = time.monotonic()
    with pytest.raises(ProtocolError):
        wire.decode_frames(frame)
    assert time.monotonic() - t0 < 2.0, (
        f"{name}: rejection took {time.monotonic() - t0:.1f}s — "
        "an allocation guard regressed"
    )


def test_fuzz_subset_contract_holds():
    """Tier-1 fuzz subset: >= 1000 seeded frames through the full
    generator (valid singles, native bodies, batches, truncations,
    byte-flips, garbage, native/batch/pickle corruption) with zero
    non-ProtocolError outcomes and zero codec divergences."""
    report = wire_fuzz.run_fuzz(seed=0, frames=1500)
    assert report.frames >= 1000
    assert report.ok, (
        f"failures={report.failures[:5]} "
        f"divergences={report.codec_divergences[:5]}"
    )
    # Both sides of the contract must actually have been exercised.
    assert report.decoded_ok > 100
    assert report.protocol_errors > 100


def test_fuzz_second_seed_contract_holds():
    """A different seed walks different corruption paths; keeps the
    subset from overfitting to one RNG stream."""
    report = wire_fuzz.run_fuzz(seed=7, frames=1200)
    assert report.ok, (
        f"failures={report.failures[:5]} "
        f"divergences={report.codec_divergences[:5]}"
    )


def test_explicit_truncation_sweep():
    """Every prefix of a valid single, native, and batch frame must
    decode or reject cleanly — the torn-frame class, exhaustively."""
    rng = random.Random(1)
    frames = [
        wire.encode(("heartbeat", 3)),
        wire.encode_native(("task", wire_fuzz.make_spec(rng), b"blob")),
        wire.encode_batch(
            [wire.encode_body(("heartbeat",)),
             wire.encode_body(("ready", "oid", 1))]
        ),
    ]
    for buf in frames:
        for cut in range(len(buf)):
            try:
                wire.decode_frames(buf[:cut])
            except ProtocolError:
                pass

def test_batch_is_all_or_nothing():
    """A batch with one corrupt sub-frame rejects the WHOLE frame —
    partial dispatch of a batch would re-order the control stream."""
    bodies = [
        wire.encode_body(("heartbeat",)),
        b"\x80\x05garbage",
        wire.encode_body(("ready", "oid", 1)),
    ]
    with pytest.raises(ProtocolError):
        wire.decode_frames(wire.encode_batch(bodies))


def test_codec_differential_no_divergence():
    """Every kind in the native table, down both codec paths: equal
    objects with equal type trees, or a documented decline."""
    report = wire_fuzz.FuzzReport()
    wire_fuzz.run_codec_check(random.Random(0), report)
    assert not report.codec_divergences, report.codec_divergences[:5]
    assert report.codec_checks >= len(wire_native.KIND_IDS)


def test_native_encode_declines_malformed_spec_position():
    """Fuzz-found encode-side bug: a schema-legal ('task', str, str)
    frame (types can't pin payload positions) must make the native
    encoder DECLINE, not crash on spec_to_tuple."""
    assert wire_native.encode(("task", "not-a-spec", "y")) is None


def test_guard_off_still_decodes_valid_frames():
    """RAY_TPU_WIRE_GUARD=0 skips the scans but valid traffic is
    unaffected (bombs are NOT exercised with the guard off — that's the
    hang this knob signs up for on trusted fabrics)."""
    saved = wire_native._GUARD
    wire_native._GUARD = False
    try:
        body = wire_native.encode(("task", wire_fuzz.make_spec(
            random.Random(2)), b"blob"))
        assert wire_native.decode(body)[0] == "task"
        assert wire.decode_frames(
            wire.encode(("heartbeat", 3))
        ) == [("heartbeat", 3)]
    finally:
        wire_native._GUARD = saved


def test_marshal_scan_accepts_everything_marshal_emits():
    """The guard must be invisible for legit bodies: anything
    marshal.dumps(..., 2) produces for data payloads passes the scan."""
    import marshal

    for probe in [None, True, False, 0, -1, 2 ** 31, -(2 ** 31), 2 ** 200,
                  -(2 ** 200), 1.5, float("inf"), b"", b"x" * 300, "", "s",
                  "é" * 70, (), (1, (2, (3,))), [], [1, [2]], {},
                  {"k": {"n": [1]}, 1: b"b"},
                  ("mixed", 2 ** 100, {"d": (None, True)}, [b"x", "y"])]:
        wire_native._scan_payload(marshal.dumps(probe, 2))


def test_pickle_scan_accepts_everything_protocol5_emits():
    import pickle

    spec = wire_fuzz.make_spec(random.Random(3))
    for probe in [("reply", "rid", False, ValueError("err"), None),
                  ("task", spec, 7), ("memo", spec, spec, spec),
                  ("y", "é" * 300, b"z" * 70000, 2 ** 100,
                   frozenset({1, 2}), bytearray(b"ab"))]:
        wire._scan_pickle(pickle.dumps(probe, protocol=5))
