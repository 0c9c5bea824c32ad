#!/usr/bin/env python
"""Deterministic wire-protocol fuzzer + differential codec check.

The runtime twin of the wire-schema lint (the same split the concurrency
lint has with the lock watchdog): the static pass proves send/recv sites
agree with wire.SCHEMAS, this harness proves the DECODER's contract —

  * every byte string, however mangled, either decodes cleanly or raises
    wire.ProtocolError.  Never a hang, never an unhandled exception
    (UnpicklingError leaking out of a recv loop kills the loop, not the
    conn), never partial dispatch of a batch;
  * the v3 native codec and the pickle fallback are INTERCHANGEABLE for
    every kind the native table claims: encoding the same frame down
    both paths and decoding must yield equal objects with equal type
    trees, or the native encoder must decline (return None) so the
    frame rides pickle — the documented subclass-fallback contract.

All generation is seeded (`--seed`), so any failure is a repro command
line, and the corpus in tests/test_wire_fuzz.py pins every frame that
ever produced a non-ProtocolError outcome.

    python scripts/wire_fuzz.py [--seed 0] [--frames 5000] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import random
import sys
from typing import Any, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from ray_tpu._private import wire, wire_native  # noqa: E402
from ray_tpu._private.task_spec import TaskSpec  # noqa: E402


# --- frame generation -------------------------------------------------------

_FIELD_POOL: Tuple[Any, ...] = (
    None, True, False, 0, 1, -7, 2 ** 40, 1.5, "", "x", "worker-3",
    b"", b"\x00\xff", (), (1, "a"), [], [1, [2]], {}, {"k": 1},
    {"nested": {"a": [1.0, None]}},
)


def _typed_value(rng: random.Random, t: Optional[type]) -> Any:
    if t is None:
        return rng.choice(_FIELD_POOL)
    if t is str:
        return rng.choice(("", "a", "task-9", "node:1"))
    if t is int:
        return rng.choice((0, 1, 4096, -1))
    if t is float:
        return rng.choice((0.0, 1.5, -2.25))
    if t is bytes:
        return rng.choice((b"", b"body", b"\x80\x05"))
    if t is list:
        return rng.choice(([], [1], ["a", {"b": 2}]))
    if t is dict:
        return rng.choice(({}, {"k": 1}))
    if t is tuple:
        return rng.choice(((), (1,)))
    return rng.choice(_FIELD_POOL)


def make_valid_frame(rng: random.Random) -> tuple:
    """A schema-legal control tuple for a random kind."""
    kind = rng.choice(sorted(wire.SCHEMAS))
    lo, hi, types = wire.SCHEMAS[kind]
    top = lo + 3 if hi is None else min(hi, lo + 3)
    n = rng.randint(lo, max(lo, top))
    fields = []
    for i in range(n):
        t = types[i] if i < len(types) else None
        fields.append(_typed_value(rng, t))
    return (kind,) + tuple(fields)


def make_spec(rng: random.Random) -> TaskSpec:
    return TaskSpec(
        task_id=f"t{rng.randrange(1 << 16):x}",
        name="fuzz_fn",
        fn_id=f"f{rng.randrange(1 << 16):x}",
        args_blob=bytes(rng.getrandbits(8) for _ in range(rng.randrange(16))),
        num_returns=rng.randint(1, 3),
        resources={"CPU": 1.0},
    )


def _encode_valid(rng: random.Random) -> bytes:
    """One physical frame (single or batch) of schema-legal sub-frames."""
    choice = rng.random()
    if choice < 0.25:
        return wire.encode(make_valid_frame(rng))
    if choice < 0.5:
        # native-capable body (may still fall back to pickle)
        obj = rng.choice(
            [
                ("task", make_spec(rng), b"blob"),
                ("pcall", make_spec(rng)),
                ("reply", rng.randrange(1 << 20), True, {"v": [1, "x"]}),
                ("heartbeat",),
                make_valid_frame(rng),
            ]
        )
        return wire.encode_native(obj)
    bodies = [
        wire.encode_body(make_valid_frame(rng))
        for _ in range(rng.randint(1, 6))
    ]
    return wire.encode_batch(bodies)


def _encode_invalid(rng: random.Random) -> bytes:
    """Frames that must be rejected with ProtocolError (or, for a few
    shapes, happen to still parse — either outcome is in-contract; what
    matters is no OTHER exception escapes)."""
    kindpick = rng.randrange(10)
    if kindpick == 0:  # unknown kind (the refs_push bug class)
        return wire.encode(("no_such_kind_" + str(rng.randrange(100)), 1))
    if kindpick == 1:  # arity violation
        kind = rng.choice(sorted(wire.SCHEMAS))
        lo, hi, _types = wire.SCHEMAS[kind]
        n = rng.choice([max(0, lo - 1), (hi + 1) if hi is not None else lo + 99])
        return wire.encode((kind,) + ("x",) * n)
    if kindpick == 2:  # leading-type violation
        kind = rng.choice(
            [k for k, s in wire.SCHEMAS.items() if any(t for t in s[2])]
        )
        lo, _hi, types = wire.SCHEMAS[kind]
        fields: List[Any] = [
            _typed_value(rng, t) for t in types[:lo]
        ] + [None] * max(0, lo - len(types))
        # poison one typed position with the wrong type
        i = rng.randrange(len([t for t in types if t]) or 1)
        fields[i] = object.__new__(object) if rng.random() < 0.2 else (
            12345 if types[i] is not int else "not-an-int"
        )
        try:
            return wire.encode((kind,) + tuple(fields[:lo]))
        except Exception:
            return wire.encode((kind,) + ("x",) * lo)
    if kindpick == 3:  # truncation of a valid frame
        buf = _encode_valid(rng)
        return buf[: rng.randrange(len(buf))]
    if kindpick == 4:  # byte-flip mutation
        buf = bytearray(_encode_valid(rng))
        for _ in range(rng.randint(1, 4)):
            pos = rng.randrange(len(buf))
            buf[pos] ^= 1 << rng.randrange(8)
        return bytes(buf)
    if kindpick == 5:  # garbage with a valid single-frame header
        return wire._HEADER + bytes(
            rng.getrandbits(8) for _ in range(rng.randrange(64))
        )
    if kindpick == 6:  # garbage, no header
        return bytes(rng.getrandbits(8) for _ in range(rng.randrange(64)))
    if kindpick == 7:  # native-body corruption
        body = bytearray(wire_native.encode(("reply", 1, True, {"a": 1})))
        mode = rng.randrange(3)
        if mode == 0:
            body[0] = rng.choice([0x00, 0x7F, 0x79])  # unknown kind id
        elif mode == 1:
            body[1] = (body[1] + 1 + rng.randrange(200)) % 256  # marshal ver
        else:
            body = body[: 2 + rng.randrange(max(1, len(body) - 2))]  # torn
        return wire._HEADER + bytes(body)
    if kindpick == 8:  # batch structural corruption
        bodies = [wire.encode_body(make_valid_frame(rng)) for _ in range(3)]
        buf = bytearray(wire.encode_batch(bodies))
        mode = rng.randrange(3)
        if mode == 0:
            buf[4] = (buf[4] + 1 + rng.randrange(20)) % 256  # count
        elif mode == 1:
            buf[wire._BATCH_HEADER.size] ^= 0xFF  # first sub-length
        else:
            buf.extend(b"\x00" * rng.randint(1, 8))  # trailing bytes
        return bytes(buf)
    # pickled-body corruption: valid header, broken pickle stream
    payload = rng.choice(
        [
            b"\x80\x05garbage",
            b"\x80\x04cnot_a_module\nNoSuchClass\n.",
            pickle.dumps(make_valid_frame(rng))[: rng.randrange(4, 24)],
            b"",
        ]
    )
    return wire._HEADER + payload


class FuzzReport:
    def __init__(self) -> None:
        self.frames = 0
        self.decoded_ok = 0
        self.protocol_errors = 0
        # (hex frame, exception repr) for every OUT-OF-CONTRACT outcome
        self.failures: List[Tuple[str, str]] = []
        self.codec_checks = 0
        self.codec_divergences: List[str] = []

    @property
    def ok(self) -> bool:
        return not self.failures and not self.codec_divergences


def check_frame(buf: bytes, report: FuzzReport) -> None:
    """Contract: decode_frames returns a list or raises ProtocolError."""
    report.frames += 1
    try:
        objs = wire.decode_frames(buf)
        assert isinstance(objs, list)
        report.decoded_ok += 1
    except wire.ProtocolError:
        report.protocol_errors += 1
    except Exception as e:  # out of contract: corpus material
        report.failures.append((bytes(buf).hex(), repr(e)))


# --- differential codec check ----------------------------------------------


def _type_tree_equal(a: Any, b: Any) -> bool:
    """Equality INCLUDING exact container/scalar types at every level —
    catches a dict subclass silently flattening to dict."""
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):
        if set(a) != set(b):
            return False
        return all(
            _type_tree_equal(k, k2) and _type_tree_equal(a[k], b[k2])
            for k, k2 in zip(sorted(a, key=repr), sorted(b, key=repr))
        )
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(
            _type_tree_equal(x, y) for x, y in zip(a, b)
        )
    if isinstance(a, TaskSpec):
        return a.__dict__ == b.__dict__
    return a == b


def differential_codec_cases(rng: random.Random) -> List[tuple]:
    """Representative frames for every kind in the native table."""
    spec = make_spec(rng)
    cases = [
        ("refop", "oid-1", "incr"),
        ("done", "t1", True, {"recv": 1.0}),
        ("done", "t1", True, b"value", {"recv": 1.0}),
        ("task", spec, b"args"),
        ("create_actor", spec, b"args"),
        ("pcall", spec),
        ("pdone", "t1", True, b"res"),
        ("task_events", [("t1", "RUNNING", 1.5)]),
        ("metrics_push", {"tasks_finished": 12.0}),
        ("refs_push", {"o1": {"count": 1}}),
        ("prof_push", {"stack;frame": 7}),
        ("spans", [("submit", 1.0, 2.0, {"t": "1"})]),
        ("reply", 42, True, {"r": [1, "x", (2.5, None)]}),
        ("reply", 43, False, "error text"),
        ("heartbeat",),
        ("heartbeat", 3),
        ("direct_seal", "o1", 128, "node-1"),
        ("direct_lineage", {"o1": ("spec", b"blob")}),
        ("lease_return", "lease-1"),
    ]
    missing = set(wire_native.KIND_IDS) - {c[0] for c in cases}
    assert not missing, f"differential cases missing kinds: {missing}"
    return cases


class _DictSub(dict):
    pass


class _ListSub(list):
    pass


def run_codec_check(rng: random.Random, report: FuzzReport) -> None:
    for obj in differential_codec_cases(rng):
        report.codec_checks += 1
        pickled = pickle.loads(pickle.dumps(obj, protocol=5))
        native_body = wire_native.encode(obj)
        if native_body is not None:
            try:
                decoded = wire_native.decode(native_body)
            except Exception as e:
                report.codec_divergences.append(
                    f"{obj[0]}: native decode failed on own encode: {e!r}"
                )
                continue
            if not _type_tree_equal(decoded, pickled):
                report.codec_divergences.append(
                    f"{obj[0]}: native {decoded!r} != pickle {pickled!r}"
                )
            # the full wire path must agree too
            via_wire = wire.decode_frames(wire._HEADER + native_body)[0]
            if not _type_tree_equal(via_wire, pickled):
                report.codec_divergences.append(
                    f"{obj[0]}: wire-path native decode diverges"
                )
        elif not _type_tree_equal(pickled, obj):
            report.codec_divergences.append(
                f"{obj[0]}: pickle fallback does not round-trip"
            )
    # Subclass contract: container subclasses in user-reachable positions
    # must DECLINE native encoding (marshal would flatten or reject them);
    # the pickle fallback preserves the exact type.
    for payload in (_DictSub(a=1), _ListSub([1, 2]), {"k": _ListSub()}):
        report.codec_checks += 1
        frame = ("reply", 1, True, payload)
        if wire_native.encode(frame) is not None:
            report.codec_divergences.append(
                f"reply with {type(payload).__name__} payload took the "
                "native path — subclass fallback contract broken"
            )
            continue
        rt = pickle.loads(pickle.dumps(frame, protocol=5))
        if not _type_tree_equal(rt, frame):
            report.codec_divergences.append(
                f"pickle fallback flattened {type(payload).__name__}"
            )
    # A spec whose user-influenced field is a subclass must also decline.
    report.codec_checks += 1
    sub_spec = make_spec(rng)
    sub_spec.runtime_env = _DictSub(env_vars={})
    if wire_native.encode(("pcall", sub_spec)) is not None:
        report.codec_divergences.append(
            "pcall with dict-subclass runtime_env took the native path"
        )


# --- driver -----------------------------------------------------------------


def run_fuzz(
    seed: int, frames: int, valid_ratio: float = 0.3
) -> FuzzReport:
    rng = random.Random(seed)
    report = FuzzReport()
    run_codec_check(rng, report)
    for _ in range(frames):
        if rng.random() < valid_ratio:
            buf = _encode_valid(rng)
        else:
            buf = _encode_invalid(rng)
        check_frame(buf, report)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--frames", type=int, default=5000)
    ap.add_argument(
        "--valid-ratio", type=float, default=0.3,
        help="fraction of generated frames that are schema-legal",
    )
    ap.add_argument("--json", action="store_true", dest="json_out")
    args = ap.parse_args(argv)

    report = run_fuzz(args.seed, args.frames, args.valid_ratio)
    if args.json_out:
        print(
            json.dumps(
                {
                    "ok": report.ok,
                    "frames": report.frames,
                    "decoded_ok": report.decoded_ok,
                    "protocol_errors": report.protocol_errors,
                    "failures": report.failures,
                    "codec_checks": report.codec_checks,
                    "codec_divergences": report.codec_divergences,
                },
                indent=2,
            )
        )
    else:
        print(
            f"frames={report.frames} decoded_ok={report.decoded_ok} "
            f"protocol_errors={report.protocol_errors} "
            f"codec_checks={report.codec_checks}"
        )
        for hexframe, exc in report.failures:
            print(f"  OUT-OF-CONTRACT: {exc} frame={hexframe}")
        for d in report.codec_divergences:
            print(f"  CODEC DIVERGENCE: {d}")
    if not report.ok:
        print(
            f"\nFAIL: {len(report.failures)} out-of-contract frame(s), "
            f"{len(report.codec_divergences)} codec divergence(s) "
            f"(seed={args.seed})"
        )
        return 1
    print(f"\nOK: contract held for {report.frames} frames (seed={args.seed})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
