"""Native hot-frame codec: struct-framed, data-only bodies — no pickle.

ray: src/ray/protobuf/common.proto — the reference's hot control frames
(task pushes, task done, ref-count ops, resource/metric reports) are typed
protobuf messages: decoding one constructs plain structs, never arbitrary
objects, and the schema is the wire contract.  Ours spoke pickle for every
frame, which costs more than it looks: pickling a TaskSpec dataclass
serializes the class reference and every FIELD NAME per task (~750 bytes,
~11µs encode + ~14µs decode), and unpickling executes the full object-
construction machinery on the single-writer head for every hot frame.

This module is the pickle-free path for the half-dozen hottest frame
kinds.  A native body is

    u8 kind_id (1..0x7F) | u8 marshal_version | marshal(payload)

where `payload` is a plain data tuple (the TaskSpec rides as a positional
FIELD TUPLE, not an object) and `marshal` is CPython's C serializer for
code-free data: ~0.8µs/spec each way, 14–17x faster than the dataclass
pickle, and — like protobuf — decoding can only ever build
None/bool/int/float/str/bytes/list/tuple/dict, never invoke a
constructor or reducer.  The first body byte disambiguates from pickle
(whose protocol-2+ streams always start with 0x80), so native and
pickled bodies coexist per frame inside the existing v3 framing; see
wire.py for the negotiation/fallback rule.

Fallback contract: `encode(obj)` returns None whenever the frame doesn't
fit the packed schema — unknown kind, unexpected arity, a payload value
marshal can't take (e.g. a scheduling-strategy instance, an exception in
a reply) — and the caller pickles instead.  Decode is strict: a
malformed native body raises ProtocolError, the same boundary rejection
a bad pickled frame gets.  Strict includes BOUNDED: marshal.loads
pre-allocates containers/strings at their declared size, so decode first
walks the stream and rejects any body whose declared sizes outrun its
bytes (see _scan_payload) — without it, an 11-byte body can make the
head zero out gigabytes.  RAY_TPU_WIRE_GUARD=0 disables the walk on
trusted fabrics.
"""

from __future__ import annotations

import marshal
import struct as _struct
from typing import Any, Optional

MARSHAL_VERSION = marshal.version

# kind_id registry.  Stable small ints — these are on the wire.  0x80 is
# forbidden (pickle's protocol marker is the discriminator byte).
KIND_IDS = {
    "refop": 1,
    "done": 2,
    "task": 3,
    "create_actor": 4,
    "pcall": 5,
    "pdone": 6,
    "task_events": 7,
    "metrics_push": 8,
    "refs_push": 9,
    "prof_push": 10,
    "spans": 11,
    # ids 12 and 13 are retired (never reused): a body carrying one is
    # refused as any unknown kind is.
    "reply": 14,
    "heartbeat": 15,
    "direct_seal": 16,
    "direct_lineage": 17,
    "lease_return": 18,
}
_ID_KINDS = {v: k for k, v in KIND_IDS.items()}

# Kinds whose payload _payload()/decode() shape at an EXACT extra-field
# arity (everything else passes obj[1:] through unchanged, so the wire
# schema alone bounds it).  The wire-schema lint cross-checks this table
# against wire.SCHEMAS — drift between the two is a frame that encodes
# here and fails validation there.
NATIVE_ARITIES = {
    "task": 2,          # (spec, blob)
    "create_actor": 2,  # (spec, blob)
    "pcall": 1,         # (spec,)
    "reply": 3,         # (req_id, ok, value)
}

# TaskSpec rides as a positional field tuple: the field list is resolved
# once (import order: task_spec has no wire dependency) and its LENGTH is
# part of the decode check — a spec tuple of any other arity is a skewed
# peer and must reject loudly, not build a shifted spec.
_SPEC_FIELDS: Optional[tuple] = None
_SPEC_GETTER = None


def _spec_fields() -> tuple:
    global _SPEC_FIELDS, _SPEC_GETTER
    if _SPEC_FIELDS is None:
        import dataclasses
        import operator

        from ray_tpu._private.task_spec import TaskSpec

        _SPEC_FIELDS = tuple(f.name for f in dataclasses.fields(TaskSpec))
        _SPEC_GETTER = operator.itemgetter(*_SPEC_FIELDS)
    return _SPEC_FIELDS


def spec_to_tuple(spec) -> Optional[tuple]:
    """Positional field tuple, or None when a field can't ride marshal
    (strategy objects fall back to pickle; plain str/None strategies — the
    hot shapes — pack).  itemgetter walks the instance dict at C speed —
    this runs once per task push."""
    if _SPEC_GETTER is None:
        _spec_fields()
    try:
        return _SPEC_GETTER(spec.__dict__)
    except (KeyError, AttributeError, TypeError):
        # Skewed/subclassed instance — or not a spec object at all (a
        # malformed frame must DECLINE to pickle, never crash encode).
        return None


def tuple_to_spec(t: tuple):
    from ray_tpu._private.task_spec import TaskSpec

    fields = _spec_fields()
    if len(t) != len(fields):
        raise ProtocolError(
            f"native TaskSpec has {len(t)} fields, this build expects "
            f"{len(fields)} — mixed-version cluster"
        )
    spec = TaskSpec.__new__(TaskSpec)
    spec.__dict__.update(zip(fields, t))
    return spec


class ProtocolError(ConnectionError):
    """Raised on malformed native bodies (wire.py re-exports its own; this
    subclass keeps the module import-light and is caught as
    ConnectionError everywhere conns die)."""


_SAFE_SCALARS = (type(None), bool, int, float, str, bytes)


def _data_safe(v, _depth: int = 0) -> bool:
    """EXACT-type recursive check for user-influenced payload positions.
    marshal silently serializes container SUBCLASSES as their base type
    (a SampleBatch(dict) would come back a plain dict); positions our own
    code builds are exact by construction, but user-reachable ones
    (reply values, runtime_env) must verify or fall back to pickle."""
    t = type(v)
    if t in _SAFE_SCALARS:
        return True
    if _depth > 16:
        return False
    if t is dict:
        return all(
            _data_safe(k, _depth + 1) and _data_safe(x, _depth + 1)
            for k, x in v.items()
        )
    if t is list or t is tuple:
        return all(_data_safe(x, _depth + 1) for x in v)
    return False


def _spec_safe(spec) -> bool:
    """The user-influenced spec fields (everything else is built by the
    submit machinery with exact types; args_blob is opaque bytes)."""
    return (
        type(spec.resources) is dict
        and (spec.runtime_env is None or _data_safe(spec.runtime_env))
    )


def _payload(obj: tuple) -> Any:
    """Frame tuple -> marshal-ready payload, or the _UNSUPPORTED sentinel.
    Per-kind shaping keeps decode strict and specs positional."""
    kind = obj[0]
    if kind in ("task", "create_actor"):
        # ("task", spec, blob)
        if len(obj) != 3:
            return _UNSUPPORTED
        st = spec_to_tuple(obj[1])
        if st is None or not _spec_safe(obj[1]):
            return _UNSUPPORTED
        return (st, obj[2])
    if kind == "pcall":
        # ("pcall", spec) — the direct-push twin of "task"
        if len(obj) != 2:
            return _UNSUPPORTED
        st = spec_to_tuple(obj[1])
        if st is None or not _spec_safe(obj[1]):
            return _UNSUPPORTED
        return (st,)
    if kind == "reply":
        # ("reply", req_id, ok, value) — value is op-defined and may be
        # or contain anything (exceptions, refs, user returns).
        if len(obj) != 4 or not _data_safe(obj[3]):
            return _UNSUPPORTED
        return obj[1:]
    return obj[1:]


_UNSUPPORTED = object()


def encode(obj: Any) -> Optional[bytes]:
    """Native body for a control tuple, or None -> caller pickles."""
    if not (isinstance(obj, tuple) and obj and isinstance(obj[0], str)):
        return None
    kid = KIND_IDS.get(obj[0])
    if kid is None:
        return None
    payload = _payload(obj)
    if payload is _UNSUPPORTED:
        return None
    try:
        body = marshal.dumps(payload, 2)
    except ValueError:
        return None  # a field marshal can't take: pickle fallback
    return bytes((kid, MARSHAL_VERSION)) + body


def is_native(body) -> bool:
    return bool(body) and body[0] != 0x80


# Allocation guard.  marshal.loads allocates each container/string at its
# DECLARED size before reading a single element: an 11-byte body whose
# payload is `28 00 10 00 20` (tuple opcode, count 0x20100000) makes
# r_object zero out a ~4 GB tuple on the single-writer head — a one-frame
# allocation bomb from any corrupted or hostile peer.  _scan_payload
# walks the stream first and verifies every declared length/count fits
# the bytes actually present (each element costs >= 1 byte, so a count
# can never exceed the remaining payload), keeping loads' allocation
# O(len(body)).  Codes outside the data subset our encoder (marshal
# version 2) emits — refs, code objects, legacy spellings — reject: we
# never produce them, so receiving one is skew or corruption, not data.
#
# Action table, indexed by type-code byte: >= 0 is a fixed byte count to
# skip; negatives select a header shape.  FLAG_REF'd codes (0x80 bit) and
# unknown codes stay _A_BAD.
_A_BAD, _A_STR32, _A_STR8, _A_SEQ, _A_DICT, _A_NULL, _A_LONG = (
    -1, -2, -3, -4, -5, -6, -7,
)
_M_ACTIONS = [_A_BAD] * 256
for _c in b"NTFS.":      # None / True / False / StopIteration / Ellipsis
    _M_ACTIONS[_c] = 0
_M_ACTIONS[ord("i")] = 4    # int32
_M_ACTIONS[ord("I")] = 8    # int64 (legacy)
_M_ACTIONS[ord("g")] = 8    # binary float
_M_ACTIONS[ord("y")] = 16   # binary complex
for _c in b"sutaA":      # bytes / unicode / interned / ascii: u32 len
    _M_ACTIONS[_c] = _A_STR32
for _c in b"zZ":         # short ascii: u8 len
    _M_ACTIONS[_c] = _A_STR8
for _c in b"([<>":       # tuple / list / set / frozenset: i32 count
    _M_ACTIONS[_c] = _A_SEQ
_M_ACTIONS[ord("{")] = _A_DICT   # dict: items until NULL key
_M_ACTIONS[ord("0")] = _A_NULL   # TYPE_NULL: dict terminator only
_M_ACTIONS[ord("l")] = _A_LONG   # long: i32 digit count, 2 bytes each
del _c

_I32 = _struct.Struct("<i")


def _scan_payload(data) -> None:
    """Bounds-check a marshal stream without materializing it.

    Linear walk over the type-code stream: every declared string length
    and container count must fit the bytes that remain (an element costs
    >= 1 byte), and the CUMULATIVE declared allocation must stay O(n) —
    nested containers each bounded by `remaining` could otherwise still
    sum to O(n^2).  Grammar (matching counts, balanced dicts) is left to
    marshal.loads, which raises cleanly once allocation is bounded; this
    pass only guarantees loads can't allocate disproportionately and
    that only data-subset codes appear."""
    if type(data) is not bytes:
        data = bytes(data)
    n = len(data)
    pos = 0
    alloc = 0
    limit = 32 * n + 4096  # declared slots+bytes a legit body could need
    actions = _M_ACTIONS
    unpack = _I32.unpack_from
    while pos < n:
        act = actions[data[pos]]
        pos += 1
        if act >= 0:  # fixed-width scalar; overrun lands on the final check
            pos += act
            continue
        if act == _A_STR32:
            if pos + 4 > n:
                raise ProtocolError("truncated marshal string header")
            ln, = unpack(data, pos)
            pos += 4
            if ln < 0 or ln > n - pos:
                raise ProtocolError(
                    f"marshal string declares {ln} bytes, {n - pos} remain"
                )
            pos += ln
            continue
        if act == _A_SEQ:
            if pos + 4 > n:
                raise ProtocolError("truncated marshal container header")
            cnt, = unpack(data, pos)
            pos += 4
            if cnt < 0 or cnt > n - pos:
                raise ProtocolError(
                    f"marshal container declares {cnt} items, only "
                    f"{n - pos} bytes remain — allocation bomb"
                )
            alloc += cnt * 8
            if alloc > limit:
                raise ProtocolError(
                    "marshal body declares allocations far beyond its size"
                )
            continue
        if act == _A_STR8:
            if pos >= n:
                raise ProtocolError("truncated marshal string header")
            pos += 1 + data[pos]
            continue
        if act == _A_LONG:
            if pos + 4 > n:
                raise ProtocolError("truncated marshal long")
            cnt, = unpack(data, pos)
            pos += 4 + 2 * (cnt if cnt >= 0 else -cnt)
            continue
        if act == _A_DICT or act == _A_NULL:
            continue
        code = data[pos - 1]
        if code & 0x80:
            raise ProtocolError(
                "marshal ref flag outside the wire data subset"
            )
        raise ProtocolError(
            f"marshal type code {code:#x} outside the wire data subset"
        )
    if pos != n:
        raise ProtocolError("truncated marshal body")


# Resolved once per process (config.get caches too; this skips even the
# call).  RAY_TPU_WIRE_GUARD=0 trusts the fabric and decodes unguarded.
_GUARD: Optional[bool] = None


def _guard_enabled() -> bool:
    global _GUARD
    if _GUARD is None:
        from ray_tpu._private import config

        _GUARD = bool(config.get("wire_guard"))
    return _GUARD


def decode(body) -> Any:
    """Strict decode of a native body back into the control tuple."""
    if len(body) < 3:
        raise ProtocolError("truncated native frame body")
    kid, mver = body[0], body[1]
    kind = _ID_KINDS.get(kid)
    if kind is None:
        raise ProtocolError(f"unknown native frame kind id {kid}")
    if mver != MARSHAL_VERSION:
        raise ProtocolError(
            f"native codec version skew: peer marshal v{mver}, this "
            f"interpreter v{MARSHAL_VERSION} — run matching Pythons or "
            "set RAY_TPU_WIRE_NATIVE=0"
        )
    guard = _GUARD
    if guard is None:
        guard = _guard_enabled()
    if guard:
        _scan_payload(body[2:])
    try:
        payload = marshal.loads(bytes(body[2:]))
    except (ValueError, EOFError, TypeError, MemoryError) as e:
        # MemoryError: with the guard off, a bomb body that fails its
        # giant allocation still dies as a boundary rejection.
        raise ProtocolError(f"malformed native {kind!r} body: {e}") from None
    if not isinstance(payload, tuple):
        raise ProtocolError(f"native {kind!r} payload is not a tuple")
    if kind in ("task", "create_actor"):
        if len(payload) != 2 or not isinstance(payload[0], tuple):
            raise ProtocolError(f"native {kind!r} payload shape")
        return (kind, tuple_to_spec(payload[0]), payload[1])
    if kind == "pcall":
        if len(payload) != 1 or not isinstance(payload[0], tuple):
            raise ProtocolError("native 'pcall' payload shape")
        return (kind, tuple_to_spec(payload[0]))
    return (kind,) + payload
