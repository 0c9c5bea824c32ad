"""Versioned, schema-validated control-plane framing + frame coalescing.

ray: src/ray/protobuf/*.proto — the reference's control plane is typed
protobuf-over-gRPC with versioned services.  Rounds 1-3 here sent raw
pickled tuples: no version negotiation (a mixed-version cluster fails
with arbitrary unpickling errors mid-stream) and no message validation
(any tuple off an authenticated socket was dispatched on faith).

This module gives every control connection:

  * a frame header (magic + u16 protocol version) on EVERY frame —
    a peer speaking a different protocol version fails at the first recv
    with a clean ProtocolError naming both versions, instead of a pickle
    traceback deep in a handler;
  * a per-message schema registry: str-kinded control tuples are checked
    for known kind, arity bounds, and leading field types at decode time —
    unknown or malformed control messages are rejected at the boundary;
  * serialization confined to the framed body — since v3 the hot control
    kinds ride NATIVE bodies (wire_native.py: struct-framed marshal data
    tuples, no pickle; the first body byte discriminates, 0x80 = pickle)
    and everything else stays pickled (the authkey HMAC gates the bytes
    before any decode, as before), with raw passthrough (`send_bytes` /
    `recv_bytes` / `fileno`) for the object-transfer body path, which is
    not serialized here at all.

Protocol v2 adds the BATCH frame: one physical write carrying N
schema-validated sub-frames.  A profile of the head under load (sandbox,
1 vCPU) showed its steady state is raw syscall traffic — one posix.write
and one epoll wakeup per logical control message (the reference amortizes
this for free through gRPC stream buffering and its batched syncer/pubsub
messages, src/ray/ray_syncer/ + pubsub/publisher.h).  `BatchingConn` is the sender
side: messages queue into a pending buffer and flush on

  (a) size      — pending bytes reach RAY_TPU_WIRE_BATCH_BYTES (~64KB);
  (b) linger    — a short background sweep (RAY_TPU_WIRE_FLUSH_US,
                  ~200µs) bounds the delay of fire-and-forget frames;
  (c) explicit  — `flush()` / `flush_dirty()` BEFORE ANY BLOCKING WAIT,
                  so latency-sensitive request/reply paths never stall
                  behind the linger.  This is a RULE for new send paths:
                  queue freely, but flush before you park.

Per-sub-frame ordering, schema validation, and `wire.send`/`wire.recv`
fault-injection semantics are preserved: a `drop` clause drops an
individual sub-frame, never the whole batch; the new `wire.flush` point
covers the physical write (crash = batch lost mid-flight).  A malformed
sub-frame rejects the WHOLE batch at the boundary (no partial dispatch),
and a truncated batch body is a clean ProtocolError.

TypedConn wraps a multiprocessing.connection.Connection and preserves its
surface (send/recv/poll/fileno/close), so `multiprocessing.connection
.wait` and the recv_into fast path keep working unchanged; decoded batch
sub-frames queue receiver-side and `recv()` hands them out in order
(`poll()` reports them, `pending_frames()` exposes the count so drain
loops never strand a buffered tail behind an idle socket).
"""

from __future__ import annotations

import os
import pickle
import struct
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import config as _config
from ray_tpu._private import faults
from ray_tpu._private import lock_watchdog
from ray_tpu._private import wire_native


def _kind(obj: Any) -> Optional[str]:
    """Control-message kind for fault `match=` scoping (None for payload
    frames) — only computed when injection is enabled."""
    if isinstance(obj, tuple) and obj and isinstance(obj[0], str):
        return obj[0]
    return None

MAGIC = b"RT"
# Batch frames carry their own magic so a v2 receiver can tell one
# physical write of N sub-frames from a plain single frame; a v1 receiver
# fails both shapes with the same clean bad-magic/version error.
MAGIC_BATCH = b"RB"
# v3: frame BODIES may be native (wire_native.py: struct-framed marshal,
# no pickle) for the hot control kinds.  The first body byte
# discriminates — pickle protocol-2+ streams always start with 0x80,
# native bodies with their kind id (1..0x7F) — so pickled and native
# bodies coexist per conn and per batch.  Negotiation IS the version
# fence: every frame header carries v3, an older peer rejects the first
# frame with the clean mismatch error naming both versions, and a v3
# peer by contract decodes both body forms.  Fallback is per-frame: any
# message whose kind has no native codec, or whose payload doesn't fit
# the packed schema (strategy objects, exceptions in replies), pickles
# exactly as in v2 (RAY_TPU_WIRE_NATIVE=0 forces the pickle path for
# every frame).
PROTOCOL_VERSION = 3
_HEADER = struct.pack("<2sH", MAGIC, PROTOCOL_VERSION)
_BATCH_HEADER = struct.Struct("<2sHI")  # magic, version, sub-frame count
_SUBLEN = struct.Struct("<I")


class ProtocolError(ConnectionError):
    """Frame failed version or schema validation."""


# kind -> (min_extra_fields, max_extra_fields, leading_field_types)
# `None` in the types tuple = any.  Extra fields beyond the typed prefix
# are unconstrained (payload positions).  max_extra None = unbounded.
SCHEMAS: Dict[str, Tuple[int, Optional[int], tuple]] = {
    # worker/driver -> head.  ready's optional 5th extra field is the
    # reconnect-time actor announcement (reconciliation handshake); the
    # optional 6th is the sender's time.time() at send — the head's
    # clock-offset estimate for merging this process's spans/task events
    # into one cluster timeline; the optional 7th is the executor's
    # relayed-work announcement (task ids still held) — the head
    # re-drives in-flight work missing from it.
    "ready": (3, 7, (str, int)),
    "actor_announce": (1, 1, (list,)),
    "env_failed": (2, 2, (str, str)),
    # done's optional 4th extra field is the executor-side stage timing
    # ({"recv","start","end"} wall-clock stamps) the head folds into the
    # task's lifecycle record (clock-offset-corrected at ingest).
    "done": (3, 4, (str,)),
    "refop": (2, 2, (str, str)),
    "req": (3, 3, (int, str)),
    # object_copied's optional 3rd extra field is the transfer path the
    # puller used ("pull" sealed source / "relay" in-flight feed) — the
    # owner releases the right transfer-plan slot and labels the ledger
    # event with it.
    "object_copied": (2, 3, (str, int)),
    "actor_exit": (1, 1, (str,)),
    "fence_ack": (1, 1, (str,)),
    "direct_seal": (3, 3, (str, int)),
    "direct_lineage": (1, 1, ()),
    "promote": (3, 3, (str,)),
    "promote_error": (2, 2, (str,)),
    "seal_ow": (3, 3, (str, int)),
    "put_ow": (3, 3, (str,)),
    "task_events": (1, 1, (list,)),
    "spans": (1, 1, (list,)),
    "wire_stats": (1, 1, (dict,)),
    # Periodic per-process telemetry snapshot (util/metrics registry +
    # wire counters + internal gauges) — droppable oneway, aggregated
    # into the head's TelemetrySink (telemetry.py).
    "metrics_push": (1, 1, (dict,)),
    # Periodic per-process live-ref table (refs.py snapshot + transport
    # ownership) — the worker leg of the object ledger (`ray_tpu memory`),
    # droppable like metrics_push.
    "refs_push": (1, 1, (dict,)),
    # Periodic per-process collapsed-stack table (profiler.py snapshot,
    # cumulative since start) — the worker leg of `ray_tpu profile`.
    # Droppable like metrics_push: a lost push costs freshness only.
    "prof_push": (1, 1, (dict,)),
    # cross-process pubsub (pubsub.py remote delivery)
    "subscribe": (2, 3, (str,)),
    "unsubscribe": (2, 2, (str,)),
    "pub": (3, 3, (str,)),
    "lease_return": (1, 1, (str,)),
    "sync": (0, 1, ()),
    "kv_fetch": (1, 1, (str,)),
    # object_fetch's optional 2nd extra field flags a relay-capable
    # receiver (it understands the crc-framed "relay" body).
    "object_fetch": (1, 2, (str,)),
    # driver hello's optional 3rd extra = sender clock (same offset
    # estimate the worker ready carries).
    "driver": (2, 3, (str,)),
    "driver_store": (2, 2, ()),
    # head -> worker
    "reply": (3, 3, (int,)),
    "task": (2, 2, ()),
    "create_actor": (2, 2, ()),
    "fence": (1, 1, (str,)),
    "kill": (0, 0, ()),
    "shutdown": (0, 1, ()),
    # zygote fork server (zygote.py)
    "zygote": (1, 1, (int,)),
    "fork": (4, 4, (str, dict, str, str)),
    "forked": (2, 2, (str, int)),
    # daemon -> zygote: the node arena's open fd follows this frame as an
    # SCM_RIGHTS ancillary message on the same AF_UNIX pipe (netutil
    # send_fd/recv_fd); forked workers inherit the descriptor and map the
    # store without touching the path.
    "arena_fd": (1, 1, (str,)),
    # daemon <-> head
    "daemon": (3, 3, (str,)),
    "heartbeat": (0, 1, ()),
    # worker_exited rides two channels: zygote -> daemon sends (wid, rc),
    # daemon -> head adds the oom flag (wid, rc, oom).
    "worker_exited": (2, 3, (str,)),
    "worker_oom_killed": (1, None, (str,)),
    "log_lines": (3, 3, (str, str, list)),
    "spawn_worker": (2, 2, (str,)),
    "kill_worker": (1, 1, (str,)),
    "delete_object": (1, 1, (str,)),
    # peer transport
    "pcall": (1, 2, ()),
    "pcancel": (1, 1, (str,)),
    "pdone": (3, 3, (str,)),
    # transfer plane / handshake replies
    "ok": (1, 1, (int,)),
    # relay reply header: (total_bytes, chunk_bytes) — body is crc-framed
    # chunks streamed as the serving board's watermark advances.
    "relay": (2, 2, (int, int)),
    "missing": (0, 0, ()),
    "driver_ack": (1, 1, (dict,)),
    "protocol_error": (1, 2, ()),
    # external-env policy serving (rllib/policy_client.py)
    "start_episode": (1, 1, ()),
    "get_action": (3, 3, (str,)),
    "log_returns": (2, 2, (str, float)),
    "end_episode": (2, 3, (str,)),
    "error": (1, 2, ()),
}


def _validate(obj: Any) -> None:
    """Schema-check str-kinded control tuples; other values (one-shot
    payload replies: kv bytes, ack dicts) pass through untyped."""
    if not (isinstance(obj, tuple) and obj and isinstance(obj[0], str)):
        return
    spec = SCHEMAS.get(obj[0])
    if spec is None:
        raise ProtocolError(f"unknown control message kind {obj[0]!r}")
    lo, hi, types = spec
    n = len(obj) - 1
    if n < lo or (hi is not None and n > hi):
        raise ProtocolError(
            f"control message {obj[0]!r} has {n} fields, expected "
            f"[{lo}, {hi if hi is not None else 'inf'}]"
        )
    for i, t in enumerate(types):
        if t is not None and not isinstance(obj[i + 1], t):
            raise ProtocolError(
                f"control message {obj[0]!r} field {i} is "
                f"{type(obj[i + 1]).__name__}, expected {t.__name__}"
            )


def _check_version(magic: bytes, version: int) -> None:
    if magic not in (MAGIC, MAGIC_BATCH):
        raise ProtocolError(
            "peer is not speaking the ray_tpu control protocol "
            f"(bad magic {magic!r})"
        )
    if version != PROTOCOL_VERSION:
        raise ProtocolError(
            f"protocol version mismatch: peer speaks v{version}, this "
            f"process speaks v{PROTOCOL_VERSION} — upgrade the older side"
        )


def encode_body(obj: Any) -> bytes:
    """Body bytes for one control message: native (struct-framed marshal,
    wire_native.py) for the hot kinds when the knob allows, else pickle.
    The first body byte self-describes which (0x80 = pickle)."""
    if _config.get("wire_native"):
        body = wire_native.encode(obj)
        if body is not None:
            _count_codec(native_encodes=1)
            return body
    _count_codec(pickle_encodes=1)
    return pickle.dumps(obj, protocol=5)


# Allocation guard for the pickle path (RAY_TPU_WIRE_GUARD, shared with
# the marshal-side guard in wire_native._scan_payload).  pickle.loads has
# the same pre-allocation hazard marshal does: counted opcodes
# (BINBYTES8, BYTEARRAY8 — the latter ZERO-FILLS) allocate the declared
# size before checking the buffer holds it, and LONG_BINPUT grows the
# memo table to the declared index — so a single byte flip in a pickled
# body can make the decoder commit gigabytes.  The scan walks the opcode
# stream, bounds every declared length/index against the bytes actually
# present, and admits only opcodes a protocol-2+ pickler emits (our
# encoder always writes protocol 5; a text-era opcode in a frame body is
# corruption, not data).  It bounds ALLOCATION only — pickle still
# executes reducers on scan-clean bodies; the trust model is unchanged.
_PK_BAD, _PK_C1, _PK_C4, _PK_C8, _PK_PUT4 = -1, -2, -3, -4, -5
_PK_ACTIONS = [_PK_BAD] * 256
for _op, _skip in {
    0x80: 1,          # PROTO
    0x95: 8,          # FRAME (length hint; loads tolerates mismatch)
    0x2E: 0,          # STOP
    0x28: 0, 0x30: 0, 0x31: 0, 0x32: 0,        # MARK POP POP_MARK DUP
    0x4E: 0, 0x88: 0, 0x89: 0,                 # NONE NEWTRUE NEWFALSE
    0x29: 0, 0x85: 0, 0x86: 0, 0x87: 0, 0x74: 0,  # tuples
    0x5D: 0, 0x61: 0, 0x65: 0,                 # EMPTY_LIST APPEND APPENDS
    0x7D: 0, 0x73: 0, 0x75: 0,                 # EMPTY_DICT SETITEM(S)
    0x8F: 0, 0x90: 0, 0x91: 0,                 # sets
    0x52: 0, 0x62: 0, 0x81: 0, 0x92: 0,        # REDUCE BUILD NEWOBJ(_EX)
    0x93: 0, 0x94: 0,                          # STACK_GLOBAL MEMOIZE
    0x4A: 4, 0x4B: 1, 0x4D: 2, 0x47: 8,        # BININT/1/2 BINFLOAT
    0x68: 1, 0x6A: 4, 0x71: 1,                 # BINGET LONG_BINGET BINPUT
    0x51: 0, 0x97: 0, 0x98: 0,  # BINPERSID NEXT_BUFFER READONLY_BUFFER
}.items():
    _PK_ACTIONS[_op] = _skip
_PK_ACTIONS[0x8C] = _PK_C1   # SHORT_BINUNICODE
_PK_ACTIONS[0x58] = _PK_C4   # BINUNICODE
_PK_ACTIONS[0x8D] = _PK_C8   # BINUNICODE8
_PK_ACTIONS[0x43] = _PK_C1   # SHORT_BINBYTES
_PK_ACTIONS[0x42] = _PK_C4   # BINBYTES
_PK_ACTIONS[0x8E] = _PK_C8   # BINBYTES8
_PK_ACTIONS[0x96] = _PK_C8   # BYTEARRAY8
_PK_ACTIONS[0x8A] = _PK_C1   # LONG1
_PK_ACTIONS[0x8B] = _PK_C4   # LONG4
_PK_ACTIONS[0x72] = _PK_PUT4  # LONG_BINPUT: memo grows to the index
del _op, _skip


def _scan_pickle(data) -> None:
    """Bounds-check a pickled body's opcode stream before pickle.loads.
    Raises ProtocolError when a declared length/index outruns the bytes
    present or an opcode outside the binary-protocol subset appears.
    Stops at STOP like loads does; a stream that ends without STOP is
    left for loads to reject (it can't over-allocate once every counted
    opcode is bounded)."""
    if type(data) is not bytes:
        data = bytes(data)
    n = len(data)
    pos = 0
    actions = _PK_ACTIONS
    while pos < n:
        op = data[pos]
        act = actions[op]
        pos += 1
        if act > 0:
            pos += act
            continue
        if act == 0:
            if op == 0x2E:  # STOP: loads ignores anything after it
                return
            continue
        if act == _PK_C1:
            if pos >= n:
                raise ProtocolError("truncated pickle opcode argument")
            ln = data[pos]
            pos += 1 + ln
            continue
        if act == _PK_C4 or act == _PK_C8:
            width = 4 if act == _PK_C4 else 8
            if pos + width > n:
                raise ProtocolError("truncated pickle opcode argument")
            ln = int.from_bytes(data[pos:pos + width], "little")
            pos += width
            if ln > n - pos:
                raise ProtocolError(
                    f"pickle opcode {op:#x} declares {ln} bytes, "
                    f"{n - pos} remain — allocation bomb"
                )
            pos += ln
            continue
        if act == _PK_PUT4:
            if pos + 4 > n:
                raise ProtocolError("truncated pickle opcode argument")
            idx = int.from_bytes(data[pos:pos + 4], "little")
            if idx > n:
                raise ProtocolError(
                    f"pickle memo index {idx} outruns the body — the memo "
                    "table would be grown to it"
                )
            pos += 4
            continue
        raise ProtocolError(
            f"pickle opcode {op:#x} outside the binary-protocol subset"
        )


def decode_body(body) -> Any:
    """Decode + schema-validate ONE sub-frame body (pickled or native)."""
    if body and body[0] != 0x80:
        try:
            obj = wire_native.decode(body)
        except wire_native.ProtocolError as e:
            raise ProtocolError(str(e)) from None
        _count_codec(native_decodes=1)
    else:
        # A corrupt pickled body raises UnpicklingError/EOFError/etc. —
        # wrap in ProtocolError so a torn frame is a boundary rejection
        # (conn death), never an unhandled exception in a recv loop.
        if wire_native._guard_enabled():
            _scan_pickle(body)
        try:
            obj = pickle.loads(body)
        except ProtocolError:
            raise
        except Exception as e:
            raise ProtocolError(f"malformed pickled frame body: {e!r}") from None
        _count_codec(pickle_decodes=1)
    _validate(obj)
    return obj


def encode(obj: Any) -> bytes:
    return _HEADER + pickle.dumps(obj, protocol=5)


def encode_native(obj: Any) -> bytes:
    """One full frame using the body codec (native when possible)."""
    return _HEADER + encode_body(obj)


def encode_batch(bodies: List[bytes]) -> bytes:
    """One physical frame carrying N already-pickled sub-frame bodies."""
    parts = [_BATCH_HEADER.pack(MAGIC_BATCH, PROTOCOL_VERSION, len(bodies))]
    for b in bodies:
        parts.append(_SUBLEN.pack(len(b)))
        parts.append(b)
    return b"".join(parts)


def decode(buf) -> Any:
    """Decode ONE single-kind frame (handshakes, tests).  Batch frames go
    through decode_frames — a batch here would be a framing bug."""
    objs = decode_frames(buf)
    if len(objs) != 1:
        raise ProtocolError(
            f"expected a single control frame, got a batch of {len(objs)}"
        )
    return objs[0]


def decode_frames(buf) -> List[Any]:
    """Decode a physical frame into its validated sub-frames, in order.

    A single frame yields [obj].  For a batch, the framing is checked
    whole first (a truncated batch rejects whole: the shape a mid-batch
    sender crash leaves behind), then EVERY sub-frame is decoded and
    schema-validated before any is returned: one malformed sub-frame
    rejects the whole batch at the boundary (no partial dispatch).
    Bodies may be pickled or native (v3) — decode_body dispatches per
    body."""
    if len(buf) < 4:
        raise ProtocolError("short control frame")
    magic, version = struct.unpack_from("<2sH", buf, 0)
    _check_version(magic, version)
    view = memoryview(buf)
    if magic == MAGIC:
        return [decode_body(view[4:])]
    if len(buf) < _BATCH_HEADER.size:
        raise ProtocolError("truncated batch frame (short header)")
    _m, _v, count = _BATCH_HEADER.unpack_from(buf, 0)
    bodies: List[memoryview] = []
    off = _BATCH_HEADER.size
    for _ in range(count):
        if off + _SUBLEN.size > len(buf):
            raise ProtocolError(
                f"truncated batch frame ({len(bodies)}/{count} sub-frames "
                "before the body ran out)"
            )
        (n,) = _SUBLEN.unpack_from(buf, off)
        off += _SUBLEN.size
        if off + n > len(buf):
            raise ProtocolError(
                f"truncated batch frame (sub-frame {len(bodies)} declares "
                f"{n} bytes, {len(buf) - off} remain)"
            )
        bodies.append(view[off:off + n])
        off += n
    if off != len(buf):
        raise ProtocolError(
            f"batch frame has {len(buf) - off} trailing bytes after "
            f"{count} sub-frames"
        )
    return [decode_body(b) for b in bodies]


# ---------------------------------------------------------------------------
# per-process wire statistics
#
# Counting is always on (a few int adds under a lock already serializing
# the physical write path); EXPOSURE through the state API / dashboard /
# bench output is gated on RAY_TPU_WIRE_STATS=1.  logical_frames counts
# control messages handed to send layers; physical_writes counts actual
# send_bytes calls — their ratio is the coalescing factor the
# acceptance bar is measured by.

_stats_lock = threading.Lock()
_stats_pid = os.getpid()
_STAT_KEYS = (
    "logical_frames",
    "physical_writes",
    "bytes_written",
    "batched_frames",   # logical frames that rode a multi-frame batch
    "flush_size",
    "flush_linger",
    "flush_explicit",
    "flush_direct",     # unbatched TypedConn.send / single passthrough
    # codec split: how many control bodies this process pickled vs
    # native-encoded (and the decode twins).  pickle_* per task is the
    # deterministic acceptance metric of the native-codec work — host
    # noise can fake an ops/s win, a counter can't.
    "pickle_encodes",
    "pickle_decodes",
    "native_encodes",
    "native_decodes",
)
_stats: Dict[str, int] = {k: 0 for k in _STAT_KEYS}


def _count(n_logical: int, n_bytes: int, reason: str) -> None:
    with _stats_lock:
        _stats["logical_frames"] += n_logical
        _stats["physical_writes"] += 1
        _stats["bytes_written"] += n_bytes
        if n_logical > 1:
            _stats["batched_frames"] += n_logical
        key = f"flush_{reason}"
        if key in _stats:
            _stats[key] += 1


def _count_codec(
    pickle_encodes: int = 0, pickle_decodes: int = 0,
    native_encodes: int = 0, native_decodes: int = 0,
) -> None:
    with _stats_lock:
        _stats["pickle_encodes"] += pickle_encodes
        _stats["pickle_decodes"] += pickle_decodes
        _stats["native_encodes"] += native_encodes
        _stats["native_decodes"] += native_decodes


def stats() -> Dict[str, int]:
    """Snapshot of this process's wire counters."""
    _fork_check()
    with _stats_lock:
        return dict(_stats)


def stats_enabled() -> bool:
    return bool(_config.get("wire_stats"))


def _reset_stats_for_tests() -> None:
    with _stats_lock:
        for k in _STAT_KEYS:
            _stats[k] = 0


# ---------------------------------------------------------------------------
# background linger flusher
#
# One daemon thread per process sweeps dirty BatchingConns after a short
# linger (RAY_TPU_WIRE_FLUSH_US).  It is the BOUND on fire-and-forget
# latency, not the main flush path: bursts flush on size, and every
# blocking wait flushes explicitly first.  Forked children (zygote
# workers, fork-start daemons) inherit the module state but not the
# thread — _fork_check() detects the pid change and resets.

_dirty_lock = threading.Lock()
_dirty: "set[BatchingConn]" = set()
_dirty_event = threading.Event()
_flusher_started = False


def _linger_s() -> float:
    return max(_config.get("wire_flush_us"), 0) / 1e6


def _fork_check() -> None:
    global _stats_pid, _flusher_started
    if os.getpid() == _stats_pid:
        return
    with _dirty_lock, _stats_lock:
        if os.getpid() == _stats_pid:
            return
        _stats_pid = os.getpid()
        _flusher_started = False  # parent's thread did not survive the fork
        _dirty.clear()            # nor did its conns
        for k in _STAT_KEYS:
            _stats[k] = 0


def _note_dirty(bc: "BatchingConn") -> None:
    global _flusher_started
    _fork_check()
    with _dirty_lock:
        was_empty = not _dirty
        _dirty.add(bc)
        if not _flusher_started:
            _flusher_started = True
            threading.Thread(
                target=_flusher_loop, daemon=True, name="raytpu-wire-flush"
            ).start()
        if was_empty:
            # Arm the linger sweep only on the empty->dirty transition; an
            # explicit flush_dirty() that empties the set DISARMS it
            # (_take_dirty clears the event under the same lock), so the
            # common send-then-flush-before-park pattern never wakes the
            # flusher thread at all — per-op thread wakeups were a
            # measured ~2x latency hit on a 1-vCPU host.
            _dirty_event.set()


def _forget_dirty(bc: "BatchingConn") -> None:
    with _dirty_lock:
        _dirty.discard(bc)


def _take_dirty() -> List["BatchingConn"]:
    with _dirty_lock:
        out = list(_dirty)
        _dirty.clear()
        # Atomic with the emptying: a concurrent _note_dirty serializes on
        # _dirty_lock, so it either re-arms after this clear or found the
        # set non-empty (no arm needed — we are taking its conn).
        _dirty_event.clear()
    return out


def _flusher_loop() -> None:
    while True:
        _dirty_event.wait()
        linger = _linger_s()
        if linger > 0:
            time.sleep(linger)
        # _take_dirty disarms the event; usually an explicit flush already
        # did both and this sweep finds nothing (then goes back to sleep
        # without having cost the hot path anything).
        for bc in _take_dirty():
            try:
                bc.flush(_reason="linger")
            except (OSError, ValueError):
                pass  # conn died; its owner's recv side handles it


def flush_dirty() -> None:
    """Flush every pending batch in this process NOW.  Call this before
    any blocking wait (the rule latency-sensitive paths live by) — the
    io loop, request/reply muxes, and executor idle points all do."""
    for bc in _take_dirty():
        try:
            bc.flush(_reason="explicit")
        except (OSError, ValueError):
            pass


def flush_conn(conn) -> None:
    """Flush one conn if it batches (no-op for plain TypedConns/mocks);
    transport errors surface to the caller like a failed send."""
    f = getattr(conn, "flush", None)
    if f is not None:
        f()


class TypedConn:
    """Connection wrapper applying the framing to send/recv while keeping
    the raw-byte surface for transfer bodies.  send() is atomic per conn:
    Connection.send_bytes is NOT safe under concurrent writers (header and
    body interleave), and several head threads (reply path, pub sender)
    legitimately share one driver/worker conn.

    Received batch frames are decoded whole (validate-all-then-dispatch)
    into an internal queue; recv() returns sub-frames in order.  The
    queue is only touched by the conn's single reader thread — recv
    concurrency was never supported and still isn't."""

    __slots__ = ("_c", "_send_lock", "_rbuf")

    def __init__(self, conn):
        self._c = conn
        self._send_lock = lock_watchdog.make_lock("TypedConn._send_lock")
        self._rbuf: List[Any] = []  # decoded-but-undelivered sub-frames

    def send(self, obj: Any) -> None:
        if faults.ENABLED and faults.point("wire.send", key=_kind(obj)) == "drop":
            return  # frame lost on the wire; the sender believes it went out
        buf = _HEADER + encode_body(obj)
        with self._send_lock:
            self._c.send_bytes(buf)
            _count(1, len(buf), "direct")

    def _send_frame(self, buf: bytes, n_logical: int, reason: str) -> None:
        """Physical write of a pre-encoded frame (BatchingConn flush path)
        — shares the send lock so batched and direct writers never
        interleave on the wire."""
        with self._send_lock:
            self._c.send_bytes(buf)
            _count(n_logical, len(buf), reason)

    def recv(self) -> Any:
        while True:
            if self._rbuf:
                return self._rbuf.pop(0)
            objs = decode_frames(self._c.recv_bytes())
            if faults.ENABLED:
                # drop clauses fire per SUB-frame (key = message kind),
                # exactly as they did per physical frame pre-batching.
                objs = [
                    o for o in objs
                    if faults.point("wire.recv", key=_kind(o)) != "drop"
                ]
            if not objs:
                continue  # everything dropped; wait for the next frame
            self._rbuf = objs
            return self._rbuf.pop(0)

    def pending_frames(self) -> int:
        """Decoded sub-frames awaiting recv().  Drain loops must consume
        these before blocking on the fd — the socket shows no data for
        them, so an epoll/wait would strand a buffered tail."""
        return len(self._rbuf)

    # raw passthrough (object-transfer body, recv_into via fileno)
    def send_bytes(self, b) -> None:
        self._c.send_bytes(b)

    def recv_bytes(self):
        return self._c.recv_bytes()

    def poll(self, timeout: float = 0.0) -> bool:
        if self._rbuf:
            return True
        return self._c.poll(timeout)

    def fileno(self) -> int:
        return self._c.fileno()

    def close(self) -> None:
        self._c.close()

    @property
    def closed(self) -> bool:
        return self._c.closed

    def __repr__(self) -> str:
        return f"TypedConn({self._c!r})"


class BatchingConn:
    """Coalescing sender over a TypedConn (recv side passes through).

    send() encodes the message immediately (native codec or pickle —
    cheap, and the bytes are what the size threshold meters) and queues
    it; the pending run is flushed
    as ONE physical frame on size / linger / explicit flush.  A single
    pending message flushes as a plain frame — the batch envelope only
    appears when it pays for itself.

    Failure model: the first flush that hits a dead socket marks the conn
    broken; from then on send() raises OSError AT THE CALL, restoring the
    pre-batching contract that callers (oneway backlogs, reply paths)
    detect a dead conn at send time.  Messages stranded in the pending
    buffer by the breaking flush are recoverable via drain_pending() —
    the worker reconnect path replays them ahead of its oneway backlog.

    send_lock is the wire-serialization lock for the PENDING BUFFER; the
    physical write additionally serializes on the TypedConn's own send
    lock, so batched flushes and direct TypedConn sends on the same conn
    never interleave frames."""

    __slots__ = (
        "_c", "send_lock", "_pending", "_pending_bytes", "_batch_bytes",
        "_broken", "flush_reasons", "_pending_first_kind",
    )

    def __init__(self, conn, batch_bytes: Optional[int] = None):
        self._c = wrap(conn)
        self.send_lock = lock_watchdog.make_lock("BatchingConn.send_lock")
        self._pending: List[bytes] = []
        self._pending_bytes = 0
        self._batch_bytes = (
            _config.get("wire_batch_bytes") if batch_bytes is None else batch_bytes
        )
        self._broken = False
        # Per-conn flush-reason histogram (the per-process aggregate lives
        # in wire.stats()).
        self.flush_reasons: Dict[str, int] = {}
        # Kind of the batch's LEADING message: the wire.flush fault key,
        # so clauses scope by stream exactly like wire.send ones
        # (match=^done kills a task executor at its done-batch flush
        # without touching a replica's pdone batches).
        self._pending_first_kind: Optional[str] = None

    @property
    def conn(self):
        """The underlying TypedConn (tests, fd surgery)."""
        return self._c

    def send(self, obj: Any) -> None:
        if self._batch_bytes <= 0:
            # Coalescing disabled (RAY_TPU_WIRE_BATCH_BYTES=0): behave as
            # a plain TypedConn — the unbatched comparison baseline.
            self._c.send(obj)
            return
        if self._broken:
            raise OSError("connection previously failed a batch flush")
        if faults.ENABLED and faults.point("wire.send", key=_kind(obj)) == "drop":
            return  # frame lost on the wire; the sender believes it went out
        body = encode_body(obj)
        with self.send_lock:
            if not self._pending:
                self._pending_first_kind = _kind(obj)
            self._pending.append(body)
            self._pending_bytes += len(body) + _SUBLEN.size
            if self._pending_bytes >= self._batch_bytes:
                self._flush_locked("size")
                return
        _note_dirty(self)

    def flush(self, _reason: str = "explicit") -> None:
        with self.send_lock:
            self._flush_locked(_reason)

    def _flush_locked(self, reason: str) -> None:
        # caller holds self.send_lock
        if not self._pending:
            return
        if faults.ENABLED:
            # crash = die with the batch in flight (the receiver sees a
            # torn physical stream — EOF, or a truncated frame that
            # decode_frames rejects whole); delay stretches the flush
            # window; error/drop fail/lose the whole batch, which is one
            # physical message now.  Key = "<leading kind>:<reason>" so
            # clauses scope per stream (match=^done) or per trigger
            # (match=linger).
            key = f"{self._pending_first_kind or 'payload'}:{reason}"
            if faults.point("wire.flush", key=key) == "drop":
                self._pending = []
                self._pending_bytes = 0
                self._pending_first_kind = None
                return
        bodies = self._pending
        if len(bodies) == 1:
            buf = _HEADER + bodies[0]
        else:
            buf = encode_batch(bodies)
        try:
            self._c._send_frame(buf, len(bodies), reason)
        except (OSError, ValueError):
            # Leave the pending run in place for drain_pending(): the
            # conn is dead, but the messages may carry ownership state a
            # reconnect path can replay.
            self._broken = True
            raise
        self._pending = []
        self._pending_bytes = 0
        self._pending_first_kind = None
        self.flush_reasons[reason] = self.flush_reasons.get(reason, 0) + 1

    def drain_pending_bodies(self) -> List[bytes]:
        """Take back queued-but-unflushed PICKLED bodies (a broken conn's
        tail) for replay on a replacement conn via send_body().  Raw by
        design: unpickling can construct ObjectRefs, whose refcount hooks
        take the transport lock — poison while the caller holds a conn
        lock (the reconnect path does)."""
        with self.send_lock:
            bodies, self._pending = self._pending, []
            self._pending_bytes = 0
            self._pending_first_kind = None
        return bodies

    def drain_pending(self) -> List[Any]:
        """drain_pending_bodies, decoded (tests/diagnostics — do NOT call
        while holding a conn lock, see above)."""
        return [decode_body(b) for b in self.drain_pending_bodies()]

    def send_body(self, body: bytes) -> None:
        """Queue an already-pickled body (replay of a drained tail)."""
        if self._broken:
            raise OSError("connection previously failed a batch flush")
        if self._batch_bytes <= 0:
            with self.send_lock:
                self._c._send_frame(_HEADER + body, 1, "direct")
            return
        with self.send_lock:
            self._pending.append(body)
            self._pending_bytes += len(body) + _SUBLEN.size
            if self._pending_bytes >= self._batch_bytes:
                self._flush_locked("size")
                return
        _note_dirty(self)

    # -- recv + passthrough surface (the conn's reader side is unchanged)

    def recv(self) -> Any:
        return self._c.recv()

    def pending_frames(self) -> int:
        return self._c.pending_frames()

    def send_bytes(self, b) -> None:
        self._c.send_bytes(b)

    def recv_bytes(self):
        return self._c.recv_bytes()

    def poll(self, timeout: float = 0.0) -> bool:
        return self._c.poll(timeout)

    def fileno(self) -> int:
        return self._c.fileno()

    def close(self) -> None:
        _forget_dirty(self)
        self._c.close()

    @property
    def closed(self) -> bool:
        return self._c.closed

    def __repr__(self) -> str:
        return f"BatchingConn({self._c!r}, pending={len(self._pending)})"


def wrap(conn) -> TypedConn:
    if isinstance(conn, (TypedConn, BatchingConn)):
        return conn
    return TypedConn(conn)


def batching(conn) -> BatchingConn:
    """Wrap a conn in the coalescing sender (idempotent)."""
    return conn if isinstance(conn, BatchingConn) else BatchingConn(conn)


def connect(address, authkey: bytes) -> TypedConn:
    """Client-side connect + auth + wrap (the stdlib handshake runs on the
    raw connection; framing starts with the first application message)."""
    from multiprocessing.connection import Client

    return TypedConn(Client(tuple(address), authkey=authkey))
