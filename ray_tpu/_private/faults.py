"""Deterministic fault-injection plane: named, seeded injection points.

ray: the reference hardens its recovery paths with targeted testing knobs
baked into RayConfig (`testing_asio_delay_us`, `gcs_rpc_server_*` failure
injection) rather than wall-clock kill threads — a failure seen once in CI
must be re-runnable from its config.  This module is that plane for this
build: every hazard site (wire frame send/recv, peer connect/flush/
re-drive, head control delivery, pubsub publish, object-plane chunk pull,
zygote fork replies, GCS snapshot writes) calls a NAMED point, and a
one-line spec names a scenario:

    RAY_TPU_FAULT_SPEC='peer.send:drop@every=7,proc=worker' \
    RAY_TPU_FAULT_SEED=7 python my_job.py

Spec grammar (clauses joined by ';'):

    clause   := point ':' action ['@' selector (',' selector)*]
    point    := dotted name, trailing '*' wildcard ok  ("peer.*")
    action   := 'drop' | 'error' | 'crash' | 'delay=<seconds>'
    selector := 'nth=<n>'      fire only on the n-th visit (1-based)
              | 'every=<n>'    fire on every n-th visit
              | 'after=<n>'    visits <= n are never eligible
              | 'times=<m>'    fire at most m times, then the clause is spent
              | 'prob=<p>'     fire with probability p (seeded, deterministic)
              | 'at=<seconds>' eligible only once wall time since configure()
                               passes this mark (schedule anchor: "kill the
                               head at t=3s" = 'head.send:crash@at=3')
              | 'match=<s>'    fire only when the site's key contains s
                               ('^s' anchors: key must START with s — e.g.
                               match=^done hits "done" but not "pdone")
              | 'proc=<s>'     fire only in processes whose tag contains s
                               (tags: 'main', 'head', 'worker:<wid>',
                               'daemon:<node_id>', 'zygote'; a worker
                               hosting an actor appends ':actor:<Class>',
                               so proc=actor:Replica scopes a kill to
                               serve replicas)

Actions at the point:
    drop   -> point() returns "drop"; the site skips the operation while
              reporting success (a lost message, not a failed send);
    delay  -> point() sleeps the given seconds, then proceeds;
    error  -> point() raises InjectedFault (a ConnectionError, so sites
              that already catch OSError route it through their existing
              failure handling — the whole point);
    crash  -> SIGKILL the calling process at the point (worker/daemon/
              zygote/head process death, exactly where it hurts).

Determinism: all randomness (`prob=`) comes from a clause-local
random.Random seeded by (RAY_TPU_FAULT_SEED, point pattern, clause index),
and counter selectors are pure functions of the per-clause visit count —
the same spec + seed + visit sequence produces the same injection schedule
(asserted by tests/test_faults.py).  The fired log (`log()`) records every
injection for replay triage; the soak harness prints the seed on failure.

Overhead when unset: hazard sites guard with `if faults.ENABLED:` — a
module attribute read on the fast path, no call, no allocation.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "ENABLED",
    "InjectedFault",
    "configure",
    "disable",
    "parse_spec",
    "point",
    "log",
    "stats",
    "seed",
    "set_crash_hook",
    "set_process_tag",
]

# Module-level disabled fast path: sites check this attribute before
# calling point().  Rebound (never mutated in place) by configure/disable.
ENABLED: bool = False


class InjectedFault(ConnectionError):
    """Raised by an 'error' action.  Subclasses ConnectionError (hence
    OSError) so every site's existing failure handling treats it exactly
    like a real transport fault."""


class FaultSpecError(ValueError):
    """Spec parse failure — loud by design; a typo'd chaos plan that
    silently injects nothing would report false robustness."""


class _Clause:
    __slots__ = (
        "pattern", "action", "delay_s", "nth", "every", "after", "times",
        "prob", "at_s", "match", "proc", "rng", "visits", "fired", "lock",
    )

    def __init__(self, pattern: str, action: str, delay_s: float, index: int,
                 seed_val: int, nth: Optional[int], every: Optional[int],
                 after: int, times: Optional[int], prob: Optional[float],
                 at_s: Optional[float], match: Optional[str],
                 proc: Optional[str]):
        self.pattern = pattern
        self.action = action
        self.delay_s = delay_s
        self.nth = nth
        self.every = every
        self.after = after
        self.times = times
        self.prob = prob
        self.at_s = at_s
        self.match = match
        self.proc = proc
        # Clause-local deterministic stream: independent of every other
        # clause and of call interleaving across points.
        self.rng = random.Random(f"{seed_val}:{pattern}:{index}")
        self.visits = 0
        self.fired = 0
        self.lock = threading.Lock()

    def matches_point(self, name: str) -> bool:
        if self.pattern.endswith("*"):
            return name.startswith(self.pattern[:-1])
        return name == self.pattern

    def check(self, key: Optional[str], now_s: float) -> bool:
        """One visit; True = fire.  Counter/rng state advances under the
        clause lock so concurrent visitors see a consistent schedule."""
        if self.match is not None:
            if key is None:
                return False
            if self.match.startswith("^"):
                if not key.startswith(self.match[1:]):
                    return False
            elif self.match not in key:
                return False
        if self.proc is not None and self.proc not in _PROC_TAG:
            return False
        with self.lock:
            self.visits += 1
            v = self.visits
            if self.times is not None and self.fired >= self.times:
                return False
            if self.at_s is not None and now_s < self.at_s:
                return False
            if v <= self.after:
                return False
            if self.nth is not None and v != self.nth:
                return False
            if self.every is not None and (v - self.after) % self.every != 0:
                return False
            if self.prob is not None and self.rng.random() >= self.prob:
                return False
            self.fired += 1
            return True


_lock = threading.Lock()
_clauses: List[_Clause] = []
_seed: int = 0
_t0: float = 0.0
_spec_str: str = ""
# Fired-injection log for replay triage (bounded; soak prints it on
# failure together with the seed).
_LOG_MAX = 4096
_log: List[Tuple[float, str, str, int]] = []  # (t, point, action, visit)

# Process identity for proc= scoping.  Workers get theirs from the env
# their spawner set; zygote/daemon/head override explicitly at entry.
_PROC_TAG: str = (
    "worker:" + os.environ["RAY_TPU_WORKER_ID"]
    if os.environ.get("RAY_TPU_WORKER_ID")
    else "main"
)


def set_process_tag(tag: str) -> None:
    global _PROC_TAG
    _PROC_TAG = tag


# Pre-SIGKILL hook for 'crash' actions (telemetry.install sets the flight-
# recorder dump here): the one chance to persist what this process saw
# before the fault plane kills it.  Best-effort — a hook failure must not
# turn a deterministic crash into anything else.
_crash_hook: Optional[callable] = None


def set_crash_hook(hook) -> None:
    global _crash_hook
    _crash_hook = hook


def _parse_float(field: str, raw: str) -> float:
    try:
        return float(raw)
    except ValueError:
        raise FaultSpecError(f"fault spec: {field}={raw!r} is not a number")


def _parse_int(field: str, raw: str) -> int:
    try:
        return int(raw)
    except ValueError:
        raise FaultSpecError(f"fault spec: {field}={raw!r} is not an integer")


def _parse_clause(text: str, index: int, seed_val: int) -> _Clause:
    head, sep, selpart = text.partition("@")
    if ":" not in head:
        raise FaultSpecError(
            f"fault clause {text!r}: expected '<point>:<action>"
            f"[@sel,...]' (e.g. 'peer.send:drop@every=7')"
        )
    pattern, _, action_raw = head.partition(":")
    pattern = pattern.strip()
    action_raw = action_raw.strip()
    if not pattern:
        raise FaultSpecError(f"fault clause {text!r}: empty point name")
    delay_s = 0.0
    if action_raw.startswith("delay"):
        _, eq, secs = action_raw.partition("=")
        if not eq:
            raise FaultSpecError(
                f"fault clause {text!r}: delay needs '=<seconds>'"
            )
        delay_s = _parse_float("delay", secs)
        action = "delay"
    elif action_raw in ("drop", "error", "crash"):
        action = action_raw
    else:
        raise FaultSpecError(
            f"fault clause {text!r}: unknown action {action_raw!r} "
            "(want drop | delay=<s> | error | crash)"
        )
    nth = every = times = None
    after = 0
    prob = at_s = None
    match = proc = None
    if sep:
        for sel in selpart.split(","):
            sel = sel.strip()
            if not sel:
                continue
            k, eq, v = sel.partition("=")
            if not eq:
                raise FaultSpecError(
                    f"fault clause {text!r}: selector {sel!r} needs '=<value>'"
                )
            if k == "nth":
                nth = _parse_int(k, v)
            elif k == "every":
                every = _parse_int(k, v)
                if every <= 0:
                    raise FaultSpecError(f"fault spec: every={v} must be > 0")
            elif k == "after":
                after = _parse_int(k, v)
            elif k == "times":
                times = _parse_int(k, v)
            elif k == "prob":
                prob = _parse_float(k, v)
                if not 0.0 <= prob <= 1.0:
                    raise FaultSpecError(f"fault spec: prob={v} not in [0,1]")
            elif k == "at":
                at_s = _parse_float(k, v)
            elif k == "match":
                match = v
            elif k == "proc":
                proc = v
            else:
                raise FaultSpecError(
                    f"fault clause {text!r}: unknown selector {k!r} (want "
                    "nth|every|after|times|prob|at|match|proc)"
                )
    return _Clause(pattern, action, delay_s, index, seed_val, nth, every,
                   after, times, prob, at_s, match, proc)


def parse_spec(spec: str, seed_val: int = 0) -> List[_Clause]:
    """Parse a spec WITHOUT installing it.  The registry export: the
    concurrency lint (analysis/fault_registry.py) validates every literal
    spec in tests/scripts against the generated fault-point catalog with
    this — the real parser, so the lint can never accept a spec the
    runtime would reject.  Raises FaultSpecError on any typo."""
    return [
        _parse_clause(part.strip(), i, seed_val)
        for i, part in enumerate(spec.split(";"))
        if part.strip()
    ]


def configure(spec: str, seed_val: Optional[int] = None) -> None:
    """Parse + install a fault plan.  Raises FaultSpecError on any typo —
    never silently installs a partial plan."""
    global ENABLED, _clauses, _seed, _t0, _spec_str
    if seed_val is None:
        seed_val = _parse_int("RAY_TPU_FAULT_SEED",
                              os.environ.get("RAY_TPU_FAULT_SEED", "0") or "0")
    clauses = parse_spec(spec, seed_val)
    with _lock:
        _clauses = clauses
        _seed = seed_val
        _spec_str = spec
        _t0 = time.monotonic()
        _log.clear()
        ENABLED = bool(clauses)


def disable() -> None:
    global ENABLED, _clauses, _spec_str
    with _lock:
        _clauses = []
        _spec_str = ""
        _log.clear()
        ENABLED = False


def refresh_from_env() -> None:
    """(Re)install the plan from RAY_TPU_FAULT_SPEC / RAY_TPU_FAULT_SEED.
    Called at import (children inherit the env) and by Runtime.__init__
    (so ray_tpu.init(_system_config={'fault_spec': ...}) lands here after
    config.set_system_config exports the env form)."""
    spec = os.environ.get("RAY_TPU_FAULT_SPEC", "")
    if spec:
        configure(spec)


def seed() -> int:
    return _seed


def spec() -> str:
    return _spec_str


def point(name: str, key: Optional[str] = None) -> Optional[str]:
    """One hazard-site visit.  Returns None (proceed) or "drop" (the site
    pretends the operation happened and lost the message); raises
    InjectedFault for 'error'; sleeps for 'delay'; SIGKILLs the process
    for 'crash'.  Sites guard the call with `if faults.ENABLED:`."""
    if not ENABLED:
        return None
    now_s = time.monotonic() - _t0
    outcome: Optional[str] = None
    for c in _clauses:
        if not c.matches_point(name):
            continue
        if not c.check(key, now_s):
            continue
        with _lock:
            if len(_log) < _LOG_MAX:
                _log.append((now_s, name, c.action, c.visits))
        if c.action == "delay":
            time.sleep(c.delay_s)
        elif c.action == "crash":
            import signal

            if _crash_hook is not None:
                try:
                    _crash_hook(name)
                except Exception:
                    pass
            os.kill(os.getpid(), signal.SIGKILL)
        elif c.action == "error":
            raise InjectedFault(
                f"injected fault at {name} (visit {c.visits}, seed {_seed})"
            )
        elif c.action == "drop":
            outcome = "drop"
    return outcome


def log() -> List[Tuple[float, str, str, int]]:
    """Fired injections this configuration: (t_since_configure, point,
    action, clause_visit_index)."""
    with _lock:
        return list(_log)


def stats() -> Dict[str, int]:
    """point -> fired count (summed over clauses)."""
    out: Dict[str, int] = {}
    with _lock:
        for _t, name, _a, _v in _log:
            out[name] = out.get(name, 0) + 1
    return out


def _reset_for_tests() -> None:
    disable()


# Children (workers, daemons, zygote) inherit the spec via os.environ; the
# plan is live from this module's first import in every process.
refresh_from_env()
