"""Zygote: pre-warmed fork server for fast worker spawn.

The reference prestarts idle workers per language/runtime-env so actor
creation binds to a live process instead of paying an interpreter boot
(ray: src/ray/raylet/worker_pool.h:156, PopWorker/StartWorkerProcess).
On this build a fresh CPython interpreter + worker-runtime imports cost
~150-300ms of CPU per worker — at 1000 actors that IS the creation
budget (round-4 bench: 3.8 actors/s, entirely spawn-bound).

The zygote goes further than prestart: ONE interpreter boots, imports
the worker runtime (never jax — forking a process with an initialized
XLA client is undefined), connects back to its owner (head runtime or
node daemon), and serves ("fork", wid, overrides, out, err) requests.
A fork costs ~2ms, so worker supply scales with the scheduler, not
with interpreter boots.

Invariants:
  * the zygote is SINGLE-THREADED until it forks (fork + threads is the
    classic deadlock) and never imports jax/torch: a process that has
    opened the chip owns it, and a fork of a live XLA client is undefined,
    so only the worker that runs jax code ever loads it;
  * children are direct children of the zygote: PR_SET_PDEATHSIG chains
    owner -> zygote -> worker, preserving the die-with-owner invariant
    daemon workers rely on, and the zygote reaps exits, reporting them
    as ("worker_exited", wid, pid) so never-connected boot crashes are
    classified without waiting for a conn-EOF that will never come.
"""

from __future__ import annotations

import os
import signal
import sys


def _arm_pdeathsig() -> None:
    try:
        import ctypes

        ctypes.CDLL(None).prctl(1, signal.SIGTERM)  # PR_SET_PDEATHSIG
    except Exception:
        pass


def _child_entry(overrides: dict, out_path: str, err_path: str) -> None:
    """Runs in the forked child: restore the worker env, point stdio at
    the worker's log files, and enter the normal worker main."""
    # fork(2) clears PR_SET_PDEATHSIG: re-arm so the worker dies with the
    # ZYGOTE (its parent), completing the owner -> zygote -> worker chain.
    if os.environ.get("RAY_TPU_PDEATHSIG") or overrides.get("RAY_TPU_PDEATHSIG"):
        _arm_pdeathsig()
    os.environ.update({k: str(v) for k, v in overrides.items()})
    # The fork inherited the ZYGOTE's fault-plane state (its process tag
    # and visit counters): re-derive the worker identity and restart the
    # clause counters so proc=worker clauses scope correctly and each
    # worker's injection schedule starts from zero.
    from ray_tpu._private import faults

    faults.set_process_tag(
        "worker:" + os.environ.get("RAY_TPU_WORKER_ID", "?")
    )
    faults.refresh_from_env()
    try:
        out_fd = os.open(out_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        err_fd = os.open(err_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        os.dup2(out_fd, 1)
        os.dup2(err_fd, 2)
        os.close(out_fd)
        os.close(err_fd)
        # Re-bind the Python-level streams to the new fds (the inherited
        # file objects still wrap the zygote's /dev/null-ish stdio).
        sys.stdout = os.fdopen(1, "w", buffering=1)
        sys.stderr = os.fdopen(2, "w", buffering=1)
    except OSError:
        pass  # log redirection is best-effort; the worker still runs
    from ray_tpu._private.worker_proc import _subprocess_entry

    try:
        _subprocess_entry()
    except SystemExit:
        raise
    except BaseException:
        import traceback

        traceback.print_exc()
    finally:
        os._exit(0)


def main() -> None:
    _arm_pdeathsig()
    # Two attachment modes: an inherited pipe fd (daemon-owned zygotes —
    # RAY_TPU_ZYGOTE_FD) or a connect-back to the head's listener (head
    # runtime's zygote).
    inherited_fd = os.environ.get("RAY_TPU_ZYGOTE_FD")
    # Pre-import the worker runtime + serialization stack.  Everything
    # here must be thread-free and fork-safe; jax/torch are NOT on this
    # list by design.
    import cloudpickle  # noqa: F401
    import numpy  # noqa: F401

    import ray_tpu  # noqa: F401  (public API surface user tasks touch first)
    import ray_tpu._native  # noqa: F401  (ctypes arena binding: dlopen once)
    import ray_tpu._private.object_plane  # noqa: F401
    import ray_tpu._private.peer  # noqa: F401
    import ray_tpu._private.log_monitor  # noqa: F401
    import ray_tpu._private.runtime  # noqa: F401  (worker_main imports it for _worker_mode)
    import ray_tpu._private.runtime_env  # noqa: F401
    import ray_tpu._private.serialization  # noqa: F401
    import ray_tpu._private.store  # noqa: F401
    import ray_tpu._private.worker_proc  # noqa: F401
    import ray_tpu.exceptions  # noqa: F401
    from ray_tpu._private import faults
    from ray_tpu._private import wire

    faults.set_process_tag("zygote")

    if inherited_fd is not None:
        from multiprocessing.connection import Connection

        conn = wire.wrap(Connection(int(inherited_fd)))
    else:
        addr = (
            os.environ["RAY_TPU_DRIVER_HOST"],
            int(os.environ["RAY_TPU_DRIVER_PORT"]),
        )
        authkey = bytes.fromhex(os.environ["RAY_TPU_AUTHKEY"])
        conn = wire.connect(addr, authkey)
    conn.send(("zygote", os.getpid()))
    children: dict = {}  # pid -> wid

    def reap() -> None:
        while children:
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                children.clear()
                return
            if pid == 0:
                return
            wid = children.pop(pid, None)
            if wid is not None:
                try:
                    rc = os.waitstatus_to_exitcode(status)
                except ValueError:
                    rc = -1
                try:
                    conn.send(("worker_exited", wid, rc))
                except OSError:
                    os._exit(0)

    while True:
        try:
            ready = conn.poll(1.0)
        except OSError:
            os._exit(0)
        reap()
        if not ready:
            continue
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            os._exit(0)  # owner gone; children follow via their own pdeathsig
        if isinstance(msg, tuple) and msg and msg[0] == "arena_fd":
            # The daemon's node-arena fd follows as an SCM_RIGHTS
            # ancillary message on this AF_UNIX pipe: hold it open so
            # every forked worker inherits it and maps the store without
            # resolving the path (store.py prefers RAY_TPU_ARENA_FD).
            from ray_tpu._private import netutil

            try:
                afd = netutil.recv_fd(conn)
                os.environ["RAY_TPU_ARENA_FD"] = str(afd)
            except (OSError, EOFError, ValueError):
                pass  # workers fall back to opening the arena by path
            continue
        if not (isinstance(msg, tuple) and msg and msg[0] == "fork"):
            continue
        _, wid, overrides, out_path, err_path = msg
        pid = os.fork()
        if pid == 0:
            try:
                conn.close()
            except Exception:
                pass
            _child_entry(overrides, out_path, err_path)
            os._exit(0)  # unreachable; _child_entry never returns
        children[pid] = wid
        try:
            # drop -> the ("forked", ...) reply is lost while both zygote
            # and child live: the owner's pid-less handle must reap via
            # its grace window.  error (an OSError) -> zygote exit, the
            # conn-break twin of the same scenario.
            if not (
                faults.ENABLED
                and faults.point("zygote.forked", key=wid) == "drop"
            ):
                conn.send(("forked", wid, pid))
        except OSError:
            os._exit(0)


if __name__ == "__main__":
    main()
