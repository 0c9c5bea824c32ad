"""Node daemon: the per-host worker-pool + object-store process (raylet-lite).

ray: src/ray/raylet/main.cc + node_manager.h:115 — one daemon per host owns
that host's worker processes.  TPU-first simplification: scheduling and
ownership stay with the driver (single-controller); the daemon's jobs are
  * process supervision on its host — spawn workers on request, kill them
    on request, and take the whole pool down with it when it dies (node
    failure); workers connect DIRECTLY to the driver over TCP (the direct
    task transport, ray: direct_task_transport.h:75);
  * the NODE OBJECT STORE — an isolated per-node shm directory (no path is
    shared across nodes) that this daemon creates, its workers seal results
    into, and its ObjectServer serves to other nodes over the transfer
    plane (ray: the plasma store + object manager attached to each raylet,
    src/ray/object_manager/object_manager.h:117).

Launch:  python -m ray_tpu._private.node_daemon
with env RAY_TPU_DRIVER_HOST/PORT, RAY_TPU_AUTHKEY, RAY_TPU_NODE_CONFIG
(json: node_id, num_cpus, resources, labels, session, store_root?).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
from typing import Dict


def _peer_host() -> str:
    from ray_tpu._private import config as _config

    return _config.get("node_ip")


def _apply_pythonpath(env: Dict[str, str]) -> None:
    """Stamp PYTHONPATH so children resolve ray_tpu + the daemon's own
    module search path (one implementation: worker env AND zygote env)."""
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    paths = [pkg_root] + [p for p in sys.path if p] + (
        env.get("PYTHONPATH", "").split(os.pathsep) if env.get("PYTHONPATH") else []
    )
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(paths))


def _build_worker_env(
    wid: str, host: str, port: int, authkey_hex: str, session: str, renv,
    store_dir: str, node_id: str,
) -> Dict[str, str]:
    from ray_tpu._private.runtime_env import worker_env_entries

    renv = renv or {}
    env_vars = renv.get("env_vars") or {}
    env = os.environ.copy()
    env.update(
        {
            "RAY_TPU_DRIVER_HOST": host,
            "RAY_TPU_DRIVER_PORT": str(port),
            "RAY_TPU_AUTHKEY": authkey_hex,
            "RAY_TPU_WORKER_ID": wid,
            "RAY_TPU_SESSION": session,
            # Log files are block-buffered without this: prints must land
            # promptly for the monitor to forward them.
            "PYTHONUNBUFFERED": "1",
            # This node's store, NOT the session default: workers seal into
            # and read from their own node's directory only.
            "RAY_TPU_STORE_DIR": store_dir,
            # Node identity rides the worker's "ready" handshake so a
            # restarted head can adopt the worker back onto this node.
            "RAY_TPU_NODE_ID": node_id,
            # Peer-transport advertise host: this NODE's address (the
            # worker's direct-call listener must be reachable from other
            # nodes' workers), not the head's.
            "RAY_TPU_PEER_HOST": _peer_host(),
            **worker_env_entries(renv),
        }
    )
    env.update({k: str(v) for k, v in env_vars.items()})
    # Workers must die with their daemon even on SIGKILL (a raylet's workers
    # don't outlive node death): worker_main arms PR_SET_PDEATHSIG.
    env["RAY_TPU_PDEATHSIG"] = "1"
    _apply_pythonpath(env)
    return env


def main() -> None:
    from multiprocessing.connection import Client

    host = os.environ["RAY_TPU_DRIVER_HOST"]
    port = int(os.environ["RAY_TPU_DRIVER_PORT"])
    authkey_hex = os.environ["RAY_TPU_AUTHKEY"]
    cfg = json.loads(os.environ["RAY_TPU_NODE_CONFIG"])
    node_id = cfg["node_id"]
    session = cfg["session"]
    from ray_tpu._private import faults, telemetry

    faults.set_process_tag(f"daemon:{node_id}")
    telemetry.install(f"daemon:{node_id}")

    # The node object store: an isolated per-node directory (distinct even
    # when several daemons share one machine in tests — no cross-node path
    # sharing), created HERE so the arena exists before any worker joins.
    from ray_tpu._private import config as _config
    from ray_tpu._private.object_plane import ObjectServer
    from ray_tpu._private.store import ShmStore, _default_capacity, _default_shm_root

    store_root = cfg.get("store_root") or _default_shm_root()
    store_dir = os.path.join(store_root, f"raytpu-{session}-{node_id}")
    capacity = _config.get("object_store_memory") or _default_capacity(store_root)
    store = ShmStore(session, capacity=capacity, dir_path=store_dir)
    authkey = bytes.fromhex(authkey_hex)
    # read_board: the pipelined-broadcast relay path — this server streams
    # the landed prefix of a pull still in flight in one of this node's
    # workers (the board file in the shared store dir carries progress).
    obj_server = ObjectServer(
        store.get_raw, authkey, advertise_host=_config.get("node_ip"),
        read_board=store.read_board,
    )
    # The node arena's fd, held open for handoff to workers: the zygote
    # gets it over its AF_UNIX pipe (SCM_RIGHTS, netutil.send_fd) and
    # forked workers inherit it; directly-spawned workers inherit via
    # pass_fds.  A worker that cannot map the fd falls back to the path,
    # then to the file-per-object store (store.py arena.map fallback).
    arena_fd = None
    if store.arena is not None:
        try:
            arena_fd = os.open(store.arena.path, os.O_RDWR)
        except OSError:
            arena_fd = None
    # This node's log dir: workers' stdout/stderr land here; the monitor
    # below tails the files and forwards fresh lines to the head
    # (ray: per-node log_monitor.py publishing to the driver).
    log_dir = f"/tmp/raytpu-logs-{session}-{node_id}"
    send_lock = threading.Lock()

    from ray_tpu._private import wire
    from ray_tpu._private.netutil import set_nodelay

    def connect():
        # Batching sender: heartbeats piggyback on whatever log_lines /
        # worker_exited frames are pending — one physical write per loop
        # tick instead of one per message (the flush sits right before
        # the loop's blocking wait).
        c = wire.batching(wire.connect((host, port), authkey))
        set_nodelay(c)
        import time as _t

        c.send(
            (
                "daemon",
                node_id,
                {
                    "num_cpus": cfg.get("num_cpus", 1.0),
                    "resources": cfg.get("resources") or {},
                    "labels": cfg.get("labels") or {},
                    "object_endpoint": obj_server.endpoint,
                    # Clock-offset sample for the head's merged timeline
                    # (same estimate the worker ready hello carries).
                    "clock": _t.time(),
                },
                os.getpid(),
            )
        )
        c.flush()  # the head's handshake thread is waiting on this hello
        return c

    def reconnect():
        """Head conn lost: in head-split mode, retry the head's fixed
        address for the window (a restarted head re-registers this node);
        None = give up (classic mode or window expired) -> node death."""
        import time as _time

        window = _config.get("reconnect_window_s")
        if window <= 0:
            return None
        deadline = _time.monotonic() + window
        while _time.monotonic() < deadline:
            try:
                return connect()
            except Exception:
                _time.sleep(0.5)
        return None

    conn = connect()

    def forward_logs(wid, stream, lines):
        try:
            with send_lock:
                conn.send(("log_lines", wid, stream, lines))
        except OSError:
            pass  # head away (restart window); lines stay in the files

    from ray_tpu._private.log_monitor import LogMonitor, open_worker_logs

    log_monitor = LogMonitor(log_dir, forward_logs)

    children: Dict[str, subprocess.Popen] = {}
    spawn_ts: Dict[str, float] = {}
    # Zygote fork server for this node's workers (zygote.py): ~2ms forks
    # from a pre-imported interpreter instead of ~250ms interpreter boots
    # — and forked workers inherit numpy/cloudpickle already imported, so
    # a cold broadcast pull doesn't pay a numpy import inside the
    # unpickle (measured ~0.9s per worker on a contended host).
    zyg: Dict[str, object] = {"conn": None, "proc": None, "env": None}
    zpids: Dict[str, int] = {}  # zygote-forked wid -> pid

    def start_zygote() -> None:
        if not _config.get("use_zygote"):
            return
        from multiprocessing.connection import Pipe

        parent, child = Pipe()
        env = os.environ.copy()
        env["PYTHONUNBUFFERED"] = "1"
        env["RAY_TPU_ZYGOTE_FD"] = str(child.fileno())
        _apply_pythonpath(env)
        try:
            p = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.zygote"],
                env=env,
                pass_fds=[child.fileno()],
                close_fds=True,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
        except OSError:
            parent.close()
            child.close()
            return
        child.close()
        zyg["conn"] = wire.wrap(parent)
        zyg["proc"] = p
        zyg["env"] = env
        # Hand the node arena's open fd to the zygote over this AF_UNIX
        # pipe (SCM_RIGHTS): the frame announces it, the ancillary
        # message carries it, and every forked worker inherits the
        # descriptor (the zygote stamps RAY_TPU_ARENA_FD with ITS fd
        # number).  Failure is non-fatal — workers fall back to opening
        # the arena by path.
        if arena_fd is not None:
            from ray_tpu._private import netutil

            try:
                zyg["conn"].send(("arena_fd", store.arena.path))
                netutil.send_fd(zyg["conn"], arena_fd, p.pid)
            except (OSError, ValueError):
                pass

    def zygote_fork(wid: str, full_env: Dict[str, str]) -> bool:
        zc = zyg["conn"]
        if zc is None:
            return False
        base = zyg["env"] or {}
        overrides = {k: v for k, v in full_env.items() if base.get(k) != v}
        from ray_tpu._private.log_monitor import worker_log_paths

        os.makedirs(log_dir, exist_ok=True)
        out_path, err_path = worker_log_paths(log_dir, wid)
        try:
            zc.send(("fork", wid, overrides, out_path, err_path))
        except OSError:
            zyg["conn"] = None
            start_zygote()
            return False
        zpids[wid] = -1  # pid lands with the ("forked", ...) reply
        import time as _time

        spawn_ts[wid] = _time.monotonic()
        return True

    # OOM protection (ray: memory_monitor.h:52 + worker_killing_policy.h):
    # under memory pressure, kill ONE worker (retriable error head-side)
    # instead of letting the kernel OOM-killer take the whole daemon.
    from ray_tpu._private.memory_monitor import MemoryMonitor

    def _oom_workers():
        # list() snapshot: the monitor thread iterates while the main loop
        # spawns/reaps; mutating a dict mid-iteration raises and the beat
        # would be silently skipped exactly during post-kill churn.
        out = {
            wid: (p.pid, spawn_ts.get(wid, 0.0))
            for wid, p in list(children.items())
            if p.poll() is None
        }
        for wid, pid in list(zpids.items()):
            if pid > 0:
                out[wid] = (pid, spawn_ts.get(wid, 0.0))
        return out

    oom_killed: Dict[str, tuple] = {}

    def _oom_kill(wid: str, rss: int, used: int, limit: int) -> None:
        p = children.get(wid)
        zpid = zpids.get(wid)
        if p is None and not (zpid and zpid > 0):
            return
        # Record + tell the head FIRST so the crash is classified as OOM,
        # then SIGKILL — a graceful terminate could block on the very
        # allocation that caused the pressure.  The info also rides the
        # eventual worker_exited report (belt and braces: the worker's own
        # conn EOF races this message on a different socket).
        oom_killed[wid] = (rss, used, limit)
        try:
            with send_lock:
                conn.send(("worker_oom_killed", wid, rss, used, limit))
        except OSError:
            pass
        try:
            if p is not None:
                p.kill()
            else:
                os.kill(zpid, signal.SIGKILL)
        except OSError:
            pass

    refresh_ms = _config.get("memory_monitor_refresh_ms")
    mem_monitor = None
    if refresh_ms > 0:
        mem_monitor = MemoryMonitor(
            _oom_workers,
            _oom_kill,
            limit_bytes=_config.get("memory_limit_bytes"),
            threshold=_config.get("memory_usage_threshold"),
            interval_s=refresh_ms / 1000.0,
            policy=_config.get("oom_worker_killing_policy"),
        )
        mem_monitor.start()

    def shutdown(*_a):
        if mem_monitor is not None:
            mem_monitor.stop()
        if zyg["proc"] is not None:
            try:
                zyg["proc"].terminate()  # forked workers follow (pdeathsig)
            except OSError:
                pass
        for pid in zpids.values():
            if pid > 0:
                try:
                    os.kill(pid, signal.SIGTERM)
                except OSError:
                    pass
        for p in children.values():
            try:
                p.terminate()
            except OSError:
                pass
        for p in children.values():
            try:
                p.wait(timeout=2)
            except Exception:
                try:
                    p.kill()
                except OSError:
                    pass
        try:
            log_monitor.flush()  # last lines (incl. crash output) reach head
            log_monitor.stop()
        except Exception:
            pass
        obj_server.close()
        store.destroy()
        sys.exit(0)

    # Signal handlers only set a flag: shutdown() flushes logs through
    # send_lock, and a handler interrupting a frame that already holds it
    # (reap's send) would self-deadlock on the non-reentrant lock.
    stop_flag = {"stop": False}

    def _request_stop(*_a):
        stop_flag["stop"] = True

    signal.signal(signal.SIGTERM, _request_stop)
    signal.signal(signal.SIGINT, _request_stop)

    def reap() -> None:
        """Collect exited children (no zombies) and report them — the
        driver's reaper cannot see remote processes, so a worker that dies
        before connecting would otherwise hang its task forever."""
        for wid, p in list(children.items()):
            rc = p.poll()
            if rc is not None:
                children.pop(wid, None)
                spawn_ts.pop(wid, None)
                try:
                    with send_lock:
                        conn.send(("worker_exited", wid, rc, oom_killed.pop(wid, None)))
                except OSError:
                    pass

    # Liveness heartbeats (ray: gcs_health_check_manager.h:28-37 — the
    # reference PULLS health checks; a push on the existing conn gives the
    # head the same signal without another listener): a hung daemon or a
    # half-open TCP conn stops heartbeating and the head declares the node
    # dead on timeout instead of trusting EOF alone.
    import time as _time

    from multiprocessing.connection import wait as conn_wait

    start_zygote()
    hb_period = _config.get("health_check_period_ms") / 1000.0
    last_hb = 0.0
    push_period = max(_config.get("metrics_push_ms"), 0) / 1000.0
    last_push = 0.0

    pending_kills: set = set()  # kill_worker raced a fork in flight

    def _report_exited(wid: str, rc) -> None:
        zpids.pop(wid, None)
        spawn_ts.pop(wid, None)
        pending_kills.discard(wid)
        try:
            with send_lock:
                conn.send(("worker_exited", wid, rc, oom_killed.pop(wid, None)))
        except OSError:
            pass

    def drain_zygote() -> None:
        zc = zyg["conn"]
        while zc is not None:
            try:
                if not zc.poll(0):
                    return
                zmsg = zc.recv()
            except (EOFError, OSError):
                # Zygote died.  Its forked workers die with it (pdeathsig
                # chains zygote -> worker) and fork requests in flight are
                # lost — report every zygote worker exited so the head
                # reschedules instead of waiting on a reply that will
                # never come.
                zyg["conn"] = None  # respawned on the next spawn request
                for wid in list(zpids):
                    _report_exited(wid, -1)
                return
            if zmsg[0] == "forked":
                wid, pid = zmsg[1], zmsg[2]
                zpids[wid] = pid
                if wid in pending_kills:
                    # A kill_worker landed while the fork was in flight:
                    # apply it now instead of silently dropping it.
                    pending_kills.discard(wid)
                    try:
                        os.kill(pid, signal.SIGTERM)
                    except OSError:
                        pass
            elif zmsg[0] == "worker_exited":
                _report_exited(zmsg[1], zmsg[2])

    while True:
        if stop_flag["stop"]:
            shutdown()
            return
        now = _time.monotonic()
        if hb_period > 0 and now - last_hb >= hb_period:
            last_hb = now
            try:
                with send_lock:
                    conn.send(("heartbeat", node_id))
            except OSError:
                pass  # EOF path below handles reconnection
        if push_period > 0 and now - last_push >= push_period:
            # Telemetry push: the daemon's registry + wire counters plus
            # its store gauges, riding the same batch flush the heartbeat
            # does (droppable: a failed send just loses a tick).
            last_push = now
            snap = telemetry.snapshot_process(
                extra={
                    "node_live_workers": float(
                        len(children) + sum(1 for p in zpids.values() if p > 0)
                    ),
                }
            )
            try:
                with send_lock:
                    conn.send(("metrics_push", snap))
            except OSError:
                pass
        # Flush-before-blocking-wait: the heartbeat above plus any pending
        # log_lines / worker_exited / oom reports leave as one write.
        try:
            conn.flush()
        except OSError:
            pass  # EOF path below handles reconnection
        if conn.pending_frames():
            has_msg = True  # a decoded batch tail would never wake wait()
        else:
            try:
                waitset = [conn] + ([zyg["conn"]] if zyg["conn"] is not None else [])
                ready = conn_wait(waitset, timeout=0.5)
                has_msg = conn in ready
            except (EOFError, OSError):
                conn = reconnect()
                if conn is None:
                    shutdown()
                    return
                continue
        drain_zygote()
        reap()
        if not has_msg:
            continue
        msgs = []
        try:
            msgs.append(conn.recv())
            while len(msgs) < 64 and conn.poll(0):
                msgs.append(conn.recv())
            while conn.pending_frames():
                msgs.append(conn.recv())
        except (EOFError, OSError):
            # Head gone: reconnect in head-split mode, else this host's
            # pool dies with it.
            conn = reconnect()
            if conn is None:
                shutdown()
                return
            continue
        for msg in msgs:
            kind = msg[0]
            if kind == "spawn_worker":
                _, wid, renv = msg
                env = _build_worker_env(
                    wid, host, port, authkey_hex, session, renv, store_dir, node_id
                )
                if zyg["conn"] is None:
                    start_zygote()  # died/never started: next spawn forks
                if not zygote_fork(wid, env):
                    outf, errf = open_worker_logs(log_dir, wid)
                    if arena_fd is not None:
                        # Direct spawn inherits the arena fd (the zygote
                        # path receives it via SCM_RIGHTS instead).
                        env["RAY_TPU_ARENA_FD"] = str(arena_fd)
                    try:
                        children[wid] = subprocess.Popen(
                            [sys.executable, "-m", "ray_tpu._private.worker_proc"],
                            env=env,
                            close_fds=True,
                            pass_fds=(arena_fd,) if arena_fd is not None else (),
                            stdout=outf,
                            stderr=errf,
                        )
                        spawn_ts[wid] = _time.monotonic()
                    finally:
                        outf.close()
                        errf.close()
            elif kind == "kill_worker":
                p = children.get(msg[1])
                zpid = zpids.get(msg[1])
                if p is not None:
                    try:
                        p.terminate()
                    except OSError:
                        pass
                    # reap() collects and reports it next cycle
                elif zpid is not None and zpid > 0:
                    try:
                        os.kill(zpid, signal.SIGTERM)
                    except OSError:
                        pass
                    # the zygote reaps and reports it
                elif zpid == -1:
                    # Fork in flight: remember the kill for the ("forked",
                    # pid) reply instead of dropping it.
                    pending_kills.add(msg[1])
            elif kind == "delete_object":
                # Owner freed the object (refcount hit zero): drop this
                # node's copy (ray: the raylet's local object manager
                # eviction on ownership release).
                store.delete(msg[1])
            elif kind == "shutdown":
                shutdown()
                return


if __name__ == "__main__":
    main()
