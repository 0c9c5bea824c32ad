"""Socket tuning + fd passing for control-plane connections.

TCP_NODELAY on every control conn, both ends.  Without it, the
write-write-read pattern the protocol produces (a refop oneway piggybacked
right before a request on the same conn) trips Nagle + delayed-ACK and
turns a sub-millisecond round trip into ~40ms — the reference disables
Nagle on its RPC sockets for the same reason (grpc sets TCP_NODELAY by
default).
"""

from __future__ import annotations

import os
import socket


def send_fd(channel, fd: int, dest_pid: int) -> None:
    """Ship a PLAIN file descriptor (not a connection) over an AF_UNIX
    channel via SCM_RIGHTS — the arena-handoff primitive: a node daemon
    passes its open arena fd to the zygote, whose forked workers inherit
    it and mmap the store without resolving the path.  The caller keeps
    (and must close) its own copy; the receiver gets a duplicate."""
    from multiprocessing import reduction

    reduction.send_handle(channel, fd, dest_pid)


def recv_fd(channel) -> int:
    """Receive a plain fd passed with send_fd; the returned descriptor is
    owned by the caller."""
    from multiprocessing import reduction

    return reduction.recv_handle(channel)


def set_nodelay(conn) -> None:
    """Disable Nagle on a multiprocessing.connection.Connection (TCP only;
    silently no-ops for anything else)."""
    try:
        s = socket.socket(fileno=os.dup(conn.fileno()))
    except OSError:
        return
    try:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass
    finally:
        s.close()
