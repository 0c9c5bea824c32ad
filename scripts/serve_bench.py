"""Serve data-plane micro-benchmark: QPS + p50/p99 latency.

ray: release/serve_tests/workloads/serve_micro_benchmark.py — handle-path
and HTTP-path throughput/latency on a trivial deployment (measures the
runtime, not the model).  Writes one JSON line.  Numbers are host-bound:
record nproc with them.

Run: python scripts/serve_bench.py [--requests 300] [--concurrency 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentile(xs, p):
    xs = sorted(xs)
    return xs[min(int(len(xs) * p), len(xs) - 1)]


def bench_handle(handle, n: int, concurrency: int):
    import ray_tpu

    lat = []
    lock = threading.Lock()

    def worker(count):
        for _ in range(count):
            t0 = time.monotonic()
            ray_tpu.get(handle.remote(1), timeout=60)
            dt = time.monotonic() - t0
            with lock:
                lat.append(dt)

    t0 = time.monotonic()
    threads = [
        threading.Thread(target=worker, args=(n // concurrency,))
        for _ in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    return len(lat) / wall, lat


def bench_http(addr: str, n: int, concurrency: int):
    # Persistent connection per client thread (the proxy speaks HTTP/1.1
    # keep-alive): a fresh TCP connection per request measures the
    # kernel's connect path, not the serve data plane — the reference's
    # serve benchmarks reuse sessions the same way.
    import http.client
    from urllib.parse import urlparse

    parsed = urlparse(addr)
    lat = []
    lock = threading.Lock()

    def worker(count):
        conn = http.client.HTTPConnection(
            parsed.hostname, parsed.port, timeout=60
        )
        for _ in range(count):
            t0 = time.monotonic()
            conn.request("GET", "/echo?x=1")
            conn.getresponse().read()
            dt = time.monotonic() - t0
            with lock:
                lat.append(dt)
        conn.close()

    t0 = time.monotonic()
    threads = [
        threading.Thread(target=worker, args=(n // concurrency,))
        for _ in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    return len(lat) / wall, lat


def bench_http_under_idle_load(addr: str, n: int, concurrency: int,
                               idle_conns: int):
    """p99 of active requests while `idle_conns` extra keep-alive
    connections sit open — the asyncio proxy must hold them at flat
    latency (a thread-per-connection server degrades as idle_conns grows;
    ray: uvicorn's event loop has the same property)."""
    import http.client
    import socket
    from urllib.parse import urlparse

    parsed = urlparse(addr)
    idle = []
    try:
        for _ in range(idle_conns):
            s = socket.create_connection(
                (parsed.hostname, parsed.port), timeout=30
            )
            # One real request primes the connection as keep-alive.
            s.sendall(b"GET /echo?x=1 HTTP/1.1\r\nHost: x\r\n\r\n")
            idle.append(s)
        for s in idle:
            s.recv(65536)  # drain the priming response; conn stays open
        qps, lat = bench_http(addr, n, concurrency)
    finally:
        for s in idle:
            try:
                s.close()
            except OSError:
                pass
    return qps, lat


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=300)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--idle-conns", type=int, default=0,
                    help="sweep: hold N idle keep-alive conns during the "
                         "HTTP bench and report latency under that load")
    ap.add_argument("--output", default=None)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_options={"host": "127.0.0.1", "port": 0})

    @serve.deployment(name="echo", num_replicas=2, max_concurrent_queries=32)
    def echo(body=None):
        return {"ok": True}

    handle = serve.run(echo.bind())
    ray_tpu.get(handle.remote(0), timeout=60)  # warm both paths
    addr = serve.get_http_address()

    hqps, hlat = bench_handle(handle, args.requests, args.concurrency)
    wqps, wlat = bench_http(addr, args.requests, args.concurrency)

    out = {
        "nproc": os.cpu_count(),
        "requests": args.requests,
        "concurrency": args.concurrency,
        "handle_qps": round(hqps, 1),
        "handle_p50_ms": round(_percentile(hlat, 0.50) * 1e3, 2),
        "handle_p99_ms": round(_percentile(hlat, 0.99) * 1e3, 2),
        "http_qps": round(wqps, 1),
        "http_p50_ms": round(_percentile(wlat, 0.50) * 1e3, 2),
        "http_p99_ms": round(_percentile(wlat, 0.99) * 1e3, 2),
    }
    if args.idle_conns:
        iqps, ilat = bench_http_under_idle_load(
            addr, args.requests, args.concurrency, args.idle_conns
        )
        out.update(
            {
                "idle_conns": args.idle_conns,
                "http_qps_under_idle": round(iqps, 1),
                "http_p99_ms_under_idle": round(
                    _percentile(ilat, 0.99) * 1e3, 2
                ),
            }
        )
    line = json.dumps(out)
    print(line)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    serve.shutdown()
    ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
