"""The load generator: a separate OS process that never imports JAX
(stdlib + numpy), so the clients do not share the server's GIL and the
chip belongs to the runner alone.

What arrives when is the traffic mix's: `--traffic` names the mix's data
file, whose `loop` names a module under `loops/` (closed loop today; an
open loop is a new file there, not an edit here). This file holds what
every loop shares: the pool of encoded requests, the connection, the
response reader and the protocol with the runner.

Protocol with the runner: it prints `READY` when the loop is built (its
connections open), waits for one line on stdin, prints `START <unix
time>` and drives the loop for `--seconds`; requests in flight at the end
are awaited. It then writes `--out` (npz: per request the pool index,
send time, end time, HTTP status; unix seconds) and, for the pool
indices the runner marked in `--keep`, the raw answer bytes
(`--out`.answers.json).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import socket
import sys
import time

import numpy as np


def read_response(sock: socket.socket, buf: bytearray):
    """-> (status, body bytes); leaves any surplus in `buf`."""
    while True:
        end = buf.find(b"\r\n\r\n")
        if end >= 0:
            break
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed the connection")
        buf += chunk
    head = bytes(buf[:end])
    status = int(head[9:12])
    length = 0
    for line in head.split(b"\r\n")[1:]:
        if line[:15].lower() == b"content-length:":
            length = int(line[15:])
    need = end + 4 + length
    while len(buf) < need:
        chunk = sock.recv(max(65536, need - len(buf)))
        if not chunk:
            raise ConnectionError("server closed the connection")
        buf += chunk
    body = bytes(buf[end + 4:need])
    del buf[:need]
    return status, body


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--path", required=True)
    ap.add_argument("--pool", required=True,
                    help="npz: `data` uint8 bodies back to back, `offsets`")
    ap.add_argument("--keep", default=None,
                    help="npy bool mask over the pool: answers to keep")
    ap.add_argument("--traffic", required=True,
                    help="the mix's data file: `loop` and its parameters")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    pool = np.load(args.pool)
    data, offsets = pool["data"].tobytes(), pool["offsets"]
    n_pool = len(offsets) - 1
    keep = (np.load(args.keep) if args.keep else np.zeros(n_pool, bool))
    head = (f"POST {args.path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            "Content-Type: application/json\r\nContent-Length: ").encode()
    requests = [
        head + str(int(offsets[i + 1] - offsets[i])).encode() + b"\r\n\r\n"
        + data[int(offsets[i]):int(offsets[i + 1])]
        for i in range(n_pool)
    ]

    def connect() -> socket.socket:
        s = socket.create_connection(("127.0.0.1", args.port), timeout=120)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    with open(args.traffic) as f:
        traffic = json.load(f)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "loops",
                        f"{traffic['loop']}.py")
    spec = importlib.util.spec_from_file_location("bench_loop", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    loop = mod.Loop({"requests": requests, "keep": keep, "connect": connect,
                     "read_response": read_response, "traffic": traffic})
    print("READY", flush=True)
    sys.stdin.readline()
    to_unix = time.time() - time.perf_counter()
    start = time.perf_counter()
    print(f"START {start + to_unix!r}", flush=True)
    loop.start(start, args.seconds)
    rows, kept, errors, alive = loop.join()
    flat = np.array(rows, np.float64).reshape(-1, 4)
    np.savez(
        args.out,
        index=flat[:, 0].astype(np.int64),
        t_send=flat[:, 1] + to_unix,
        t_end=flat[:, 2] + to_unix,
        status=flat[:, 3].astype(np.int64),
        window=np.array([start + to_unix, args.seconds]),
        pool_wrapped=np.array([int((flat[:, 0] >= n_pool).sum())]),
    )
    with open(args.out + ".answers.json", "w") as f:
        json.dump({str(k): v for k, v in kept.items()}, f)
    for e in errors[:5]:
        print("loadgen:", e, file=sys.stderr)
    return 0 if not alive else 4


if __name__ == "__main__":
    sys.exit(main())
