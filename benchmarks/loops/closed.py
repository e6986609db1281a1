"""Closed loop: `clients` threads, each one keep-alive HTTP/1.1
connection, each sending its next body when the last answer is in.
Client c takes bodies c, c + clients, c + 2 * clients, ... of the pool,
so no body repeats inside a window unless the pool runs out.

A loop module (`loops/<traffic's loop>.py`) gives `Loop(env)`, built
before the window (`env`: `requests` the encoded HTTP requests, `keep`
the mask of answers to keep, `connect()`, `read_response(sock, buf)`,
`traffic` the mix's file); `start(t0, seconds)` opens the window at
`time.perf_counter()` == t0; `join()` waits for the requests in flight
and returns (rows of (pool index, send time, end time, status),
{pool index: answer bytes}, errors, threads still alive).
"""

from __future__ import annotations

import threading
import time


class Loop:
    def __init__(self, env: dict):
        self.env = env
        self.clients = int(env["traffic"]["clients"])
        self.socks = [env["connect"]() for _ in range(self.clients)]
        self.rows = [[] for _ in range(self.clients)]
        self.kept = [{} for _ in range(self.clients)]
        self.errors: list = []
        self.go = threading.Event()
        self.deadline = 0.0
        self.threads = [
            threading.Thread(target=self._client, args=(c,), daemon=True)
            for c in range(self.clients)]
        for t in self.threads:
            t.start()

    def _client(self, c: int) -> None:
        requests, keep = self.env["requests"], self.env["keep"]
        read_response = self.env["read_response"]
        sock, buf = self.socks[c], bytearray()
        mine, answers, n_pool = self.rows[c], self.kept[c], len(requests)
        self.go.wait()
        i = c
        while True:
            t0 = time.perf_counter()
            if t0 >= self.deadline:
                return
            idx = i % n_pool
            try:
                sock.sendall(requests[idx])
                status, body = read_response(sock, buf)
            except OSError as e:
                mine.append((i, t0, time.perf_counter(), 0))
                self.errors.append(f"client {c}: {e!r}")
                return
            mine.append((i, t0, time.perf_counter(), status))
            if status == 200 and i < n_pool and keep[idx]:
                answers[idx] = body.decode()
            i += self.clients

    def start(self, t0: float, seconds: float) -> None:
        self.deadline = t0 + seconds
        self.seconds = seconds
        self.go.set()

    def join(self):
        for t in self.threads:
            t.join(timeout=self.seconds + 300)
        alive = sum(t.is_alive() for t in self.threads)
        for s in self.socks:
            s.close()
        rows = [r for mine in self.rows for r in mine]
        kept = {k: v for d in self.kept for k, v in d.items()}
        return rows, kept, self.errors, alive
