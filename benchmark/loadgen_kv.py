#!/usr/bin/env python3
"""A load-generator worker of the key-value cells: a JAX-free child of
run.py that owns some of the mix's connections and drives each in a closed
loop of ONE command in flight (memtier_benchmark's `--pipeline=1`): a
`SET` or a `GET` out, its reply in, the next command.

    python benchmark/loadgen_kv.py    (one JSON job on stdin's first line)

Protocol as loadgen.py's: prints `ready` once connected and generated;
reads `go <t>` (CLOCK_MONOTONIC seconds, shared by every process of the
host) and sends from t; reads `end <t1>`, starts no command after t1, waits
for what is in flight, then writes one pickle to stdout: per connection
the number of operations sent, when each was sent and each reply parsed,
the value EVERY `GET` answered (fixed-width, as an array — which reads
crossed another connection's write is only known once all connections are
in), and every reply of another shape than the expected one (`odd`).
"""

from __future__ import annotations

import json
import os
import pickle
import selectors
import socket
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen_kv       # noqa: E402
import traffic          # noqa: E402
from nodes import reply_end   # noqa: E402

OK = b"+OK\r\n"


class Client:
    """One connection's closed loop, one command in flight."""

    def __init__(self, conn: int, port: int, ops: traffic.ConnOps,
                 world, mix: dict):
        self.conn = conn
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.sent = 0
        self.done = 0
        self.kinds = ops.kinds.tolist()
        self.records = ops.records.tolist()
        self.world = world
        self.mix = mix
        n = len(self.kinds)
        self.t_sent = np.zeros(n, dtype=np.float64)
        self.t_done = np.zeros(n, dtype=np.float64)
        self.vals = np.zeros(n, dtype=f"S{world.width}")
        # a GET's expected reply frame: $<width>\r\n<value>\r\n
        self.head = b"$%d\r\n" % world.width
        self.frame = len(self.head) + world.width + 2
        self.odd = {}              # op -> raw reply of another shape
        self.failed = None

    def send_next(self) -> bool:
        i = self.sent
        if i >= len(self.kinds):
            # a faster node than the mix foresaw: say so, never just stop
            self.failed = "ran out of generated operations " \
                          "(the mix's max_ops_per_conn)"
            return False
        k = self.world.key(self.records[i])
        if self.kinds[i] == traffic.UPDATE:
            v = self.world.pool.value(
                traffic.write_serial(self.world.n, self.mix, self.conn, i))
            out = b"*3\r\n$3\r\nSET\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n" \
                % (len(k), k, len(v), v)
        else:
            out = b"*2\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(k), k)
        self.t_sent[i] = time.monotonic()
        self.sock.sendall(out)
        self.sent = i + 1
        return True

    def on_readable(self) -> bool:
        """-> whether the command in flight is answered."""
        data = self.sock.recv(1 << 12)
        if not data:
            raise ConnectionError("server closed the connection")
        buf = self.buf
        buf += data
        i = self.done
        if self.kinds[i] == traffic.UPDATE:
            if buf == OK:
                buf.clear()
                self.t_done[i] = time.monotonic()
                self.done = i + 1
                return True
        elif len(buf) == self.frame and buf.startswith(self.head):
            self.vals[i] = bytes(buf[len(self.head):-2])
            buf.clear()
            self.t_done[i] = time.monotonic()
            self.done = i + 1
            return True
        end = reply_end(buf, 0)
        if end < 0:
            return False
        self.odd[i] = bytes(buf[:end])
        del buf[:end]
        self.t_done[i] = time.monotonic()
        self.done = i + 1
        return True

    def result(self) -> dict:
        n = self.sent
        return {"conn": self.conn, "sent": n, "done": self.done, "depth": 1,
                "t_sent": self.t_sent[:n].copy(),
                "t_done": self.t_done[:n].copy(),
                "vals": self.vals[:n].copy(), "odd": self.odd,
                "failed": self.failed}


def run(job: dict) -> list:
    config, mix = job["config"], job["mix"]
    world = datagen_kv.build_world(config, job["seed"])
    clients = [Client(c, job["port"],
                      traffic.conn_ops(mix, world.n, 1, job["seed"], c),
                      world, mix)
               for c in job["conns"]]
    sel = selectors.DefaultSelector()
    for cl in clients:
        sel.register(cl.sock, selectors.EVENT_READ, cl)
    sys.stdout.buffer.write(b"ready\n")
    sys.stdout.buffer.flush()
    t0 = float(sys.stdin.readline().split()[1])
    t1 = float("inf")             # until the parent says `end <t1>`
    sel.register(sys.stdin, selectors.EVENT_READ, None)
    while time.monotonic() < t0:
        time.sleep(min(0.001, max(0.0, t0 - time.monotonic())))
    live = set()
    for cl in clients:
        if cl.send_next():
            live.add(cl)
    while live:
        events = sel.select(timeout=1.0)
        now = time.monotonic()
        if now > t1 + float(job["grace_seconds"]):
            for cl in live:
                cl.failed = f"no reply {now - t1:.0f}s after the window"
            break
        for key, _ in events:
            cl = key.data
            if cl is None:
                t1 = float(sys.stdin.readline().split()[1])
                sel.unregister(sys.stdin)
                continue
            if cl not in live:
                continue
            try:
                if cl.on_readable() and (time.monotonic() >= t1
                                         or not cl.send_next()):
                    live.discard(cl)
            except (OSError, ValueError) as e:
                cl.failed = f"{type(e).__name__}: {e}"
                live.discard(cl)
    for cl in clients:
        cl.sock.close()
    return [cl.result() for cl in clients]


def main() -> None:
    job = json.loads(sys.stdin.readline())
    results = run(job)
    pickle.dump(results, sys.stdout.buffer, protocol=pickle.HIGHEST_PROTOCOL)
    sys.stdout.buffer.flush()


if __name__ == "__main__":
    main()
