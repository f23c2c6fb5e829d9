#!/usr/bin/env python3
"""A load-generator worker: a JAX-free child of run.py that owns some of
the mix's connections and drives each in a closed loop — a pipeline of
`depth` commands out, its replies in, the next pipeline.

    python benchmark/loadgen.py    (one JSON job on stdin's first line)

Protocol: prints `ready` once connected and generated; reads `go <t>`
(CLOCK_MONOTONIC seconds, shared by every process of the host) and sends
from t; reads `end <t1>` whenever the parent has seen the warm-up settle,
starts no pipeline after t1, waits for what is in flight, then writes
one pickle to stdout: per connection the number of operations sent, when
each pipeline was sent and each reply parsed, every write's acknowledgement
and the raw replies of the reads the seed marked for comparison.
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

import datagen          # noqa: E402
import traffic          # noqa: E402
from nodes import reply_end   # noqa: E402


class Client:
    """One connection's closed loop."""

    def __init__(self, conn: int, port: int, ops: traffic.ConnOps,
                 world, mix: dict):
        self.conn = conn
        self.ops = ops
        self.depth = int(mix["pipeline"])
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.pos = 0
        self.sent = 0              # operations sent
        self.done = 0              # replies parsed
        self.kinds = ops.kinds
        self.check = ops.check
        self.world = world
        self.rows = world.n * world.fieldcount
        self.mix = mix
        self.t_sent = []           # per pipeline
        self.t_done = np.zeros(len(ops), dtype=np.float64)
        self.acks = {}             # op -> raw reply of a write
        self.reads = {}            # op -> raw reply of a marked read
        self.failed = None

    def send_pipeline(self) -> bool:
        lo = self.sent
        hi = min(lo + self.depth, len(self.kinds))
        if hi == lo:
            # a faster node than the mix foresaw: say so, never just stop
            self.failed = "ran out of generated operations " \
                          "(the mix's max_ops_per_conn)"
            return False
        key, fields, pool = self.world.key, self.world.fields, self.world.pool
        out = []
        ops = self.ops
        for i, kind, rec, fld in zip(range(lo, hi),
                                     ops.kinds[lo:hi].tolist(),
                                     ops.records[lo:hi].tolist(),
                                     ops.fields[lo:hi].tolist()):
            k = key(rec)
            if kind == traffic.UPDATE:
                v = pool.value(traffic.write_serial(self.rows, self.mix,
                                                    self.conn, i))
                f = fields[fld]
                out.append(b"*4\r\n$4\r\nHSET\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n"
                           b"$%d\r\n%s\r\n" % (len(k), k, len(f), f,
                                               len(v), v))
            else:
                out.append(b"*2\r\n$7\r\nHGETALL\r\n$%d\r\n%s\r\n"
                           % (len(k), k))
        self.t_sent.append(time.monotonic())
        self.sock.sendall(b"".join(out))
        self.sent = hi
        return True

    def on_readable(self) -> None:
        data = self.sock.recv(1 << 18)
        if not data:
            raise ConnectionError("server closed the connection")
        if self.pos and self.pos == len(self.buf):
            self.buf.clear()
            self.pos = 0
        self.buf += data
        now = time.monotonic()
        first = self.done
        while self.done < self.sent:
            end = reply_end(self.buf, self.pos)
            if end < 0:
                break
            i = self.done
            if self.kinds[i] == traffic.UPDATE:
                self.acks[i] = bytes(self.buf[self.pos:end])
            elif self.check[i]:
                self.reads[i] = bytes(self.buf[self.pos:end])
            self.pos = end
            self.done += 1
        self.t_done[first:self.done] = now
        if self.pos > (1 << 20):
            del self.buf[:self.pos]
            self.pos = 0

    def result(self) -> dict:
        return {"conn": self.conn, "sent": self.sent, "done": self.done,
                "depth": self.depth, "t_sent": np.array(self.t_sent),
                "t_done": self.t_done[:self.sent].copy(),
                "acks": self.acks, "reads": self.reads,
                "failed": self.failed}


def run(job: dict) -> list:
    config, mix = job["config"], job["mix"]
    world = datagen.build_world(config, job["seed"])
    clients = [Client(c, job["port"],
                      traffic.conn_ops(mix, world.n, world.fieldcount,
                                       job["seed"], c), world, mix)
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
        if cl.send_pipeline():
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
                cl.on_readable()
                if cl.done == cl.sent and (time.monotonic() >= t1
                                           or not cl.send_pipeline()):
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
