#!/usr/bin/env python3
"""A load worker of YCSB workload D: loadgen.py's closed loop — a
pipeline of `depth` commands out, its replies in, the next pipeline — over
traffic_d.py's reads (`HGETALL`) and inserts (one `HSET` of every field of
a new record).

    python benchmark/loadgen_d.py    (one JSON job on stdin's first line)

Protocol and result as loadgen.py's (`ready`, `go <t>`, `end <t1>`, one
pickle of per-connection records).  Of a compared read a record keeps the
number of fields it answered and their digest (`got_n`, `got_d`, by
operation: reference_d.reply_digest), not its reply; of an insert its raw
reply (`acks`).
"""

from __future__ import annotations

import json
import os
import pickle
import selectors
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen          # noqa: E402
import loadgen          # noqa: E402
import traffic_d as T   # noqa: E402
from nodes import reply_end             # noqa: E402
from reference_d import reply_digest    # noqa: E402


class Client(loadgen.Client):
    """One connection's closed loop over workload D's operations."""

    def __init__(self, conn: int, port: int, ops: T.ConnOps, world,
                 mix: dict):
        super().__init__(conn, port, ops, world, mix)
        self.got_n = np.full(len(ops), -2, dtype=np.int8)
        self.got_d = np.zeros(len(ops), dtype=np.uint64)

    def send_pipeline(self) -> bool:
        lo = self.sent
        hi = min(lo + self.depth, len(self.kinds))
        if hi == lo:
            self.failed = "ran out of generated operations " \
                          "(the mix's max_ops_per_conn)"
            return False
        key, fields, initial = self.world.key, self.world.fields, \
            self.world.initial
        head = b"*%d\r\n$4\r\nHSET\r\n" % (2 + 2 * len(fields))
        out = []
        for kind, rec in zip(self.kinds[lo:hi].tolist(),
                             self.ops.records[lo:hi].tolist()):
            k = key(rec)
            if kind == T.INSERT:
                out.append(head + b"$%d\r\n%s\r\n" % (len(k), k) + b"".join(
                    b"$%d\r\n%s\r\n$%d\r\n%s\r\n" % (len(f), f, len(v), v)
                    for f, v in initial(rec).items()))
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
            if self.kinds[i] == T.INSERT:
                self.acks[i] = bytes(self.buf[self.pos:end])
            elif self.check[i]:
                self.got_n[i], self.got_d[i] = reply_digest(
                    bytes(self.buf[self.pos:end]))
            self.pos = end
            self.done += 1
        self.t_done[first:self.done] = now
        if self.pos > (1 << 20):
            del self.buf[:self.pos]
            self.pos = 0

    def result(self) -> dict:
        return dict(super().result(), got_n=self.got_n[:self.sent].copy(),
                    got_d=self.got_d[:self.sent].copy())


def run(job: dict) -> list:
    """loadgen.run's loop over this module's clients."""
    world = datagen.build_world(job["config"], job["seed"])
    clients = [Client(c, job["port"],
                      T.conn_ops(job["mix"], world.n, job["seed"], c), world,
                      job["mix"]) for c in job["conns"]]
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
    live = {cl for cl in clients if cl.send_pipeline()}
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
    results = run(json.loads(sys.stdin.readline()))
    pickle.dump(results, sys.stdout.buffer, protocol=pickle.HIGHEST_PROTOCOL)
    sys.stdout.buffer.flush()


if __name__ == "__main__":
    main()
