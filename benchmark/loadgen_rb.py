#!/usr/bin/env python3
"""A load-generator worker of the redis-benchmark cell: a JAX-free child of
run.py that owns some of the mix's connections and drives each in a closed
loop of ONE command in flight (redis-benchmark's `-P 1`), the command
drawn from the default tests (traffic_rb.py).

    python benchmark/loadgen_rb.py    (one JSON job on stdin's first line)

Protocol as loadgen_kv.py's: prints `ready` once connected and generated;
reads `go <t>` and sends from t; reads `end <t1>`, starts no command after
t1, waits for what is in flight, then writes one pickle to stdout: per
connection the number of operations sent and answered, when each was sent
and its reply parsed, one number per reply (`num`: an integer reply's
value; a GET's value as its serial, -1 for nil; a SPOP's 1 for the member,
0 for nil; an LRANGE's length; a SET's 1 for +OK), every value of the
LRANGEs the comparison reads whole (`lr`), and every reply of another
shape than its command's (`odd`).
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

import traffic_rb as T          # noqa: E402
from nodes import reply_end     # noqa: E402

OK = b"+OK\r\n"
NIL = b"$-1\r\n"
ITEM = 4 + T.WIDTH + 2          # $3\r\n<3 bytes>\r\n


class Client:
    """One connection's closed loop, one command in flight."""

    def __init__(self, conn: int, port: int, cfg: dict, mix: dict):
        self.conn = conn
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.ops = T.conn_ops(mix, cfg["seed"], conn)
        self.kinds = self.ops.kinds.tolist()
        self.check = self.ops.check
        self.cfg, self.mix = cfg, mix
        self.sent = self.done = 0
        n = len(self.kinds)
        self.t_sent = np.zeros(n, dtype=np.float64)
        self.t_done = np.zeros(n, dtype=np.float64)
        self.num = np.zeros(n, dtype=np.int64)
        self.lr = {}                # op -> its LRANGE's values, joined
        self.odd = {}               # op -> raw reply of another shape
        self.member = b"$%d\r\n%s\r\n" % (len(cfg["member"]),
                                          cfg["member"].encode())
        self.failed = None

    def send_next(self) -> bool:
        i = self.sent
        if i >= len(self.kinds):
            self.failed = "ran out of generated operations " \
                          "(the mix's max_ops_per_conn)"
            return False
        out = T.command(self.cfg, self.mix, self.ops, self.conn, i)
        self.t_sent[i] = time.monotonic()
        self.sock.sendall(out)
        self.sent = i + 1
        return True

    def _take(self, end: int, value: int) -> bool:
        i = self.done
        self.num[i] = value
        del self.buf[:end]
        self.t_done[i] = time.monotonic()
        self.done = i + 1
        return True

    def on_readable(self) -> bool:
        """-> whether the command in flight is answered."""
        data = self.sock.recv(1 << 16)
        if not data:
            raise ConnectionError("server closed the connection")
        buf = self.buf
        buf += data
        i = self.done
        k = self.kinds[i]
        if buf[:1] == b":" and k in (T.INCR, T.LPUSH, T.RPUSH, T.SADD,
                                     T.HSET):
            end = buf.find(b"\r\n")
            if end < 0:
                return False
            return self._take(end + 2, int(buf[1:end]))
        if k == T.SET and buf[:5] == OK:
            return self._take(5, 1)
        if k in (T.GET, T.SPOP) and buf[:5] == NIL:
            return self._take(5, -1 if k == T.GET else 0)
        if k == T.GET and len(buf) >= ITEM and buf[:4] == b"$3\r\n" \
                and buf[7:9] == b"\r\n":
            return self._take(ITEM, int.from_bytes(buf[4:7], "big"))
        if k == T.SPOP and buf[:len(self.member)] == self.member:
            return self._take(len(self.member), 1)
        if k == T.LRANGE and buf[:1] == b"*":
            head = buf.find(b"\r\n")
            if head < 0:
                return False
            n = int(buf[1:head])
            end = head + 2 + n * ITEM
            if len(buf) < end:
                return False
            items = np.frombuffer(bytes(buf[head + 2:end]),
                                  dtype=np.uint8).reshape(n, ITEM)
            if n <= int(self.ops.stop[i]) + 1 and n >= 0 and \
                    (items[:, :4] == np.frombuffer(b"$3\r\n", np.uint8)).all() \
                    and (items[:, 7:] == np.frombuffer(b"\r\n",
                                                       np.uint8)).all():
                if self.check[i]:
                    self.lr[i] = items[:, 4:7].tobytes()
                return self._take(end, n)
        end = reply_end(buf, 0)
        if end < 0:
            return False
        self.odd[i] = bytes(buf[:end])
        return self._take(end, 0)

    def result(self) -> dict:
        n = self.sent
        return {"conn": self.conn, "sent": n, "done": self.done,
                "t_sent": self.t_sent[:n].copy(),
                "t_done": self.t_done[:n].copy(),
                "num": self.num[:n].copy(), "lr": self.lr, "odd": self.odd,
                "failed": self.failed}


def run(job: dict) -> list:
    cfg = dict(job["config"], seed=job["seed"])
    clients = [Client(c, job["port"], cfg, job["mix"]) for c in job["conns"]]
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
