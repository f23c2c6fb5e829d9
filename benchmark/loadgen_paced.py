#!/usr/bin/env python3
"""A paced load worker: loadgen.py's connections driven in an OPEN loop —
every connection sends its next pipeline of `depth` commands at its due
time, whether or not the last one was answered.

    python benchmark/loadgen_paced.py    (one JSON job on stdin's first line)

The job is loadgen.py's plus `rate_ops`: operations a second over all of
the job's connections together.  A connection's due times are `go` plus a
phase drawn from `--seed` (so the connections do not fire together) plus
whole multiples of depth x connections / rate: every seed sends the same
work in the same window.  A pipeline that cannot be sent at its due time
(the worker was busy, the socket full) is sent as soon as it can be, and
the schedule is kept: lateness is reported, never hidden by shifting what
follows.

Protocol and result as loadgen.py's (`ready`, `go <t>`, `end <t1>`, one
pickle of per-connection records), so one comparison reads both; each
record also carries `late_ms`, per pipeline the send time less the due
time.
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
import traffic          # noqa: E402
from loadgen import Client   # noqa: E402


def phase_of(seed: int, conn: int, interval: float) -> float:
    rng = np.random.default_rng([int(seed), 0x70616365, conn])
    return float(rng.random()) * interval


def run(job: dict) -> list:
    config, mix = job["config"], job["mix"]
    world = datagen.build_world(config, job["seed"])
    clients = [Client(c, job["port"],
                      traffic.conn_ops(mix, world.n, world.fieldcount,
                                       job["seed"], c), world, mix)
               for c in job["conns"]]
    interval = int(mix["pipeline"]) * len(clients) / float(job["rate_ops"])
    sel = selectors.DefaultSelector()
    for cl in clients:
        sel.register(cl.sock, selectors.EVENT_READ, cl)
    sys.stdout.buffer.write(b"ready\n")
    sys.stdout.buffer.flush()
    t0 = float(sys.stdin.readline().split()[1])
    t1 = float("inf")             # until the parent says `end <t1>`
    sel.register(sys.stdin, selectors.EVENT_READ, None)
    due = {cl: t0 + phase_of(job["seed"], cl.conn, interval)
           for cl in clients}
    late = {cl: [] for cl in clients}
    dead = set()

    def bury(cl, why: str) -> None:
        cl.failed = cl.failed or why
        dead.add(cl)
        sel.unregister(cl.sock)

    while True:
        now = time.monotonic()
        sending = now < t1
        if not sending and all(cl.done == cl.sent for cl in clients
                               if cl not in dead):
            break
        if now > t1 + float(job["grace_seconds"]):
            for cl in clients:
                if cl.done < cl.sent and cl not in dead:
                    cl.failed = f"no reply {now - t1:.0f}s after the window"
            break
        if sending:
            for cl in clients:
                if cl not in dead and due[cl] <= now and due[cl] < t1:
                    try:
                        if not cl.send_pipeline():
                            bury(cl, "")
                            continue
                    except OSError as e:
                        bury(cl, f"{type(e).__name__}: {e}")
                        continue
                    late[cl].append((cl.t_sent[-1] - due[cl]) * 1e3)
                    due[cl] += interval
        nxt = min((due[cl] for cl in clients if cl not in dead),
                  default=now + 0.05)
        wait = min(0.05, max(0.0, nxt - time.monotonic())) if sending \
            else 0.05
        for key, _ in sel.select(timeout=wait):
            cl = key.data
            if cl is None:
                t1 = float(sys.stdin.readline().split()[1])
                sel.unregister(sys.stdin)
                continue
            if cl in dead:
                continue
            try:
                cl.on_readable()
            except (OSError, ValueError) as e:
                bury(cl, f"{type(e).__name__}: {e}")
    for cl in clients:
        cl.sock.close()
    return [dict(cl.result(), late_ms=np.array(late[cl])) for cl in clients]


def main() -> None:
    job = json.loads(sys.stdin.readline())
    results = run(job)
    pickle.dump(results, sys.stdout.buffer, protocol=pickle.HIGHEST_PROTOCOL)
    sys.stdout.buffer.flush()


if __name__ == "__main__":
    main()
