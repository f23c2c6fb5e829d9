#!/usr/bin/env python3
"""The plain reference of YCSB workload D put in the program's place: a
RESP server over reference_d.RefTableD, with the faults the control and
the tests need.

    python benchmark/fake_d_node.py <port> <config.json> <seed> <fault>

`none` answers as the reference does (a run against it is `correct`).
The control breaks the guarantee the configuration states — an
acknowledged insert is read back at once:
  `stale-ack`     an insert is acknowledged now and applied when the same
                  connection's next insert arrives (a deferred flush).
The faults the cell can have, planted where the answer is produced:
  `drop-insert`   one insert in 50 is acknowledged and never applied;
  `partial`       one read in 50 of a whole record answers 9 of its 10
                  fields.
It serves HSET, HGETALL and INFO; nothing of the program is imported.
"""

from __future__ import annotations

import json
import os
import socketserver
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen                      # noqa: E402
from fake_node import read_command  # noqa: E402
from reference_d import RefTableD   # noqa: E402

FAULTS = ("none", "stale-ack", "drop-insert", "partial")
EVERY = 50


class Handler(socketserver.StreamRequestHandler):
    def handle(self):
        srv = self.server
        pending = None          # stale-ack: the insert not yet applied
        while True:
            cmd = read_command(self.rfile)
            if cmd is None:
                return
            verb = cmd[0].lower()
            with srv.lock:
                if verb == b"hset":
                    rec = int(cmd[1][4:])
                    pairs = list(zip(cmd[2::2], cmd[3::2]))
                    srv.writes += 1
                    if srv.fault == "stale-ack":
                        if pending:
                            srv.table.hset(*pending)
                        pending = (rec, pairs)
                        out = b":%d\r\n" % len(pairs)
                    elif srv.fault == "drop-insert" and \
                            srv.writes % EVERY == 0:
                        out = b":%d\r\n" % len(pairs)
                    else:
                        out = b":%d\r\n" % srv.table.hset(rec, pairs)
                elif verb == b"hgetall":
                    row = srv.table.hgetall(int(cmd[1][4:]))
                    if row:
                        srv.reads += 1
                        if srv.fault == "partial" and \
                                srv.reads % EVERY == 0:
                            row.pop(next(iter(row)))
                    out = b"*%d\r\n" % len(row) + b"".join(
                        b"*2\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n"
                        % (len(f), f, len(v), v)
                        for f, v in row.items())
                elif verb == b"info":
                    text = (f"keys:{srv.table.world.n}\r\nengine:reference\r\n"
                            f"jax_backend:none\r\n").encode()
                    out = b"$%d\r\n%s\r\n" % (len(text), text)
                else:
                    out = b"-ERR unknown command\r\n"
            self.wfile.write(out)


class Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


def main(argv: list) -> None:
    port, config_path, seed, fault = argv
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r} (have {FAULTS})")
    with open(config_path) as f:
        config = json.load(f)
    srv = Server(("127.0.0.1", int(port)), Handler)
    srv.table = RefTableD(datagen.build_world(config, int(seed)))
    srv.fault, srv.lock = fault, threading.Lock()
    srv.writes = srv.reads = 0
    srv.serve_forever()


if __name__ == "__main__":
    main(sys.argv[1:])
