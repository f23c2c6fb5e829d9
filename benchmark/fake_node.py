#!/usr/bin/env python3
"""The plain reference put in the program's place: a RESP server over
reference.RefTable, with the faults the control and the tests need.

    python benchmark/fake_node.py <port> <config.json> <seed> <fault>

`none` answers as the reference does (a run against it is `correct`).
The control breaks the guarantee the configuration states — an
acknowledged write is read back at once:
  `stale-ack`    a write is acknowledged now and applied when the same
                 connection's next write arrives (a deferred flush).
The faults a served cell can have, planted where the answer is produced:
  `drop-write`   one write in 500 is acknowledged and never applied (the
                 step returned its state unchanged);
  `alter-answer` one read in 500 has one byte of one value altered.
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

import datagen              # noqa: E402
from reference import RefTable   # noqa: E402

FAULTS = ("none", "stale-ack", "drop-write", "alter-answer")
EVERY = 500


def read_command(rf):
    line = rf.readline()
    if not line:
        return None
    n = int(line[1:])
    parts = []
    for _ in range(n):
        size = int(rf.readline()[1:])
        parts.append(rf.read(size + 2)[:-2])
    return parts


class Handler(socketserver.StreamRequestHandler):
    def handle(self):
        srv = self.server
        pending = None          # stale-ack: the write not yet applied
        while True:
            cmd = read_command(self.rfile)
            if cmd is None:
                return
            verb = cmd[0].lower()
            with srv.lock:
                if verb == b"hset":
                    rec = int(cmd[1][4:])
                    srv.writes += 1
                    if srv.fault == "stale-ack":
                        if pending:
                            srv.table.hset(*pending)
                        pending = (rec, cmd[2], cmd[3])
                        out = b":0\r\n"
                    elif srv.fault == "drop-write" and \
                            srv.writes % EVERY == 0:
                        out = b":0\r\n"
                    else:
                        out = b":%d\r\n" % srv.table.hset(rec, cmd[2], cmd[3])
                elif verb == b"hgetall":
                    rec = int(cmd[1][4:])
                    srv.reads += 1
                    row = srv.table.hgetall(rec)
                    if srv.fault == "alter-answer" and \
                            srv.reads % EVERY == 0:
                        f = next(iter(row))
                        row[f] = b"#" + row[f][1:]
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
    world = datagen.build_world(config, int(seed))
    srv = Server(("127.0.0.1", int(port)), Handler)
    srv.table = RefTable(world)
    srv.fault, srv.lock = fault, threading.Lock()
    srv.writes = srv.reads = 0
    srv.serve_forever()


if __name__ == "__main__":
    main(sys.argv[1:])
