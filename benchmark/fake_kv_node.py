#!/usr/bin/env python3
"""The plain key-value reference put in the program's place: a RESP server
over reference_kv.RefStore, with the faults the control and the tests need
(fake_node.py's place for the register world).

    python benchmark/fake_kv_node.py <port> <config.json> <seed> <fault>

`none` answers as the reference does (a run against it is `correct`).
The control breaks the guarantee the configuration states — an
acknowledged `SET` is read back at once:
  `stale-ack`    a `SET` is acknowledged now and applied when the same
                 connection's next `SET` arrives (a deferred flush).
The faults a served cell can have, planted where the answer is produced:
  `drop-write`   one `SET` in 500 is acknowledged and never applied;
  `alter-answer` one `GET` in 500 has one byte of its value altered.
It serves SET, GET and INFO; nothing of the program is imported.
"""

from __future__ import annotations

import json
import os
import socketserver
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen_kv                   # noqa: E402
from fake_node import read_command  # noqa: E402
from reference_kv import OK, RefStore   # noqa: E402

FAULTS = ("none", "stale-ack", "drop-write", "alter-answer")
EVERY = 500


class Handler(socketserver.StreamRequestHandler):
    def handle(self):
        srv = self.server
        number = srv.store.world.number
        pending = None          # stale-ack: the write not yet applied
        while True:
            try:
                cmd = read_command(self.rfile)
            except (OSError, ValueError):
                return
            if cmd is None:
                return
            verb = cmd[0].lower()
            with srv.lock:
                if verb == b"set":
                    k = number(cmd[1])
                    srv.writes += 1
                    if srv.fault == "stale-ack":
                        if pending:
                            srv.store.set(*pending)
                        pending = (k, cmd[2])
                        out = OK
                    elif srv.fault == "drop-write" and \
                            srv.writes % EVERY == 0:
                        out = OK
                    else:
                        out = srv.store.set(k, cmd[2])
                elif verb == b"get":
                    srv.reads += 1
                    v = srv.store.get(number(cmd[1]))
                    if srv.fault == "alter-answer" and \
                            srv.reads % EVERY == 0:
                        v = b"#" + v[1:]
                    out = b"$%d\r\n%s\r\n" % (len(v), v)
                elif verb == b"info":
                    text = (f"keys:{srv.store.world.n}\r\n"
                            "engine:reference\r\njax_backend:none\r\n"
                            ).encode()
                    out = b"$%d\r\n%s\r\n" % (len(text), text)
                else:
                    out = b"-ERR unknown command\r\n"
            try:
                self.wfile.write(out)
            except OSError:
                return


class Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    request_queue_size = 1024


def main(argv: list) -> None:
    port, config_path, seed, fault = argv
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r} (have {FAULTS})")
    with open(config_path) as f:
        config = json.load(f)
    world = datagen_kv.build_world(config, int(seed))
    srv = Server(("127.0.0.1", int(port)), Handler)
    srv.store = RefStore(world)
    srv.fault, srv.lock = fault, threading.Lock()
    srv.writes = srv.reads = 0
    srv.serve_forever()


if __name__ == "__main__":
    main(sys.argv[1:])
