#!/usr/bin/env python3
"""The plain redis-benchmark reference put in the program's place: a RESP
server over reference_rb.RefStore, with the faults the control and the
tests need (fake_kv_node.py's place for the five keys).

    python benchmark/fake_rb_node.py <port> <config.json> <seed> <fault>

`none` answers as the reference does (a run against it is `correct`).
The control breaks the guarantee the configuration states — an
acknowledged write is read back at once:
  `stale-ack`    a SET, HSET or push is acknowledged now (a push with the
                 length it would make) and applied when the same
                 connection's next such write arrives (a deferred flush).
The faults a served cell can have, planted where the answer is produced:
  `drop-write`   one SET, HSET, push, SADD or SPOP in 500 of its kind is
                 acknowledged (a SADD :1 or a SPOP's member where the
                 member would be added or taken) and never applied;
                 `drop-write:sadd,spop` drops only the verbs it lists;
  `alter-answer` one GET or LRANGE in 500 has one byte of a value altered.
It serves the mix's commands, the read-backs' and INFO; nothing of the
program is imported.
"""

from __future__ import annotations

import os
import socketserver
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fake_node import read_command     # noqa: E402
from reference_rb import RefStore      # noqa: E402

FAULTS = ("none", "stale-ack", "drop-write", "alter-answer")
EVERY = 500
DEFERRABLE = (b"set", b"hset", b"lpush", b"rpush")
DROPPABLE = DEFERRABLE + (b"sadd", b"spop")


def _alter(out: bytes) -> bytes:
    """One byte of the reply's last value changed (its frame kept); a nil
    or an empty list has none."""
    if out in (b"$-1\r\n", b"*0\r\n", b"$0\r\n\r\n"):
        return out
    return out[:-3] + bytes([out[-3] ^ 1]) + out[-2:]


class Handler(socketserver.StreamRequestHandler):
    def handle(self):
        srv = self.server
        store = srv.store
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
                if verb in srv.drop:
                    srv.writes[verb] = srv.writes.get(verb, 0) + 1
                if srv.fault == "stale-ack" and verb in DEFERRABLE:
                    if pending:
                        apply(store, pending)
                    pending = cmd
                    out = ack(store, cmd)
                elif verb in srv.drop and srv.writes[verb] % EVERY == 0:
                    out = ack(store, cmd)
                elif verb == b"info":
                    text = b"keys:0\r\nengine:reference\r\njax_backend:none\r\n"
                    out = b"$%d\r\n%s\r\n" % (len(text), text)
                else:
                    out = apply(store, cmd)
                    if verb in (b"get", b"lrange"):
                        srv.reads += 1
                        if srv.fault == "alter-answer" and \
                                srv.reads % EVERY == 0:
                            out = _alter(out)
            try:
                self.wfile.write(out)
            except OSError:
                return


def ack(store: RefStore, cmd: list) -> bytes:
    """The reply a write would have, without applying it."""
    verb = cmd[0].lower()
    if verb == b"set":
        return b"+OK\r\n"
    if verb == b"hset":
        return b":%d\r\n" % (cmd[2] not in store.hashes.get(cmd[1], {}))
    if verb == b"sadd":
        return b":%d\r\n" % (cmd[2] not in store.sets.get(cmd[1], ()))
    if verb == b"spop":
        s = store.sets.get(cmd[1])
        if not s:
            return b"$-1\r\n"
        m = next(iter(s))
        return b"$%d\r\n%s\r\n" % (len(m), m)
    return b":%d\r\n" % (len(store.lists.get(cmd[1], ())) + 1)


def apply(store: RefStore, cmd: list) -> bytes:
    verb, args = cmd[0].lower(), cmd[1:]
    if verb == b"set":
        return store.set(*args)
    if verb == b"get":
        return store.get(*args)
    if verb == b"incr":
        return store.incr(*args)
    if verb in (b"lpush", b"rpush"):
        return store.push(args[0], args[1], verb == b"lpush")
    if verb == b"lrange":
        return store.lrange(args[0], int(args[1]), int(args[2]))
    if verb == b"sadd":
        return store.sadd(*args)
    if verb == b"spop":
        return store.spop(*args)
    if verb == b"smembers":
        return store.smembers(*args)
    if verb == b"hset":
        return store.hset(*args)
    if verb == b"hget":
        return store.hget(*args)
    return b"-ERR unknown command\r\n"


class Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    request_queue_size = 1024


def main(argv: list) -> None:
    port, _config_path, _seed, fault = argv
    fault, _, verbs = fault.partition(":")
    drop = tuple(v.encode() for v in verbs.split(",")) if verbs \
        else DROPPABLE
    if fault not in FAULTS or not set(drop) <= set(DROPPABLE) or \
            (verbs and fault != "drop-write"):
        raise SystemExit(f"unknown fault {argv[3]!r} (have {FAULTS}; "
                         f"drop-write may list verbs of {DROPPABLE})")
    srv = Server(("127.0.0.1", int(port)), Handler)
    srv.store = RefStore()
    srv.fault, srv.lock = fault, threading.Lock()
    srv.drop = drop if fault == "drop-write" else ()
    srv.writes, srv.reads = {}, 0
    srv.serve_forever()


if __name__ == "__main__":
    main(sys.argv[1:])
