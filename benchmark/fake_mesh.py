#!/usr/bin/env python3
"""The plain reference of the mesh cells put in the program's place: one of
three RESP servers over reference_mesh.Replica that forward their writes to
each other, with the faults the control and the tests need.

    python benchmark/fake_mesh.py <port> <config.json> <seed> <fault> \\
        <node id> <peer id>:<peer port>,<peer id>:<peer port>

A client's HSET is stamped with the host's monotonic clock in nanoseconds
(never below the largest stamp this node has seen), applied, acknowledged,
and queued for every peer; a forwarder thread per peer ships what queued
up every FORWARD_MS as one `RAPPLY` command and waits for its answer
(asynchronous replication with a flush bound, as the program's).  A peer
applies a forwarded write by its stamp: last writer wins, the node id
breaking ties.  `none` answers as the reference does (a run against it is
`correct`).

The control breaks the rule that makes replicas converge:
  `arrival-wins`     a replica takes a peer's write by arrival, not by
                     stamp: two nodes' writes to one field that cross on
                     the links leave each node with the other's.
The faults a mesh cell can have, planted where they would arise:
  `drop-replicated`  one forwarded write in 1,000 never reaches one peer
                     (the sender counts it as sent);
  `stale-ack`        a write is acknowledged now and applied (and
                     forwarded) when the same connection's next write
                     arrives — reference.py's control, on every node.
It serves HSET, HGETALL, RAPPLY and INFO — INFO with the fields
scenarios/mesh.py reads of the program (`keys`, `connected_replicas`,
`repl_full_syncs`, `repl_log_last_uuid`, `span_repl_ingest_us`, one
`replica<i>` row a peer).  Nothing of the program is imported.
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen              # noqa: E402
from fake_node import read_command   # noqa: E402
from nodes import encode    # noqa: E402
from reference_mesh import Replica   # noqa: E402

FAULTS = ("none", "arrival-wins", "drop-replicated", "stale-ack")
EVERY = 1000
FORWARD_MS = 5.0


class Peer:
    """One outgoing link: what is queued for the peer, what was sent and
    answered, and the newest stamp applied FROM it."""

    def __init__(self, node_id: int, port: int):
        self.node_id, self.port = node_id, port
        self.queue = []
        self.i_sent = self.i_acked = self.he_sent = 0
        self.forwarded = 0
        self.connected = False


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
                    write = (int(cmd[1][4:]), cmd[2], cmd[3])
                    if srv.fault == "stale-ack":
                        if pending:
                            srv.local_write(*pending)
                        pending = write
                    else:
                        srv.local_write(*write)
                    out = b":0\r\n"
                elif verb == b"hgetall":
                    row = srv.table.hgetall(int(cmd[1][4:]))
                    out = b"*%d\r\n" % len(row) + b"".join(
                        b"*2\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n"
                        % (len(f), f, len(v), v)
                        for f, v in row.items())
                elif verb == b"rapply":
                    srv.apply_forwarded(int(cmd[1]), int(cmd[2]), cmd[3:])
                    out = b"+OK\r\n"
                elif verb == b"info":
                    text = srv.info().encode()
                    out = b"$%d\r\n%s\r\n" % (len(text), text)
                else:
                    out = b"-ERR unknown command\r\n"
            self.wfile.write(out)


class Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    request_queue_size = 64

    def local_write(self, rec: int, field: bytes, value: bytes) -> None:
        """A client's write: stamp, apply, queue for every peer (the lock
        is held)."""
        self.clock = stamp = max(self.clock + 1, time.monotonic_ns())
        self.table.apply(rec, field, value, stamp, self.node_id,
                         by_arrival=self.fault == "arrival-wins")
        self.last_write = stamp
        for p in self.peers:
            p.queue.append((rec, field, value, stamp))

    def apply_forwarded(self, origin: int, upto: int, flat: list) -> None:
        """A peer's batch: its stream's watermark after the batch, then
        (record, field, value, stamp) x n, flattened."""
        peer = next(p for p in self.peers if p.node_id == origin)
        by_arrival = self.fault == "arrival-wins"
        for k in range(0, len(flat), 4):
            stamp = int(flat[k + 3])
            self.table.apply(int(flat[k]), flat[k + 1], flat[k + 2], stamp,
                             origin, by_arrival=by_arrival)
        self.clock = max(self.clock, upto)
        peer.he_sent = upto         # moved only after the batch landed

    def info(self) -> str:
        rows = [f"keys:{self.table.world.n}", "engine:reference",
                "jax_backend:none", "span_repl_ingest_us:0",
                "repl_full_syncs:0",
                f"connected_replicas:{sum(p.connected for p in self.peers)}",
                f"repl_log_last_uuid:{self.last_write}"]
        for i, p in enumerate(self.peers):
            rows.append(f"replica{i}:addr=127.0.0.1:{p.port},node_id="
                        f"{p.node_id},i_sent={p.i_sent},i_acked={p.i_acked},"
                        f"he_sent={p.he_sent},he_acked={p.he_sent}")
        return "\r\n".join(rows) + "\r\n"


def forward(srv: Server, peer: Peer) -> None:
    """The link to one peer: connect (the peer may boot later), then ship
    the queue every FORWARD_MS and wait for the peer's answer."""
    sock = None
    while sock is None:
        try:
            sock = socket.create_connection(("127.0.0.1", peer.port),
                                            timeout=60)
        except OSError:
            time.sleep(0.05)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    rf = sock.makefile("rb")
    peer.connected = True
    while True:
        time.sleep(FORWARD_MS / 1e3)
        with srv.lock:
            batch, peer.queue = peer.queue, []
        if not batch:
            continue
        flat = []
        for rec, field, value, stamp in batch:
            peer.forwarded += 1
            if srv.fault == "drop-replicated" and \
                    peer.forwarded % EVERY == 0:
                continue
            flat += [rec, field, value, stamp]
        peer.i_sent = batch[-1][3]
        sock.sendall(encode([b"RAPPLY", srv.node_id, peer.i_sent] + flat))
        reply = rf.readline()
        if not reply.startswith(b"+OK"):
            raise SystemExit(f"peer {peer.node_id} answered {reply!r}")
        peer.i_acked = peer.i_sent


def main(argv: list) -> None:
    port, config_path, seed, fault, node_id, peers = argv
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r} (have {FAULTS})")
    with open(config_path) as f:
        config = json.load(f)
    world = datagen.build_world(config, int(seed))
    srv = Server(("127.0.0.1", int(port)), Handler)
    srv.table = Replica(world)
    srv.fault, srv.lock = fault, threading.Lock()
    srv.node_id, srv.clock, srv.last_write = int(node_id), 0, 0
    srv.peers = [Peer(*map(int, p.split(":"))) for p in peers.split(",")]
    for p in srv.peers:
        threading.Thread(target=forward, args=(srv, p), daemon=True).start()
    srv.serve_forever()


if __name__ == "__main__":
    main(sys.argv[1:])
