"""The plain reference of the mesh cells — three active-active replicas as
dictionaries — and the comparison that decides `correct` there.  It
imports nothing of the program: reference.py's table and reply parser, the
traffic generator, numpy.

**The replicas.**  `Replica` is reference.RefTable with a stamp beside
every written field: a write takes effect at a replica only if its
(stamp, node) is above the one the field holds there (last writer wins,
the node id breaking ties), whichever order the writes arrive in, so
replicas that have seen the same writes hold the same table.  fake_mesh.py
serves three of them that forward their writes to each other.

**Happened-before across nodes.**  Every process of a run is on one host
and reads one monotonic clock.  X -> Y means "X was over before Y began":
X's reply was parsed before Y's pipeline was sent, or X is earlier than Y
on one connection.  Where X and Y went to DIFFERENT nodes, X -> Y needs
the gap to be over `clock_margin_ms` (the configuration's; 2 ms): a
node stamps a write with its own clock in whole milliseconds (and a
sequence number within one), so a write acknowledged by one node and a
write sent to another inside the same millisecond or the next may carry
stamps in either order, and the store may keep either.  Past the margin
the later write's stamp is the larger on any node, because a node never
stamps below its clock and never acknowledges before it stamps.

**What a read may answer.**  Every write carries a value of its own
(datagen.ValuePool by serial), so a field of a reply names the write it
came from.  A read R sent to node N may answer, for each field, the
table's initial value or a write W sent to ANY node, unless
  * R -> W (the value comes from a write not yet sent), or
  * some write W' to that field, acknowledged BY N, has W -> W' -> R: N
    had acknowledged a later write before the read was sent, so N reads
    it back at once, whatever arrives from the peers afterwards — a peer's
    older write loses to it by its stamp.
Writes acknowledged by the OTHER nodes bind R to nothing: replication is
asynchronous, and N may not have seen them yet.

**Quiesce.**  After the window closed and every client is in, the
scenario (scenarios/mesh.py) polls the three nodes' INFO until, for every
ordered pair of nodes (A, B): A has pushed its whole log to B (`i_sent` of
A's row for B equals A's `repl_log_last_uuid`), B has acknowledged it
(`i_acked` at or past `i_sent`), B's pull watermark for A (`he_sent` of
B's row for A) is at or past A's last write — the program moves it only
after the covering batch LANDED in B's store — B has told A so (`he_acked`
equals `he_sent`), and no node's `repl_log_last_uuid` moved between two
polls.  `quiesce_max_seconds` without reaching that reads `not_quiesced`
1.  Beacons of idle links keep moving the watermarks past the last write;
that is why the pairs are compared with "at or past" and not "equal".

**After quiesce** the same records (the most-written half of the sample,
a seeded draw of the other written ones, and untouched ones) are read
back from each of the three nodes.  Per node and record, every field must
hold a write that no other write to it came after (`readback_wrong`; the
initial value where none wrote); and the three nodes' answers for a
record must be equal, fields compared as a set (`converge_wrong`, counted
by record).

The numbers are exact counts of answers that differ; each has the limit 0.
"""

from __future__ import annotations

import numpy as np

import reference
import traffic
from reference import RefTable, parse_hgetall

LIMITS = {"reads_wrong": 0, "acks_wrong": 0, "readback_wrong": 0,
          "converge_wrong": 0, "not_quiesced": 0, "never_answered": 0,
          "full_syncs": 0}


class Replica(RefTable):
    """One replica: the table, and for every written field the
    (stamp, node) of the write it holds."""

    def __init__(self, world):
        super().__init__(world)
        self.stamps = {}        # (record, field) -> (stamp, node)

    def apply(self, record: int, field: bytes, value: bytes, stamp: int,
              node: int, by_arrival: bool = False) -> bool:
        """A write with its stamp, from a client or from a peer: it takes
        effect if it is the last writer's (`by_arrival`: the control's
        broken rule — whatever arrives last wins)."""
        slot = (record, field)
        if not by_arrival and self.stamps.get(slot, (0, 0)) >= (stamp, node):
            return False
        self.stamps[slot] = (stamp, node)
        self.hset(record, field, value)
        return True


class MeshWrites(reference.Writes):
    """reference.Writes with the node every write went to, and the margin
    that "came after" needs between nodes."""

    def __init__(self, world, mix: dict, results: list, ops_of: dict,
                 node_of_conn: dict, margin_s: float):
        super().__init__(world, mix, results, ops_of)
        lookup = np.full(max(node_of_conn) + 1, -1, dtype=np.int64)
        for conn, node in node_of_conn.items():
            lookup[conn] = node
        self.node = lookup[self.conn.astype(np.int64)]
        self.margin = margin_s

    def after(self, g: slice, w: int) -> np.ndarray:
        gap = np.where(self.node[g] != self.node[w], self.margin, 0.0)
        return (self.ts[g] > self.td[w] + gap) | \
            ((self.conn[g] == self.conn[w]) & (self.idx[g] > self.idx[w]))

    def may_read_at(self, slot: int, initial: bytes, got: bytes, conn: int,
                    node: int, i: int, ts: float, td: float) -> bool:
        """May a read sent to `node` (connection, index, sent at ts,
        answered at td) answer `got` for this (record, field)?"""
        g = self.of(slot)
        if g.start == g.stop:
            return got == initial
        # writes the read's node had acknowledged before the read began
        before_r = ((self.node[g] == node) & (self.td[g] < ts)) | \
            ((self.conn[g] == conn) & (self.idx[g] < i))
        if got == initial and not before_r.any():
            return True
        for w in self.wrote(slot, g, got):
            future = self.ts[w] > td or \
                (self.conn[w] == conn and self.idx[w] > i)
            if not future and not (before_r & self.after(g, w)).any():
                return True
        return False


def readback_sample(writes: MeshWrites, world, seed: int, n_back: int) -> list:
    """Records to read back from every node: the most-written half (where
    the nodes' writes meet), a seeded draw of the other written ones, and
    n_back / 4 drawn from the whole table (untouched, nearly all)."""
    rng = np.random.default_rng([int(seed), 0x72656164])
    recs, counts = np.unique(writes.slot // world.fieldcount,
                             return_counts=True)
    if len(recs) <= n_back:
        pick = recs
    else:
        order = np.argsort(-counts, kind="stable")
        hot = recs[order[:n_back // 2]]
        rest = recs[order[n_back // 2:]]
        pick = np.concatenate([hot, rng.choice(rest, n_back - len(hot),
                                               replace=False)])
    cold = rng.integers(0, world.n, n_back // 4)
    return pick.tolist() + cold.tolist()


def check_mesh(world, mix: dict, seed: int, results: list, ops_of: dict,
               node_of_conn: dict, nodes: list, margin_ms: float,
               n_back: int, readback, quiesced: bool,
               full_syncs: int) -> dict:
    """`results`: every worker's per-connection records (loadgen.py's
    shape, from all nodes); `ops_of[conn]`: that connection's operations;
    `node_of_conn`: connection -> index into `nodes`;
    `readback(node, records)` -> raw HGETALL replies read from that node
    after quiesce.
    -> {"numbers": {name: count}, "compared": {...}, "first": str}"""
    fc = world.fieldcount
    writes = MeshWrites(world, mix, results, ops_of, node_of_conn,
                        margin_ms / 1e3)
    numbers = dict.fromkeys(LIMITS, 0)
    numbers["not_quiesced"] = 0 if quiesced else 1
    numbers["full_syncs"] = int(full_syncs)
    compared = {"reads": 0, "reads_crossing_writes": 0, "acks": 0,
                "readback": 0, "converged": 0}
    first = "" if quiesced else "not_quiesced: the links did not settle"

    def differ(name: str, what: str) -> None:
        nonlocal first
        numbers[name] += 1
        first = first or f"{name}: {what}"

    for res in results:
        conn, sent, done = res["conn"], res["sent"], res["done"]
        node = node_of_conn[conn]
        ops = ops_of[conn]
        if done < sent or res["failed"]:
            numbers["never_answered"] += max(1, sent - done)
            first = first or (f"never_answered: connection {conn} at "
                              f"{nodes[node]}: {res['failed']}")
        # every field the traffic writes exists: an HSET creates none
        compared["acks"] += len(res["acks"])
        for i, ack in res["acks"].items():
            if ack != b":0\r\n":
                differ("acks_wrong", f"conn {conn} at {nodes[node]} op {i} "
                       f"HSET {world.key(int(ops.records[i]))!r} answered "
                       f"{ack!r}, expected b':0\\r\\n'")
        ts = np.repeat(res["t_sent"], res["depth"])[:sent]
        for i in np.flatnonzero(ops.check[:done]).tolist():
            rec = int(ops.records[i])
            compared["reads"] += 1
            got = parse_hgetall(res["reads"].get(i, b""))
            want = world.initial(rec)
            if rec not in writes.records:
                ok = got == want
            else:
                compared["reads_crossing_writes"] += 1
                ok = got is not None and got.keys() == want.keys() and all(
                    writes.may_read_at(rec * fc + j, want[f], got[f], conn,
                                       node, i, float(ts[i]),
                                       float(res["t_done"][i]))
                    for j, f in enumerate(world.fields))
            if not ok:
                differ("reads_wrong", f"conn {conn} at {nodes[node]} op {i} "
                       f"HGETALL {world.key(rec)!r} answers what no "
                       "acknowledged or pending write left there")
    # after quiesce: the same records from every node
    sample = readback_sample(writes, world, seed, n_back)
    answers = []
    for n, name in enumerate(nodes):
        got_all = [parse_hgetall(raw) for raw in readback(n, sample)]
        answers.append(got_all)
        for rec, got in zip(sample, got_all):
            compared["readback"] += 1
            want = world.initial(rec)
            if not (got is not None and got.keys() == want.keys() and all(
                    writes.may_remain(rec * fc + j, want[f], got[f])
                    for j, f in enumerate(world.fields))):
                differ("readback_wrong", f"HGETALL {world.key(rec)!r} at "
                       f"{name} after quiesce is not the record's last "
                       "writes")
    for k, rec in enumerate(sample):
        compared["converged"] += 1
        if any(answers[n][k] != answers[0][k] or answers[n][k] is None
               for n in range(len(nodes))):
            differ("converge_wrong", f"HGETALL {world.key(rec)!r} differs "
                   "between nodes after quiesce")
    return {"numbers": numbers, "compared": compared, "first": first}


def conn_mix(mix: dict, peer: bool) -> dict:
    """The mix one connection's operations are generated from: the peers'
    connections compare a smaller share of their reads."""
    if not peer:
        return mix
    return dict(mix, check_share=mix["peer_check_share"])


def conn_layout(mix: dict) -> list:
    """[(node name, [connection ids])]: the mix's node first, then its
    peers, connection ids distinct across nodes (so every write's value
    is its own: traffic.write_serial)."""
    out = [(mix["node"], list(range(int(mix["connections"]))))]
    nxt = int(mix["connections"])
    for peer in mix["peers"]:
        k = int(mix["peer_connections"])
        out.append((peer, list(range(nxt, nxt + k))))
        nxt += k
    return out


def ops_for(mix: dict, world, seed: int) -> tuple:
    """-> (ops_of, node_of_conn, node names) for the whole mesh, from the
    seed alone."""
    layout = conn_layout(mix)
    ops_of, node_of = {}, {}
    for n, (_name, conns) in enumerate(layout):
        m = conn_mix(mix, peer=n > 0)
        for c in conns:
            ops_of[c] = traffic.conn_ops(m, world.n, world.fieldcount,
                                         seed, c)
            node_of[c] = n
    return ops_of, node_of, [name for name, _ in layout]
