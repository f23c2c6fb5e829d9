"""Three active-active replicas against the plain reference of the mesh
cells (benchmark/reference_mesh.py), off the chip and small.

Pinned here:
  * the reference's own arithmetic, on histories written by hand: a sound
    history reads 0 everywhere; a read that returns a value its node had
    already overwritten, a record that differs on one node after quiesce,
    and a replicated write that never reached one peer are each counted
    once; two nodes' writes inside `clock_margin_ms` may survive in either
    order, past it only the later one may;
  * three `--engine cpu` nodes of the program at 2,000 records, booted
    from snapshots that list each other (scenarios/mesh.py's recipe): the
    links come up by partial replay (`repl_full_syncs` 0 on every node), a
    concurrent seeded phase — closed loops at one node, paced loops at the
    other two, all on one zipfian — is judged by the reference with every
    number 0, and a sequential phase (one write at a time, nodes in turn,
    gaps over the margin) leaves on EVERY node exactly the record the
    reference holds.
"""

import importlib.util
import os
import pickle
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported by name as its own files do."""
    sys.path.insert(0, BENCH)
    try:
        import datagen
        import nodes
        import reference_mesh
        import traffic
        spec = importlib.util.spec_from_file_location(
            "scenario_mesh", os.path.join(BENCH, "scenarios", "mesh.py"))
        mesh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mesh)
    finally:
        sys.path.remove(BENCH)

    class B:
        pass
    b = B()
    b.datagen, b.nodes, b.ref, b.traffic, b.mesh = \
        datagen, nodes, reference_mesh, traffic, mesh
    return b


CONFIG = {
    "world": "ycsb-hash", "recordcount": 2000,
    "record": {"fieldcount": 10, "fieldlength": 100},
    "nodes": {"C": {"engine": "cpu", "node_id": 1, "settings": {}},
              "P1": {"engine": "cpu", "node_id": 2, "settings": {}},
              "P2": {"engine": "cpu", "node_id": 3, "settings": {}}},
    "clock_margin_ms": 2, "snapshot_compress_level": 1,
    "boot_timeout_s": 120, "links_timeout_s": 60,
}
MIX = {
    "operations": {"read": 0.5, "update": 0.5},
    "keys": {"kind": "zipfian", "constant": 0.99},
    "node": "C", "connections": 2, "pipeline": 8, "workers": 1,
    "peers": ["P1", "P2"], "peer_connections": 1, "peer_rate_ops": 400,
    "peer_check_share": 0.5, "max_ops_per_conn": 20000, "check_share": 0.5,
    "readback_records": 200, "grace_seconds": 30,
}
NAMES = ["C", "P1", "P2"]
SEED = 2147483777          # over 31 bits, as the driver's seeds are


# ------------------------------------------------ the reference, by hand


def hgetall_bytes(row: dict) -> bytes:
    return b"*%d\r\n" % len(row) + b"".join(
        b"*2\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n" % (len(f), f, len(v), v)
        for f, v in row.items())


class History:
    """Operations written out by hand: one connection a node (0, 1, 2),
    pipelines of one, and what each node answers after quiesce."""

    def __init__(self, bench):
        self.b = bench
        self.world = bench.datagen.build_world(dict(CONFIG, recordcount=50),
                                               SEED)
        self.ops = {c: [] for c in range(3)}    # (kind, rec, fld, ts, td, got)

    def value(self, conn: int, i: int) -> bytes:
        rows = self.world.n * self.world.fieldcount
        return self.world.pool.value(
            self.b.traffic.write_serial(rows, MIX, conn, i))

    def write(self, conn, rec, fld, ts, td) -> bytes:
        self.ops[conn].append(("w", rec, fld, ts, td, b":0\r\n"))
        return self.value(conn, len(self.ops[conn]) - 1)

    def read(self, conn, rec, ts, td, fields: dict) -> None:
        row = self.world.initial(rec)
        row.update({self.world.fields[f]: v for f, v in fields.items()})
        self.ops[conn].append(("r", rec, 0, ts, td, hgetall_bytes(row)))

    def check(self, after: dict) -> dict:
        """`after[node][rec] = {field index: value}`: what differs from
        the initial record on that node after quiesce."""
        T = self.b.traffic
        results, ops_of = [], {}
        for conn, ops in self.ops.items():
            n = len(ops)
            kinds = np.array([T.UPDATE if o[0] == "w" else T.READ
                              for o in ops], dtype=np.int8)
            ops_of[conn] = T.ConnOps(
                kinds, np.array([o[1] for o in ops], dtype=np.int64),
                np.array([o[2] for o in ops], dtype=np.int64),
                kinds == T.READ)
            results.append({
                "conn": conn, "sent": n, "done": n, "depth": 1,
                "t_sent": np.array([o[3] for o in ops], dtype=float),
                "t_done": np.array([o[4] for o in ops], dtype=float),
                "acks": {i: o[5] for i, o in enumerate(ops) if o[0] == "w"},
                "reads": {i: o[5] for i, o in enumerate(ops) if o[0] == "r"},
                "failed": None})

        def readback(node: int, records: list) -> list:
            out = []
            for rec in records:
                row = self.world.initial(rec)
                row.update({self.world.fields[f]: v for f, v in
                            after.get(node, {}).get(rec, {}).items()})
                out.append(hgetall_bytes(row))
            return out

        return self.b.ref.check_mesh(
            self.world, MIX, SEED, results, ops_of,
            {c: c for c in range(3)}, NAMES, CONFIG["clock_margin_ms"], 40,
            readback, True, 0)["numbers"]


def test_a_sound_history_reads_zero_everywhere(bench):
    h = History(bench)
    w1 = h.write(0, 7, 3, 1.000, 1.001)          # at node 0
    w2 = h.write(1, 7, 3, 1.010, 1.011)          # at node 1, 9 ms later
    h.read(1, 7, 1.020, 1.021, {3: w2})          # node 1 reads its own back
    h.read(0, 7, 1.020, 1.021, {3: w1})          # node 0 has not seen w2 yet
    h.read(2, 7, 1.020, 1.021, {})               # node 2 has seen neither
    got = h.check({n: {7: {3: w2}} for n in range(3)})
    assert set(got.values()) == {0}, got


def test_a_read_of_an_overwritten_value_is_counted_once(bench):
    h = History(bench)
    w1 = h.write(0, 7, 3, 1.000, 1.001)
    w2 = h.write(1, 7, 3, 1.010, 1.011)
    h.read(1, 7, 1.020, 1.021, {3: w1})          # node 1 acknowledged w2
    got = h.check({n: {7: {3: w2}} for n in range(3)})
    assert got.pop("reads_wrong") == 1 and set(got.values()) == {0}, got


def test_inside_the_margin_either_write_may_survive(bench):
    for gap_ms, survivor_may_be_first in ((1.0, True), (5.0, False)):
        h = History(bench)
        w1 = h.write(0, 7, 3, 1.000, 1.001)
        h.write(1, 7, 3, 1.001 + gap_ms / 1e3, 1.012)
        got = h.check({n: {7: {3: w1}} for n in range(3)})
        # all three nodes agree on w1: fine inside the margin; past it w1
        # is a write that another came after, on every node
        assert got["converge_wrong"] == 0
        assert (got["readback_wrong"] == 0) is survivor_may_be_first, got


def test_a_record_that_differs_on_one_node_is_counted_once(bench):
    h = History(bench)
    w1 = h.write(0, 7, 3, 1.000, 1.001)
    w2 = h.write(1, 7, 3, 1.0015, 1.002)         # inside the margin
    got = h.check({0: {7: {3: w1}}, 1: {7: {3: w2}}, 2: {7: {3: w2}}})
    # either may survive, so no node's answer is wrong by itself — but
    # they are not the same answer
    assert got.pop("converge_wrong") == 1 and set(got.values()) == {0}, got


def test_a_lost_replicated_write_is_counted_once(bench):
    h = History(bench)
    w1 = h.write(0, 9, 0, 1.000, 1.001)
    got = h.check({0: {9: {0: w1}}, 1: {9: {0: w1}}, 2: {}})
    assert got["converge_wrong"] == 1       # one record apart
    assert got["readback_wrong"] == 1       # wrong at the one node it missed
    assert got["reads_wrong"] == got["acks_wrong"] == 0


def test_replica_applies_by_stamp_unless_told_by_arrival(bench):
    world = bench.datagen.build_world(dict(CONFIG, recordcount=50), SEED)
    a, b = bench.ref.Replica(world), bench.ref.Replica(world)
    f = world.fields[2]
    for r, order in ((a, ((5, 1, b"x"), (9, 2, b"y"))),
                     (b, ((9, 2, b"y"), (5, 1, b"x")))):
        for stamp, node, val in order:
            r.apply(4, f, val, stamp, node)
    assert a.hgetall(4) == b.hgetall(4) and a.hgetall(4)[f] == b"y"
    assert a.apply(4, f, b"z", 9, 3) and not a.apply(4, f, b"w", 9, 1)
    b.apply(4, f, b"x", 5, 1, by_arrival=True)   # the control's rule
    assert b.hgetall(4)[f] == b"x"


# ------------------------------------------------- the program, three nodes


def test_three_nodes_from_snapshots_that_list_each_other(bench, tmp_path):
    mesh, nodes_mod, ref = bench.mesh, bench.nodes, bench.ref
    work = str(tmp_path)
    servers = nodes_mod.Servers(work, rehearse=True)
    world = bench.datagen.build_world(CONFIG, SEED)
    try:
        ports, conns = mesh.boot(servers, work, CONFIG, SEED, NAMES, "",
                                 lambda msg: None)
        addrs = {n: f"127.0.0.1:{ports[n]}" for n in NAMES}
        for n, c in conns.items():
            info = c.info()
            assert info["connected_replicas"] == "2", (n, info)
            assert info["repl_full_syncs"] == "0"
            assert len(mesh.replica_rows(info)) == 2

        # ---- concurrent, seeded: judged by the reference
        workers = mesh.start_workers(CONFIG, MIX, SEED, ports)
        t_go = time.monotonic() + 0.2
        for line in (b"go %.6f\n" % t_go, b"end %.6f\n" % (t_go + 1.5)):
            for _n, p in workers:
                p.stdin.write(line)
                p.stdin.flush()
            # a worker reads `go` with a buffered readline and waits for
            # `end` on the descriptor: the two must not share a write
            time.sleep(max(0.0, t_go + 0.3 - time.monotonic()))
        results = []
        for _n, p in workers:
            results.extend(pickle.load(p.stdout))
            assert p.wait() == 0
        quiesced, _took = mesh.quiesce(conns, addrs, 20.0)
        assert quiesced
        infos = {n: c.info() for n, c in conns.items()}
        ops_of, node_of, names = ref.ops_for(MIX, world, SEED)
        assert names == NAMES and sorted(ops_of) == [0, 1, 2, 3]

        def readback(n: int, records: list) -> list:
            c = nodes_mod.Conn(ports[NAMES[n]])
            try:
                return c.raw_replies([("HGETALL", world.key(r))
                                      for r in records])
            finally:
                c.close()

        check = ref.check_mesh(
            world, MIX, SEED, results, ops_of, node_of, NAMES,
            CONFIG["clock_margin_ms"], MIX["readback_records"], readback,
            quiesced, sum(int(i["repl_full_syncs"]) for i in infos.values()))
        assert set(check["numbers"].values()) == {0}, check
        seen = check["compared"]
        assert seen["acks"] > 500 and seen["reads_crossing_writes"] > 100
        assert seen["converged"] >= 200
        # the peers' writes reached C over the links, and C's reached them
        assert int(infos["C"]["repl_frames_coalesced"]) > 100
        assert int(infos["C"]["repl_ops_out"]) > 100
        assert int(infos["P1"]["span_repl_ingest_n"]) > 0

        # ---- sequential: one write at a time, nodes in turn, gaps over
        # the margin — the reference's answer is exact
        table = ref.Replica(world)
        records = [3, 40, 1999]
        step = 0
        for rnd in range(2):
            for rec in records:
                for j, field in enumerate(world.fields):
                    value = b"seq-%d-%d-%d" % (rnd, rec, j)
                    name = NAMES[step % 3]
                    assert conns[name].cmd("HSET", world.key(rec), field,
                                           value) == 0
                    table.apply(rec, field, value, step + 1, step % 3)
                    step += 1
                    time.sleep(0.003)
        quiesced, _took = mesh.quiesce(conns, addrs, 20.0)
        assert quiesced
        for n in range(3):
            for rec, raw in zip(records, readback(n, records)):
                assert ref.parse_hgetall(raw) == table.hgetall(rec), \
                    (NAMES[n], rec)
        assert all(c.info()["repl_full_syncs"] == "0"
                   for c in conns.values())
    finally:
        servers.kill_all()
