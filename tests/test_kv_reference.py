"""The plain key-value reference of the memtier cell
(benchmark/reference_kv.py), off the chip and small.

Pinned here, on histories written by hand (200 operations over 40 keys on
4 connections, one in flight each):
  * a sound history — every `GET` answers the latest `SET` that was over
    before it, or one still in flight — reads 0 in every number;
  * a `GET` that answers a value an acknowledged `SET` had already
    replaced, a `GET` of an untouched key that answers another key's
    value, an acknowledgement that is not `+OK`, a last write missing from
    the read-back, an operation never answered and a reply of another
    shape are each counted, in the right number;
  * the world's bulk values equal its one-by-one values, and a register
    world's snapshot boots a node that answers them (the cell's boot).
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

CONFIG = {"world": "memtier-registers", "recordcount": 40,
          "record": {"key_prefix": "memtier-", "valuelength": 32}}
MIX = {"operations": {"read": 10 / 11, "update": 1 / 11},
       "keys": {"kind": "uniform"}, "max_ops_per_conn": 50,
       "check_share": 1.0, "readback_records": 12}
SEED = 3700000077


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import datagen
        import datagen_kv
        import reference_kv
        import traffic
    finally:
        sys.path.remove(BENCH)

    class B:
        pass
    b = B()
    b.datagen, b.kv, b.ref, b.traffic = datagen, datagen_kv, reference_kv, \
        traffic
    return b


def history(bench, faults=()):
    """Four connections take turns, one operation at a time, against a
    dictionary: -> (world, results, ops_of, store).  `faults`: names of
    the defects to plant in what the connections record."""
    world = bench.kv.build_world(CONFIG, SEED)
    ops_of = {c: bench.traffic.conn_ops(MIX, world.n, 1, SEED, c)
              for c in range(4)}
    store = bench.ref.RefStore(world)
    res = {c: {"conn": c, "sent": 50, "done": 50, "depth": 1,
               "t_sent": np.zeros(50), "t_done": np.zeros(50),
               "vals": np.zeros(50, dtype="S32"), "odd": {}, "failed": None}
           for c in range(4)}
    t = 0.0
    stale = {}
    for i in range(50):
        for c in range(4):
            ops, r = ops_of[c], res[c]
            k = int(ops.records[i])
            r["t_sent"][i] = t
            if ops.kinds[i] == bench.traffic.UPDATE:
                stale.setdefault(k, store.get(k))
                store.set(k, world.pool.value(
                    bench.traffic.write_serial(world.n, MIX, c, i)))
            else:
                r["vals"][i] = store.get(k)
            r["t_done"][i] = t + 0.5
            t += 1.0
    written = sorted(stale)
    reads = [(c, i) for c in range(4) for i in range(50)
             if ops_of[c].kinds[i] == bench.traffic.READ]
    if "stale-read" in faults:
        # the last read of a written key answers what the key held before
        # its first write
        c, i = [(c, i) for c, i in reads
                if int(ops_of[c].records[i]) in stale
                and res[c]["vals"][i] != stale[int(ops_of[c].records[i])]][-1]
        res[c]["vals"][i] = stale[int(ops_of[c].records[i])]
    if "wrong-key" in faults:
        c, i = [(c, i) for c, i in reads
                if int(ops_of[c].records[i]) not in stale][0]
        res[c]["vals"][i] = world.initial((int(ops_of[c].records[i]) + 1)
                                          % world.n)
    if "bad-ack" in faults:
        c, i = [(c, i) for c in range(4) for i in range(50)
                if ops_of[c].kinds[i] == bench.traffic.UPDATE][0]
        res[c]["odd"][i] = b":0\r\n"
    if "nil" in faults:
        c, i = reads[3]
        res[c]["odd"][i] = b"$-1\r\n"
    if "lost-write" in faults:
        store.written.pop(written[0])
    if "unanswered" in faults:
        res[2]["done"] = 47
    return world, list(res.values()), ops_of, store


def check(bench, faults=()):
    world, results, ops_of, store = history(bench, faults)

    def readback(keys):
        return [b"$32\r\n%s\r\n" % store.get(k) for k in keys]
    return bench.ref.check_served_kv(world, MIX, SEED, results, ops_of,
                                     readback)


def test_a_sound_history_reads_zero_everywhere(bench):
    got = check(bench)
    assert got["numbers"] == dict.fromkeys(bench.ref.LIMITS, 0), got["first"]
    c = got["compared"]
    assert c["acks"] > 0 and c["reads_crossing_writes"] > 0
    assert c["reads"] > c["reads_crossing_writes"]
    assert c["readback"] >= 12


@pytest.mark.parametrize("fault,number,count", [
    ("stale-read", "reads_wrong", 1), ("wrong-key", "reads_wrong", 1),
    ("bad-ack", "acks_wrong", 1), ("nil", "reads_wrong", 1),
    ("lost-write", "readback_wrong", 1), ("unanswered", "never_answered", 3)])
def test_each_defect_is_counted(bench, fault, number, count):
    got = check(bench, (fault,))
    want = dict.fromkeys(bench.ref.LIMITS, 0)
    want[number] = count
    assert got["numbers"] == want, got["first"]
    assert got["first"].startswith(number)


def test_bulk_values_equal_single_values_and_the_snapshot_boots(bench,
                                                                tmp_path):
    from constdb_tpu.persist.snapshot import load_snapshot
    from constdb_tpu.server.node import Node
    world = bench.kv.build_world(dict(CONFIG, recordcount=3000), SEED)
    serials = np.array([0, 7, 2999, 3000 + 12345], dtype=np.int64)
    assert world.values_of(serials).tolist() == \
        [world.pool.value(int(s)) for s in serials]
    assert world.key(17) == b"memtier-17" and world.number(b"memtier-17") == 17
    path = str(tmp_path / "kv.snapshot")
    bench.datagen.write_snapshot(world, path, 1, "C", "127.0.0.1:1", 1)
    node = Node(node_id=1)
    load_snapshot(path, node.ks)
    for k in (0, 1499, 2999):
        kid = node.ks.lookup(world.key(k))
        assert kid >= 0 and node.ks.register_get(kid) == world.initial(k)
    assert node.ks.n_keys() == 3000
