"""The plain reference of YCSB workload D (benchmark/reference_d.py), off
the chip and small.

Pinned here, on histories written by hand (two connections, one operation
in flight each, the plain table's own answers):
  * a sound history reads 0 in every number;
  * an empty read after its insert was acknowledged, a partial record, a
    wrong insert reply, an insert missing from the read-back and an
    operation never answered are each counted, in the right number;
  * an empty read that overlapped its insert is not counted, and a whole
    record read before its insert was sent is.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

CONFIG = {"world": "ycsb-hash", "recordcount": 50,
          "record": {"fieldcount": 10, "fieldlength": 100}}
MIX = {"operations": {"read": 0.8, "insert": 0.2},
       "keys": {"kind": "latest", "constant": 0.99}, "lag": 1,
       "connections": 2, "max_ops_per_conn": 40, "check_share": 1.0,
       "readback_records": 10}
SEED = 4500000042


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import datagen
        import reference_d
        import traffic_d
    finally:
        sys.path.remove(BENCH)

    class B:
        pass
    b = B()
    b.datagen, b.ref, b.T = datagen, reference_d, traffic_d
    return b


def encode(fields: dict) -> bytes:
    return b"*%d\r\n" % len(fields) + b"".join(
        b"*2\r\n$%d\r\n%s\r\n$%d\r\n%s\r\n" % (len(f), f, len(v), v)
        for f, v in fields.items())


def history(bench):
    """The connections take turns, one operation each, against the plain
    table: op j of the whole history is sent at 10 j and answered at
    10 j + 1.  -> (world, ops_of, results, table)"""
    world = bench.datagen.build_world(CONFIG, SEED)
    ops_of = {c: bench.T.conn_ops(MIX, world.n, SEED, c) for c in (0, 1)}
    table = bench.ref.RefTableD(world)
    count = MIX["max_ops_per_conn"]
    results = {c: {"conn": c, "sent": count, "done": count, "depth": 1,
                   "t_sent": np.zeros(count), "t_done": np.zeros(count),
                   "acks": {}, "got_n": np.full(count, -2, dtype=np.int8),
                   "got_d": np.zeros(count, dtype=np.uint64),
                   "failed": None} for c in (0, 1)}
    for step in range(2 * count):
        c, i = step % 2, step // 2
        res, ops = results[c], ops_of[c]
        res["t_sent"][i], res["t_done"][i] = 10.0 * step, 10.0 * step + 1
        rec = int(ops.records[i])
        if ops.kinds[i] == bench.T.INSERT:
            pairs = list(world.initial(rec).items())
            res["acks"][i] = b":%d\r\n" % table.hset(rec, pairs)
        elif ops.check[i]:
            res["got_n"][i], res["got_d"][i] = bench.ref.reply_digest(
                encode(table.hgetall(rec)))
    return world, ops_of, [results[0], results[1]], table


def check(bench, world, ops_of, results, table, drop=()):
    def readback(records):
        return [b"*0\r\n" if r in drop else encode(table.hgetall(r))
                for r in records]
    return bench.ref.check_served_d(world, MIX, SEED, results, ops_of,
                                    readback)


def reads_of_inserts(bench, ops_of, results, world, acked_before: bool):
    """(conn, op) of the compared reads of an inserted record whose
    insert was (or was not) acknowledged before the read was sent."""
    out = []
    for res in results:
        ops = ops_of[res["conn"]]
        for i in np.flatnonzero(ops.check & (ops.records >= world.n)):
            c, k = bench.T.insert_of(int(ops.records[i]), world.n, 2)
            other = results[c]
            j = np.flatnonzero(ops_of[c].kinds == bench.T.INSERT)
            if k >= len(j):
                continue
            before = other["t_done"][j[k]] < res["t_sent"][i]
            if before == acked_before:
                out.append((res["conn"], int(i)))
    return out


def test_the_generator_inserts_in_one_sequence_and_reads_the_latest(bench):
    ops = bench.T.conn_ops(MIX, 50, SEED, 1)
    ins = ops.kinds == bench.T.INSERT
    assert ins.sum() == round(40 * 0.2)
    # connection 1's k-th insert is record 50 + 2 k + 1
    assert ops.records[ins].tolist() == [50 + 2 * k + 1
                                         for k in range(int(ins.sum()))]
    assert (ops.records[~ins] < 50 + 2 * np.cumsum(ins)[~ins]).all()
    assert ops.check[~ins].all() and not ops.check[ins].any()
    again = bench.T.conn_ops(MIX, 50, SEED, 1)
    assert (again.records == ops.records).all()


def test_a_sound_history_reads_zero(bench):
    world, ops_of, results, table = history(bench)
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"] == dict.fromkeys(bench.ref.LIMITS, 0), out["first"]
    cmp = out["compared"]
    assert cmp["reads_of_inserts"] > 0 and cmp["reads_empty"] > 0
    assert cmp["acks"] == 16 and cmp["readback"] == 16 + 10


def test_an_empty_read_after_the_acknowledged_insert_is_lost(bench):
    world, ops_of, results, table = history(bench)
    late = reads_of_inserts(bench, ops_of, results, world, True)
    assert len(late) >= 2
    for c, i in late[:2]:
        results[c]["got_n"][i], results[c]["got_d"][i] = 0, 0
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"]["reads_lost"] == 2
    assert sum(out["numbers"].values()) == 2


def test_an_empty_read_that_overlapped_its_insert_is_not_counted(bench):
    world, ops_of, results, table = history(bench)
    # the first read of ANOTHER connection's acknowledged insert
    c, i, ic, k = next(
        (c, i) + bench.T.insert_of(int(ops_of[c].records[i]), world.n, 2)
        for c, i in reads_of_inserts(bench, ops_of, results, world, True)
        if bench.T.insert_of(int(ops_of[c].records[i]), world.n, 2)[0] != c)
    j = np.flatnonzero(ops_of[ic].kinds == bench.T.INSERT)[k]
    # the read is sent before the insert's reply came: nothing is allowed
    results[ic]["t_done"][j] = results[c]["t_sent"][i] + 0.5
    results[c]["got_n"][i], results[c]["got_d"][i] = 0, 0
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"] == dict.fromkeys(bench.ref.LIMITS, 0), out["first"]


def test_a_record_read_before_its_insert_was_sent_is_wrong(bench):
    world, ops_of, results, table = history(bench)
    c, i = reads_of_inserts(bench, ops_of, results, world, True)[0]
    rec = int(ops_of[c].records[i])
    ic, k = bench.T.insert_of(rec, world.n, 2)
    j = np.flatnonzero(ops_of[ic].kinds == bench.T.INSERT)[k]
    results[ic]["t_sent"][j] = results[c]["t_done"][i] + 0.5
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"]["reads_wrong"] == 1
    assert sum(out["numbers"].values()) == 1


def test_a_partial_record_and_a_wrong_value_are_counted(bench):
    world, ops_of, results, table = history(bench)
    ops = ops_of[0]
    whole = [i for i in np.flatnonzero(ops.check)
             if results[0]["got_n"][i] == 10]
    i, j = whole[0], whole[1]
    rec = table.hgetall(int(ops.records[i]))
    rec.pop(b"field3")
    results[0]["got_n"][i], results[0]["got_d"][i] = bench.ref.reply_digest(
        encode(rec))
    rec = table.hgetall(int(ops.records[j]))
    rec[b"field0"] = b"#" + rec[b"field0"][1:]
    results[0]["got_n"][j], results[0]["got_d"][j] = bench.ref.reply_digest(
        encode(rec))
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"]["reads_partial"] == 1
    assert out["numbers"]["reads_wrong"] == 1
    assert sum(out["numbers"].values()) == 2


def test_a_wrong_insert_reply_is_counted(bench):
    world, ops_of, results, table = history(bench)
    i = next(iter(results[1]["acks"]))
    results[1]["acks"][i] = b":9\r\n"
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"]["acks_wrong"] == 1
    assert sum(out["numbers"].values()) == 1


def test_an_insert_missing_from_the_readback_is_counted(bench):
    world, ops_of, results, table = history(bench)
    gone = sorted(table.inserted)[-3:]
    out = check(bench, world, ops_of, results, table, drop=set(gone))
    assert out["numbers"]["readback_wrong"] == 3
    assert sum(out["numbers"].values()) == 3


def test_an_operation_never_answered_is_counted(bench):
    world, ops_of, results, table = history(bench)
    results[0]["done"] -= 2
    results[0]["failed"] = "no reply"
    out = check(bench, world, ops_of, results, table)
    assert out["numbers"]["never_answered"] == 2
    # an insert never answered is not held to the read-back
    assert out["numbers"]["readback_wrong"] == 0


def test_reply_digest_reads_both_layouts_and_nothing(bench):
    world = bench.datagen.build_world(CONFIG, SEED)
    rec = world.initial(7)
    flat = b"*20\r\n" + b"".join(
        b"$%d\r\n%s\r\n$%d\r\n%s\r\n" % (len(f), f, len(v), v)
        for f, v in reversed(list(rec.items())))
    want = (10, bench.ref.record_digest(rec))
    assert bench.ref.reply_digest(encode(rec)) == want
    assert bench.ref.reply_digest(flat) == want
    assert bench.ref.reply_digest(b"$-1\r\n") == (0, 0)
    assert bench.ref.reply_digest(b"*0\r\n") == (0, 0)
    assert bench.ref.reply_digest(b"-ERR no\r\n")[0] == -1
