"""Keys created under load — a run's landing (engine/hostbatch.py
resolve_keys) and a per-command create (store/keyspace.py create_key) —
held to YCSB workload D's plain reference (benchmark/reference_d.py), off
the chip.

Pinned here:
  * in one pipelined chunk, HSET of a new record's 10 fields then HGETALL
    of it answers the whole record; the read lands the run that created
    its key, which raises `serve_read_flushes_created` and
    `serve_keys_created` by 1 — a read behind a write to a key that is
    there raises `serve_read_flushes` alone;
  * the stage `key_create` counts both creation paths;
  * workload D's seeded traffic (two connections' pipelines a chunk, the
    rehearsal's shape) answers every operation as the reference does, on
    the CPU engine and on the device engine over JAX-CPU with its planes
    resident — where the inserts take a small table's element planes past
    their capacity under micro rounds.
"""

import os
import sys

import pytest

from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer

from test_serve_coalesce import cmd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 4500000077


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


def hset_all(key: bytes, fields: dict):
    return cmd(b"hset", key, *[x for fv in fields.items() for x in fv])


def counts(node) -> tuple:
    st = node.stats
    return (st.serve_read_flushes, st.serve_read_flushes_created,
            st.serve_keys_created, node.stages.snapshot()["key_create"][1])


def test_a_read_of_a_record_its_chunk_inserted_lands_the_run(bench):
    world = bench.datagen.build_world(
        {"world": "ycsb-hash", "recordcount": 10,
         "record": {"fieldcount": 10, "fieldlength": 100}}, SEED)
    node = Node(node_id=1)
    co = ServeCoalescer(node)
    old = world.initial(3)
    node.execute(hset_all(world.key(3), old))
    before = counts(node)
    new = world.initial(12)
    out, spans = bytearray(), []
    co.run_chunk([cmd(b"hset", world.key(3), b"field1", b"x" * 100),
                  hset_all(world.key(12), new),
                  cmd(b"hgetall", world.key(12))], out, spans=spans)
    replies = [bytes(out[a:b]) for a, b in zip([0] + spans, spans)]
    assert replies[:2] == [b":0\r\n", b":10\r\n"]
    assert bench.ref.reply_digest(replies[2]) == \
        (10, bench.ref.record_digest(new))
    after = counts(node)
    assert after[0] - before[0] == 1            # one read flush ...
    assert after[1] - before[1] == 1            # ... for a created key
    assert after[2] - before[2] == 1            # one key created
    assert after[3] - before[3] == 1            # by the run's landing
    # a write to a key that is there, then its read: a flush, nothing made
    co.run_chunk([cmd(b"hset", world.key(12), b"field2", b"y" * 100),
                  cmd(b"hset", world.key(3), b"field2", b"z" * 100),
                  cmd(b"hgetall", world.key(3))], bytearray())
    last = counts(node)
    assert last[0] - after[0] == 1
    assert last[1:] == after[1:]


def test_the_stage_counts_both_creation_paths():
    node = Node(node_id=1)
    assert counts(node)[3] == 0
    # the per-command path: a lone command creates its key itself
    node.execute(cmd(b"hset", b"lone", b"f", b"v"))
    assert counts(node)[3] == 1
    # a run's landing: forty new keys in one creation block (a run of
    # more rows than engine/hostbatch.HOST_ROW_MIN: the vectorized merge)
    ServeCoalescer(node).run_chunk(
        [cmd(b"hset", b"k%d" % i, b"f", b"v") for i in range(40)],
        bytearray())
    assert counts(node)[3] == 2
    assert node.ks.keys.n == 41 and node.stats.serve_keys_created == 40


def _device_node():
    pytest.importorskip("jax")
    from constdb_tpu.engine.tpu import TpuMergeEngine
    eng = TpuMergeEngine(resident=True, steady=True, warmup=0)
    return Node(node_id=1, engine=eng), eng


@pytest.mark.parametrize("engine", ["cpu", "device"])
def test_workload_d_answers_as_the_reference_does(bench, engine):
    r0, conns, depth = 300, 2, 16
    mix = {"operations": {"read": 0.75, "insert": 0.25},
           "keys": {"kind": "latest", "constant": 0.99}, "lag": 1,
           "connections": conns, "max_ops_per_conn": 30 * depth,
           "check_share": 1.0}
    world = bench.datagen.build_world(
        {"world": "ycsb-hash", "recordcount": r0,
         "record": {"fieldcount": 10, "fieldlength": 100}}, SEED)
    node, eng = _device_node() if engine == "device" else \
        (Node(node_id=1), None)
    node.merge_batches(list(world.batches()))      # the boot's table
    if eng is not None:
        assert eng._res["el"]["cap"] == 4096       # 3,000 rows resident
    table = bench.ref.RefTableD(world)
    ops = [bench.T.conn_ops(mix, r0, SEED, c) for c in range(conns)]
    co = ServeCoalescer(node)
    inserts = empties = 0
    for lo in range(0, mix["max_ops_per_conn"], depth):
        chunk, want = [], []
        for c in range(conns):
            for i in range(lo, lo + depth):
                rec = int(ops[c].records[i])
                if ops[c].kinds[i] == bench.T.INSERT:
                    fields = world.initial(rec)
                    chunk.append(hset_all(world.key(rec), fields))
                    want.append(b":%d\r\n" % table.hset(rec, list(
                        fields.items())))
                    inserts += 1
                else:
                    chunk.append(cmd(b"hgetall", world.key(rec)))
                    got = table.hgetall(rec)
                    empties += not got
                    want.append((len(got), bench.ref.record_digest(got)
                                 if got else 0))
        out, spans = bytearray(), []
        co.run_chunk(chunk, out, spans=spans)
        replies = [bytes(out[a:b]) for a, b in zip([0] + spans, spans)]
        for raw, w in zip(replies, want):
            assert (raw if isinstance(w, bytes)
                    else bench.ref.reply_digest(raw)) == w, (lo, raw[:60])
    assert node.ks.keys.n == r0 + inserts and inserts > 200 and empties
    assert node.stats.serve_keys_created == inserts
    assert node.stats.serve_read_flushes_created > 0
    if eng is not None:
        # the inserts' element rows passed the boot's planes: grown in
        # place under micro rounds, to the floor
        assert eng.mirror_grows["el"] >= 1 and eng.dev_rounds_resident > 0
        assert eng._res["el"]["cap"] == eng.GROW_FLOOR
