"""The frozen heap: once a node's table has landed — the boot restore, a
full sync — the node collects once and freezes its process's heap
(utils/stagetime.py StageClock.freeze_heap), so a full collection never
walks the table's blob planes again; `Node.replace_keyspace` thaws the
heap before it drops a keyspace, since a frozen cycle is never freed,
and a freeze is skipped while the dropped one lives (docs/INVARIANTS.md).
What the node keeps for seconds after the freeze holds atoms only, so a
collection walks none of it either: the replication ring's records, the
reply cache's entries and index, a mirror's row journal.

Pinned here, off the chip (CPU engine):
  * a boot from a seeded `ycsb-1node` snapshot at its rehearsal size
    freezes once (INFO `gc_freezes` 1, `gc_frozen_objects` above the
    heap's own), no blob plane of the keyspace, nor its per-key element
    row lists, is left in a generation the collector walks, and
    `HGETALL`s answer the snapshot's values;
  * a boot with no snapshot freezes little beyond the heap it found;
  * a full sync freezes the table it landed;
  * a state-clearing resync and a quarantined snapshot thaw before they
    drop a keyspace: the old one dies at the next collection, and the
    node is frozen again afterwards;
  * a compaction rewrites the element planes and row lists in place, so
    they stay frozen, and freezes nothing, however connections churn;
  * a freeze is skipped while the dropped keyspace is still alive;
  * the ring, the reply cache and the row journal hold no object a
    collection tracks.
Every test leaves the heap thawed (tests/conftest.py).
"""

import asyncio
import gc
import logging
import os
import sys
import weakref

import pytest

from constdb_tpu.resp.message import Bulk, Int
from constdb_tpu.server import info as info_mod
from constdb_tpu.server.io import ServerApp, start_node
from constdb_tpu.server.node import Node

from cluster_util import FAST, Client, close_cluster, converge, make_cluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 4700000047
PLANES = ("key_bytes", "reg_val", "el_member", "el_val")


@pytest.fixture(scope="module")
def ycsb(tmp_path_factory):
    """(world, snapshot path): `ycsb-1node` at its rehearsal size."""
    import json
    sys.path.insert(0, BENCH)
    try:
        import datagen
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "ycsb-1node.json")) as f:
        config = json.load(f)
    config.update(config["rehearse"])
    world = datagen.build_world(config, SEED)
    path = str(tmp_path_factory.mktemp("ycsb") / "boot.snapshot")
    datagen.write_snapshot(world, path, node_id=1, alias="C",
                           addr="127.0.0.1:1",
                           compress_level=config["snapshot_compress_level"])
    return world, path


def info_of(node) -> dict:
    out: list = []
    info_mod._section_stats(node, out)
    return dict(out)


def walked(*objs) -> list:
    """Those of `objs` that a collection of any generation walks."""
    ids = {id(o) for o in gc.get_objects()}
    return [o for o in objs if id(o) in ids]


def baseline() -> int:
    """Freeze what the test process holds already; -> its count."""
    gc.collect()
    gc.freeze()
    return gc.get_freeze_count()


def test_a_restored_table_is_frozen_before_the_node_serves(tmp_path, ycsb):
    world, snap = ycsb

    async def main():
        node = Node()
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), snapshot_path=snap)
        try:
            got = info_of(node)
            assert got["gc_freezes"] == 1
            assert got["gc_frozen_objects"] > 0
            ks = node.ks
            assert ks.n_keys() == world.n
            assert len(ks.el_member) == world.n * world.fieldcount
            # no blob plane is in generation 2, or in any other, and
            # neither are the per-key element row lists a read would build
            planes = [getattr(ks, p) for p in PLANES]
            assert not walked(*planes)
            assert len(ks.el_rows_by_kid) == world.n
            assert not walked(ks.el_rows_by_kid,
                              *list(ks.el_rows_by_kid.values())[:100])
            assert not {id(o) for o in gc.get_objects(generation=2)} \
                & {id(p) for p in planes}
            c = await Client().connect(app.advertised_addr)
            try:
                for i in (0, 1, 7, world.n // 2, world.n - 1):
                    reply = await c.cmd("hgetall", world.key(i))
                    assert {f.val: v.val for f, v in
                            (pair.items for pair in reply.items)} == \
                        world.initial(i)
            finally:
                await c.close()
        finally:
            await app.close()
    asyncio.run(main())


def test_an_empty_boot_freezes_little_beyond_the_heap_it_found(tmp_path):
    async def main():
        base = baseline()
        node = Node()
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path))
        try:
            got = info_of(node)
            assert got["gc_freezes"] == 1
            # the node, its server and its keyspace: no table
            assert 0 <= got["gc_frozen_objects"] - base < 5_000
            assert node.ks.n_keys() == 0
        finally:
            await app.close()
    asyncio.run(main())


def test_a_full_sync_freezes_the_table_it_landed(tmp_path):
    async def main():
        # a log too short for B's resume point: B takes a full sync
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=2_000)
        a, b = apps
        try:
            c = await Client().connect(a.advertised_addr)
            for i in range(300):
                await c.cmd("hset", f"h{i}", "f", f"v{i}")
            freezes = b.node.stages.freezes
            await c.cmd("meet", b.advertised_addr)
            await converge(apps)
            await c.close()
            assert b.node.ks.n_keys() == 300
            assert b.node.stages.freezes > freezes
            ks = b.node.ks
            assert not walked(*[getattr(ks, p) for p in PLANES])
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_a_state_clearing_resync_thaws_the_heap_before_the_wipe(tmp_path):
    """A frozen keyspace is dropped by a reset resync: the heap is thawed
    first, so the old table dies at the next collection, and the
    snapshot that follows is frozen in its place."""
    async def main():
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=600)
        try:
            a, b = apps
            node = b.node
            c = await Client().connect(a.advertised_addr)
            await c.cmd("meet", b.advertised_addr)
            await converge(apps)
            await c.cmd("set", "kept", "v")
            await converge(apps)
            b_port = b.port
            await b.close()
            meta_b = a.node.replicas.get(b.advertised_addr)
            del b                          # its coalescer holds the table
            for i in range(60):
                await c.cmd("set", f"fill{i}", "x" * 32)
            assert not a.node.repl_log.can_resume_from(meta_b.uuid_i_sent)
            meta_b.needs_full = True       # the pusher must wipe B
            old = weakref.ref(node.ks)
            apps[1] = ServerApp(node, host="127.0.0.1", port=b_port,
                                work_dir=str(tmp_path), **FAST)
            await apps[1].start()          # boots on the old table: frozen
            freezes = node.stages.freezes
            assert not walked(old().key_bytes)
            await converge(apps, timeout=20.0)
            assert node.reset_epoch == 1
            gc.collect()
            assert old() is None
            assert node.stages.freezes > freezes
            assert not walked(node.ks.key_bytes)
            assert await c.cmd("get", "fill59") == Bulk(b"x" * 32)
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_a_quarantined_snapshot_thaws_the_heap_before_the_wipe(
        tmp_path, ycsb, caplog):
    _world, snap = ycsb
    # a captured record would keep the load's error, and through its
    # traceback the dropped keyspace, alive past the boot's freeze
    caplog.set_level(logging.CRITICAL, logger="constdb_tpu.server.io")
    with open(snap, "rb") as f:
        data = f.read()
    bad = str(tmp_path / "bad.snapshot")
    with open(bad, "wb") as f:
        f.write(data[: len(data) // 2])    # fails mid-merge

    async def main():
        node = Node()
        node.stages.freeze_heap()          # the keyspace it boots with
        old = weakref.ref(node.ks)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), snapshot_path=bad)
        try:
            assert "boot_snapshot_quarantined" in node.stats.extra
            gc.collect()
            assert old() is None
            assert node.ks.n_keys() == 0
            assert info_of(node)["gc_freezes"] == 2
            assert not walked(node.ks.key_bytes)
        finally:
            await app.close()
    asyncio.run(main())


def test_a_compaction_freezes_the_element_planes_it_built():
    """A compaction rewrites the element planes and the per-key row lists
    in place: the frozen objects stay frozen, and nothing is frozen anew
    (the objects it drops leave the count)."""
    node = Node(node_id=1)
    for i in range(50):
        node.execute([Bulk(b"hset"), Bulk(b"h%d" % i), Bulk(b"f"),
                      Bulk(b"v%d" % i)])
    ks = node.ks
    node.freeze_heap()
    before = (ks.el_member, ks.el_val, ks.el_rows_by_kid)
    count = gc.get_freeze_count()
    ks._compact_elements()
    assert (ks.el_member, ks.el_val, ks.el_rows_by_kid) == before
    assert all(a is b for a, b in zip(
        (ks.el_member, ks.el_val, ks.el_rows_by_kid), before))
    # frozen objects the compaction let go of leave the count
    assert node.stages.freezes == 1 and gc.get_freeze_count() <= count
    assert not walked(ks.el_member, ks.el_val, ks.el_rows_by_kid,
                      *ks.el_rows_by_kid.values())
    assert node.execute([Bulk(b"hget"), Bulk(b"h7"), Bulk(b"f")]) == \
        Bulk(b"v7")


def test_compactions_under_connection_churn_freeze_nothing(tmp_path):
    """Connections come and go between compactions of a served node: no
    compaction freezes (so no dead connection is kept for good), and the
    frozen count never rises above where the boot left it."""
    async def main():
        node = Node()
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path))
        try:
            c = await Client().connect(app.advertised_addr)
            for i in range(200):
                await c.cmd("hset", f"h{i}", "f", f"v{i}")
            await c.close()
            gc.collect()
            start = info_of(node)
            for round_ in range(5):
                clients = [await Client().connect(app.advertised_addr)
                           for _ in range(4)]
                for cl in clients:
                    await cl.cmd("hset", f"h{round_}", "g", "w")
                    await cl.close()
                node.ks._compact_elements()
                got = info_of(node)
                assert got["gc_freezes"] == start["gc_freezes"] == 1
                assert got["gc_frozen_objects"] <= \
                    start["gc_frozen_objects"]
            c = await Client().connect(app.advertised_addr)
            assert await c.cmd("hget", "h3", "g") == Bulk(b"w")
            await c.close()
        finally:
            await app.close()
    asyncio.run(main())


def test_a_freeze_is_skipped_while_the_dropped_keyspace_lives():
    """Something still holds the table a resync dropped: a freeze would
    keep it for good, so the node skips it (INFO `gc_freeze_skips`) and
    freezes again once the old table is gone."""
    node = Node(node_id=1)
    node.execute([Bulk(b"set"), Bulk(b"k"), Bulk(b"v")])
    held = node.ks
    node.replace_keyspace()
    node.freeze_heap()
    got = info_of(node)
    assert (got["gc_freezes"], got["gc_freeze_skips"]) == (0, 1)
    assert walked(held.key_bytes)
    del held
    node.freeze_heap()
    got = info_of(node)
    assert (got["gc_freezes"], got["gc_freeze_skips"]) == (1, 1)
    assert got["gc_freeze_us"] > 0 and "gc_full_us" in got


def test_the_replication_ring_holds_nothing_a_collection_walks():
    """The ring keeps each write as a tuple of atoms, which the collector
    stops tracking at its first collection; readers get the arguments
    back as the messages that were pushed."""
    node = Node(node_id=1)
    for i in range(100):
        node.execute([Bulk(b"hset"), Bulk(b"h%d" % i), Bulk(b"f"),
                      Bulk(b"v%d" % i)])
    log = node.repl_log
    log.push(log.last_uuid + 1, b"cntset", [Bulk(b"c"), Int(5)])
    gc.collect()
    records = list(log._entries)
    assert len(records) == 101
    assert not any(gc.is_tracked(r) for r in records[:100])
    entries = log.entries()
    assert [(a.val) for a in entries[7].args] == [b"h7", b"f", b"v7"]
    assert all(type(a) is Bulk for a in entries[7].args)
    assert entries[-1].args == [Bulk(b"c"), Int(5)]
    assert log.at(entries[7].uuid).vals == (b"h7", b"f", b"v7")
    assert [e.uuid for e in log.run_after(0, 1_000)] == \
        [e.uuid for e in entries]


def test_a_warm_reply_cache_holds_nothing_a_collection_walks():
    """Entries and the by-key index are atoms only; a key's index turns
    from a tuple into a set past _INDEX_TUPLE_MAX entries and back, and
    stays equal to the entries it indexes through puts and drops."""
    from constdb_tpu.server.read_cache import (_INDEX_TUPLE_MAX,
                                               ReadReplyCache)
    node = Node(node_id=1)
    for f in range(40):
        node.execute([Bulk(b"hset"), Bulk(b"h"), Bulk(b"f%d" % f),
                      Bulk(b"v")])
        node.execute([Bulk(b"hset"), Bulk(b"g%d" % f), Bulk(b"f"),
                      Bulk(b"v")])
    ks = node.ks
    cache = ReadReplyCache(1 << 20)

    def index_matches():
        want: dict = {}
        for k in cache._map:
            want.setdefault(k[1], set()).add(k)
        assert {key: set(idx) for key, idx in cache._by_key.items()} == \
            want

    kid_h = ks.key_index.lookup(b"h")
    for f in range(40):
        cache.put(b"hget", b"h", b"f%d" % f, kid_h, ks, b"$1\r\nv\r\n")
        if f < _INDEX_TUPLE_MAX:
            assert type(cache._by_key[b"h"]) is tuple
    assert type(cache._by_key[b"h"]) is set
    for f in range(40):
        key = b"g%d" % f
        cache.put(b"hgetall", key, b"", ks.key_index.lookup(key), ks,
                  b"*2\r\n$1\r\nf\r\n$1\r\nv\r\n")
    index_matches()
    gc.collect()
    assert not any(gc.is_tracked(e) for e in cache._map.values())
    assert not any(gc.is_tracked(i) for i in cache._by_key.values()
                   if type(i) is tuple)
    cache.invalidate_key_members(b"h", [b"f%d" % f for f in range(30)])
    assert len(cache._by_key[b"h"]) == 10
    index_matches()
    cache.invalidate_key_members(b"g3", [b"f"])    # a whole-key kind
    cache._drop((b"hgetall", b"g4", b""))
    cache.invalidate_key(b"g5")
    assert not {b"g3", b"g4", b"g5"} & set(cache._by_key)
    index_matches()
    assert cache.get(b"hget", b"h", b"f35", ks) == b"$1\r\nv\r\n"
    assert cache.get(b"hget", b"h", b"f3", ks) is None


def test_a_row_journal_is_never_walked():
    import numpy as np
    from constdb_tpu.store.keyspace import RowJournal
    j = RowJournal()
    j.reset()
    j.add(9)
    j.add_rows(np.array([4, 9, 2], dtype=np.int32))
    # a collection that walks the array visits none of its rows
    assert len(gc.get_referents(j.rows)) <= 1
    assert j.take().tolist() == [2, 4, 9] and list(j.rows) == [2, 4, 9]
