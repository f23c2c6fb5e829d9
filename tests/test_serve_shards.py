"""Shard-per-core serving (server/serve_shards.py + parallel/serve_pool.py
+ repl_log.MergedReplLog).

The load-bearing claims, each pinned here:
  * a multi-shard node is byte-identical to the single-loop path — same
    deterministic multi-connection pipelined workload under the fixed-HLC
    hook produces the same reply byte stream per connection, the same
    canonical export, and the same repl-log entry sequence once the
    per-shard segments merge-sort by uuid;
  * shards=1 never constructs the plane: the node runs the exact PR 5
    single-loop objects (no MergedReplLog, no workers);
  * cross-shard commands are ordered barriers: they quiesce the chunk's
    outstanding routed sub-chunks first, so REPLLOG/INFO observe every
    preceding write and replies stay strictly in request order;
  * MEET/SYNC work on a sharded node in BOTH directions with an
    unmodified peer — full sync served from worker exports, steady-state
    frames routed to the owning worker, watermarks/beacons unchanged;
  * MergedReplLog's merge-sort is exact: sorted-union emission, floor
    gating (nothing at/above the smallest in-flight write uuid),
    pending_high keeping last_uuid over un-landed writes, and eviction
    horizon = max over segments.
"""

import asyncio
import random

import pytest

from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.resp.message import Arr, Bulk, Int
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.repl_log import MergedReplLog, ReplLog

from cluster_util import FAST, Client
from test_serve_coalesce import (mixed_workload, read_replies,
                                 stepping_clock)


def cmd(*parts) -> Arr:
    return Arr([p if isinstance(p, (Bulk, Int)) else
                Bulk(p if isinstance(p, bytes) else str(p).encode())
                for p in parts])


async def canon_of(node):
    if node.serve_plane is not None:
        return await node.serve_plane.canonical()
    return node.canonical()


def log_entries(node):
    """(uuid, name, size, args) sequence — merged logs sort their
    segments by uuid (per-segment prev_uuid chains differ from the
    single log's by design, so prev is not compared)."""
    log = node.repl_log
    if isinstance(log, MergedReplLog):
        ents = sorted((e for s in log.segments for e in s.entries()),
                      key=lambda e: e.uuid)
    else:
        ents = log.entries()
    return [(e.uuid, e.name, e.size,
             tuple((type(a).__name__, a.val) for a in e.args))
            for e in ents]


async def drive_node(tmp_path, serve_shards, work, serve_batch=64):
    """One node + len(work) client connections in deterministic
    lockstep (mirrors test_serve_coalesce.drive_node, with the shard
    plane in the loop when serve_shards > 1)."""
    node = Node(node_id=1, alias="n1", clock=stepping_clock())
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=str(tmp_path), serve_batch=serve_batch,
                           serve_shards=serve_shards, **FAST)
    # the cron's wall-clock hlc ticks would shift the legs' uuid streams
    app._cron_task.cancel()
    conns = [await Client().connect(app.advertised_addr) for _ in work]
    raw = [bytearray() for _ in work]
    try:
        for rnd in range(len(work[0])):
            for ci, c in enumerate(conns):
                chunk = work[ci][rnd]
                c.writer.write(b"".join(encode_msg(m) for m in chunk))
                await c.writer.drain()
                await read_replies(c, raw[ci], len(chunk))
        canonical = await canon_of(node)
        return [bytes(r) for r in raw], canonical, log_entries(node), node
    finally:
        for c in conns:
            await c.close()
        await app.close()


# ----------------------------------------------------------- differential

def test_multishards_differential(tmp_path):
    """The oracle: serve_shards=2 vs the single-loop path, same
    deterministic multi-connection workload — byte-identical reply
    streams, canonical export, and (merge-sorted) repl log."""
    # compact enough to clear the 5s marker-audit budget on the slow
    # builder box — worker spawn is most of it, and by this point in a
    # full tier-1 run the forkserver is warm from the earlier pool
    # suites; the wide slow-marked variant below is the thorough corpus
    work = mixed_workload(n_conns=2, rounds=8)

    async def main():
        g = await drive_node(tmp_path / "a", 2, work)
        w = await drive_node(tmp_path / "b", 1, work)
        return g, w

    (g_raw, g_canon, g_repl, g_node), (w_raw, w_canon, w_repl, w_node) = \
        asyncio.run(main())
    for ci, (g, w) in enumerate(zip(g_raw, w_raw)):
        assert g == w, f"conn {ci} reply stream diverged"
    assert g_canon == w_canon
    assert g_repl == w_repl
    # the sharded leg really ran through the plane
    x = g_node.stats.extra
    assert x.get("serve_shards") == 2
    assert x.get("serve_shard0_msgs", 0) + x.get("serve_shard1_msgs", 0) > 0
    assert g_node.serve_plane is not None
    assert w_node.serve_plane is None


@pytest.mark.slow
def test_multishards_differential_wide(tmp_path):
    """The bigger sweep: 3 shards, more rounds — the corpus where key
    collisions across shards and every barrier class actually occur."""
    work = mixed_workload(n_conns=4, rounds=16, seed=23)

    async def main():
        g = await drive_node(tmp_path / "a", 3, work)
        w = await drive_node(tmp_path / "b", 1, work)
        return g, w

    (g_raw, g_canon, g_repl, _), (w_raw, w_canon, w_repl, _) = \
        asyncio.run(main())
    assert g_raw == w_raw
    assert g_canon == w_canon
    assert g_repl == w_repl


def test_shards1_is_exact_single_loop_path(tmp_path):
    """serve_shards=1 (and the default) never constructs the plane: the
    node keeps the exact PR 5 objects."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_shards=1,
                               **FAST)
        try:
            assert app.serve_plane is None
            assert node.serve_plane is None
            assert type(node.repl_log) is ReplLog
        finally:
            await app.close()
    asyncio.run(main())


# ------------------------------------------------------ barrier ordering

def test_cross_shard_barrier_ordering(tmp_path):
    """One pipelined chunk spanning shards + admin barriers: replies in
    strict request order, and the barrier observes every preceding
    routed write (REPLLOG UUIDS sees all of them — quiesce-first)."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_shards=2,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            # keys spread over both shards (many distinct keys)
            writes = [cmd(b"set", b"k%02d" % i, b"v%d" % i)
                      for i in range(12)]
            chunk = writes + [cmd(b"repllog", b"uuids")] + \
                [cmd(b"get", b"k%02d" % i) for i in range(12)]
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            raw = bytearray()
            replies = await read_replies(c, raw, len(chunk))
            # 12 OKs, then the uuid list covering ALL 12 writes, then
            # the 12 values in order
            assert all(r.val == b"OK" for r in replies[:12])
            assert len(replies[12].items) == 12
            for i, r in enumerate(replies[13:]):
                assert r.val == b"v%d" % i
            x = node.stats.extra
            assert x.get("serve_xshard_barriers", 0) >= 1
            # both shards actually served traffic
            assert x.get("serve_shard0_keys", 0) > 0
            assert x.get("serve_shard1_keys", 0) > 0
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_node_id_barrier_reaches_workers(tmp_path):
    """NODE ID is a CTRL barrier: workers must stamp the NEW identity
    into subsequent writes (the plane resyncs ident after CTRL)."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_shards=2,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            await c.cmd(b"set", b"a", b"1")
            r = await c.cmd(b"node", b"id", b"42")
            assert r.val == b"OK"
            await c.cmd(b"set", b"b", b"2")
            canon = await canon_of(node)
            # register rows carry the writer node id
            (_enc, _ct, _mt, _dt, _exp, content) = canon[b"b"]
            assert content[2] == 42, content
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


# --------------------------------------------------- replication (2-node)

@pytest.mark.slow
def test_meet_sync_sharded_node_both_directions(tmp_path):
    """A sharded node and an UNMODIFIED single-loop peer: full sync
    served from worker exports, steady-state streams in both directions
    routed per key, watermarks advancing — the wire-compatibility
    claim."""
    async def main():
        na = Node(node_id=1, alias="a")
        nb = Node(node_id=2, alias="b")
        appa = await start_node(na, host="127.0.0.1", port=0,
                                work_dir=str(tmp_path / "a"),
                                serve_shards=2, **FAST)
        appb = await start_node(nb, host="127.0.0.1", port=0,
                                work_dir=str(tmp_path / "b"), **FAST)
        ca = await Client().connect(appa.advertised_addr)
        cb = await Client().connect(appb.advertised_addr)
        try:
            # pre-meet writes on the SHARDED node → B needs a full sync
            for i in range(30):
                await ca.cmd(b"set", b"ka%d" % i, b"va%d" % i)
                await ca.cmd(b"incr", b"cnt%d" % (i % 5), b"%d" % (i + 1))
                await ca.cmd(b"sadd", b"sa%d" % (i % 3), b"m%d" % i)
            await ca.cmd(b"meet", appb.advertised_addr)
            await wait_converged([na, nb])
            # steady-state INTO the sharded node (apply routing)
            for i in range(20):
                await cb.cmd(b"set", b"kb%d" % i, b"vb%d" % i)
                await cb.cmd(b"hset", b"hb%d" % (i % 4),
                             b"f%d" % i, b"v%d" % i)
            await cb.cmd(b"del", b"ka0")
            await wait_converged([na, nb])
            # steady-state OUT of the sharded node (merged peer stream)
            for i in range(20):
                await ca.cmd(b"sadd", b"out", b"m%d" % i)
            final = await wait_converged([na, nb])
            assert b"kb3" in final and b"out" in final
            assert b"ka0" not in final or final[b"ka0"][1] < final[b"ka0"][3]
            ma = na.replicas.get(appb.advertised_addr)
            mb = nb.replicas.get(appa.advertised_addr)
            assert ma.uuid_i_acked > 0          # B acked A's stream
            assert mb.uuid_he_sent > 0          # B's pull watermark moved
            assert nb.stats.cmds_replicated > 0
        finally:
            await ca.close()
            await cb.close()
            await appa.close()
            await appb.close()
    asyncio.run(main())


async def wait_converged(nodes, timeout=20.0):
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    while True:
        cc = [await canon_of(n) for n in nodes]
        if cc[0] and all(c == cc[0] for c in cc):
            return cc[0]
        if loop.time() - t0 > timeout:
            raise AssertionError(
                "no convergence: " +
                "; ".join(str(sorted(c.keys()))[:200] for c in cc))
        await asyncio.sleep(0.1)


@pytest.mark.slow
def test_boot_snapshot_restores_into_shards(tmp_path):
    """A snapshot dumped by a plain node boots a SHARDED node: state
    fans out to the workers, watermark fences set."""
    from constdb_tpu.persist.snapshot import NodeMeta, dump_keyspace

    async def main():
        plain = Node(node_id=9, alias="p")
        for i in range(50):
            plain.execute(cmd(b"set", b"k%d" % i, b"v%d" % i))
            plain.execute(cmd(b"sadd", b"s%d" % (i % 7), b"m%d" % i))
        path = str(tmp_path / "boot.snapshot")
        dump_keyspace(path, plain.ks,
                      NodeMeta(node_id=9, alias="p", addr="",
                               repl_last_uuid=plain.repl_log.last_uuid))
        node = Node()
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_shards=2,
                               snapshot_path=path, **FAST)
        try:
            assert node.node_id == 9  # identity pre-scanned from meta
            got = await canon_of(node)
            assert got == plain.canonical()
            assert node.repl_log.evicted_up_to == plain.repl_log.last_uuid
        finally:
            await app.close()
    asyncio.run(main())


# ------------------------------------------------- merged-log property

def _entry(log, uuid):
    log.push(uuid, b"set", [Bulk(b"k%d" % uuid), Bulk(b"v")])


def test_merged_repl_log_merge_sort_property():
    """Random entries scattered over segments: emission via next_after
    is exactly the sorted union, strictly increasing, and floor-gated."""
    rng = random.Random(7)
    for _trial in range(20):
        n_seg = rng.randrange(1, 5)
        merged = MergedReplLog(n_seg)
        uuids = sorted(rng.sample(range(1, 10_000), rng.randrange(0, 60)))
        owner = [rng.randrange(n_seg + 1) for _ in uuids]  # + parent seg
        for u, s in zip(uuids, owner):
            merged.segments[s].push(u, b"set", [Bulk(b"k"), Bulk(b"v")])
        # no floor: full sorted union
        got, cur = [], 0
        while (e := merged.next_after(cur)) is not None:
            got.append(e.uuid)
            cur = e.uuid
        assert got == uuids
        assert merged.last_uuid == (uuids[-1] if uuids else 0)
        assert len(merged) == len(uuids)
        # floor gate: nothing at/above the floor is emitted
        if uuids:
            floor = rng.choice(uuids)
            merged.floor = lambda f=floor: f
            got, cur = [], 0
            while (e := merged.next_after(cur)) is not None:
                got.append(e.uuid)
                cur = e.uuid
            assert got == [u for u in uuids if u < floor]
            merged.floor = lambda: None


def test_merged_repl_log_pending_high_and_eviction():
    merged = MergedReplLog(2, cap_bytes=1 << 20)
    _entry(merged.segments[0], 10)
    _entry(merged.segments[1], 20)
    assert merged.last_uuid == 20
    merged.pending_high = lambda: 50  # minted write still in flight
    assert merged.last_uuid == 50     # stream must NOT look drained
    merged.pending_high = lambda: 0
    # eviction horizon is the max across segments: a resume below ANY
    # segment's eviction point is gappy in the merged stream
    merged.segments[0].evicted_up_to = 15
    assert merged.evicted_up_to == 15
    assert not merged.can_resume_from(12)
    assert merged.can_resume_from(15)
    # fences (boot-restore / reset) fold into the maxes
    merged.evicted_up_to = 99
    merged.last_uuid = 99
    assert merged.evicted_up_to == 99 and merged.last_uuid == 99
    # at() finds entries across segments
    assert merged.at(20).uuid == 20
    assert merged.at(11) is None
    assert merged.uuids() == [10, 20]


def test_merged_repl_log_push_goes_to_local_segment():
    merged = MergedReplLog(2)
    merged.push(7, b"meet", [Bulk(b"1.2.3.4:5")])
    assert len(merged.local) == 1
    assert merged.next_after(0).uuid == 7
