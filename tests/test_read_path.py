"""Coalesced read serving + versioned reply cache (round 18).

The load-bearing claims, each pinned here (docs/INVARIANTS.md "Read
coalescing laws"):
  * a coalescing node with the read planner AND reply cache active is
    byte-identical to a CONSTDB_SERVE_BATCH=1 node under a READ-HEAVY
    pipelined workload (hot keys, every read family, scnt/sismember,
    expiry-armed keys, type conflicts, DELs) — reply streams, canonical
    export, repl_log, and command accounting all match, cache on or off;
  * replication-intake invalidation: a node serving cached hot-key reads
    while its peer streams writes to the SAME keys never serves a stale
    reply — every reply matches the uncached reference byte-for-byte (a
    stale serve is a failure, not a race);
  * sharded routing: serve_shards=2 with the read planner in the workers
    stays byte-identical to the single-loop path, with per-shard read /
    cache gauges riding worker acks;
  * the cache itself: LRU byte cap, envelope-stamp verification,
    per-key invalidation, governor accounting + hard-watermark drop;
  * INFO surfaces serve_reads_coalesced / serve_read_flushes /
    read_cache_hits/misses/bytes/invalidations.
"""

import asyncio
import random

import pytest

from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, Nil, Simple
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.read_cache import ReadReplyCache

from cluster_util import FAST, Client
from test_serve_coalesce import (cmd, read_replies, stepping_clock, u)


def read_heavy_workload(n_conns: int, rounds: int, seed: int = 31,
                        read_pct: float = 0.8) -> list:
    """Per-connection chunk lists: a hot-key read-dominated mix covering
    every planned read kind plus the demotion classes (expiry-armed
    keys, type conflicts, wrong arity) and enough writes/DELs that
    invalidation is exercised for real."""
    rng = random.Random(seed)
    work = [[] for _ in range(n_conns)]
    for rnd in range(rounds):
        for ci in range(n_conns):
            chunk = []
            for _ in range(rng.choice((1, 4, 8, 16, 24))):
                r = rng.random()
                # hot set: 6 keys absorb most reads, so the cache hits
                k = b"k%02d" % (rng.randrange(6) if rng.random() < 0.7
                                else rng.randrange(24))
                if r < read_pct:
                    q = rng.random()
                    if q < 0.30:
                        chunk.append(cmd(b"get", b"r" + k))
                    elif q < 0.45:
                        chunk.append(cmd(b"smembers", b"s" + k))
                    elif q < 0.55:
                        chunk.append(cmd(b"scnt", b"s" + k))
                    elif q < 0.65:
                        chunk.append(cmd(b"sismember", b"s" + k,
                                         b"m%d" % rng.randrange(6)))
                    elif q < 0.75:
                        chunk.append(cmd(b"hget", b"h" + k,
                                         b"f%d" % rng.randrange(4)))
                    elif q < 0.83:
                        chunk.append(cmd(b"hgetall", b"h" + k))
                    elif q < 0.89:
                        chunk.append(cmd(b"lrange", b"l" + k, 0, -1))
                    elif q < 0.91:
                        chunk.append(cmd(b"llen", b"l" + k))
                    elif q < 0.93:
                        chunk.append(cmd(b"hlen", b"h" + k))
                    elif q < 0.95:
                        chunk.append(cmd(b"get", b"c" + k))  # counter get
                    elif q < 0.97:
                        # type conflict: element read of a register
                        chunk.append(cmd(b"smembers", b"r" + k))
                    elif q < 0.99:
                        # expiry-armed key (set below): demotes
                        chunk.append(cmd(b"get", b"x" + k))
                    else:
                        # wrong arity: unplannable, exact op error
                        chunk.append(cmd(b"get"))
                else:
                    q = rng.random()
                    if q < 0.30:
                        chunk.append(cmd(b"set", b"r" + k,
                                         b"v%d" % rng.getrandbits(24)))
                    elif q < 0.50:
                        chunk.append(cmd(b"sadd", b"s" + k,
                                         b"m%d" % rng.randrange(6)))
                    elif q < 0.60:
                        chunk.append(cmd(b"srem", b"s" + k,
                                         b"m%d" % rng.randrange(6)))
                    elif q < 0.75:
                        chunk.append(cmd(b"hset", b"h" + k,
                                         b"f%d" % rng.randrange(4),
                                         b"v%d" % rng.getrandbits(16)))
                    elif q < 0.82:
                        chunk.append(cmd(b"incr", b"c" + k,
                                         rng.randrange(1, 9)))
                    elif q < 0.88:
                        chunk.append(cmd(b"lpush", b"l" + k,
                                         b"x%d" % rng.getrandbits(16)))
                    elif q < 0.93:
                        chunk.append(cmd(b"del", rng.choice(
                            (b"r", b"s", b"h", b"l")) + k))
                    elif q < 0.97:
                        chunk.append(cmd(b"set", b"x" + k, b"exp"))
                    else:
                        # arm an expiry far in the future: reads of
                        # x-keys demote forever after
                        chunk.append(cmd(b"expireat", b"x" + k,
                                         u(1 << 21)))
            work[ci].append(chunk)
    return work


async def drive_node(tmp_path, serve_batch, work, serve_shards=1):
    """Lockstep driver (the test_serve_coalesce pattern), returning the
    node for gauge inspection."""
    node = Node(node_id=1, alias="n1", clock=stepping_clock())
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=str(tmp_path), serve_batch=serve_batch,
                           serve_shards=serve_shards, **FAST)
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
        if node.serve_plane is not None:
            canonical = await node.serve_plane.canonical()
            repl = None  # merged log compared via canonical + replies
        else:
            canonical = node.canonical()
            repl = [(e.uuid, e.prev_uuid, e.name, e.size,
                     tuple((type(a).__name__, a.val) for a in e.args))
                    for e in node.repl_log.entries()]
        return [bytes(r) for r in raw], canonical, repl, node
    finally:
        for c in conns:
            await c.close()
        await app.close()


# ------------------------------------------------------------ differential

def test_read_heavy_differential(tmp_path):
    """The oracle: read planner + reply cache vs the exact per-command
    path — byte-identical reply streams, canonical export, repl_log,
    and command accounting under a hot-key read-heavy workload."""
    work = read_heavy_workload(n_conns=3, rounds=12)

    async def main():
        got = await drive_node(tmp_path / "a", 64, work)
        want = await drive_node(tmp_path / "b", 1, work)
        return got, want

    (g_raw, g_canon, g_repl, g_node), (w_raw, w_canon, w_repl, w_node) = \
        asyncio.run(main())
    for ci, (g, w) in enumerate(zip(g_raw, w_raw)):
        assert g == w, f"conn {ci} reply stream diverged"
    assert g_canon == w_canon
    assert g_repl == w_repl
    g_st, w_st = g_node.stats, w_node.stats
    assert g_st.cmds_processed == w_st.cmds_processed
    # the read plane engaged for real: planned reads, cache traffic,
    # read-your-writes flushes, and demotions all occurred
    assert g_st.serve_reads_coalesced > 0
    assert g_st.serve_read_flushes > 0
    rc = g_node.read_cache
    assert rc.hits > 0 and rc.misses > 0
    assert rc.invalidations > 0
    assert g_st.serve_barriers > 0  # demoted reads + DELs still barrier
    # the pinned leg never planned a read
    assert w_st.serve_reads_coalesced == 0
    assert w_node.read_cache.hits == 0


def test_read_differential_cache_off(tmp_path, monkeypatch):
    """CONSTDB_READ_CACHE_MB=0: the read planner still batches, replies
    stay byte-identical, and the cache machinery never engages."""
    monkeypatch.setenv("CONSTDB_READ_CACHE_MB", "0")
    work = read_heavy_workload(n_conns=2, rounds=8, seed=77)

    async def main():
        got = await drive_node(tmp_path / "a", 64, work)
        want = await drive_node(tmp_path / "b", 1, work)
        return got, want

    (g_raw, _gc, _gr, g_node), (w_raw, _wc, _wr, _w) = asyncio.run(main())
    for g, w in zip(g_raw, w_raw):
        assert g == w
    assert g_node.stats.serve_reads_coalesced > 0
    rc = g_node.read_cache
    assert rc.hits == 0 and rc.misses == 0 and len(rc) == 0


def test_sharded_read_differential(tmp_path):
    """serve_shards=2: reads route to the shard workers' planners and
    stay byte-identical to the single-loop path; per-shard read/cache
    gauges ride the worker acks."""
    work = read_heavy_workload(n_conns=2, rounds=8, seed=5)

    async def main():
        g = await drive_node(tmp_path / "a", 64, work, serve_shards=2)
        w = await drive_node(tmp_path / "b", 64, work, serve_shards=1)
        return g, w

    (g_raw, g_canon, _gr, g_node), (w_raw, w_canon, _wr, _w) = \
        asyncio.run(main())
    for ci, (g, w) in enumerate(zip(g_raw, w_raw)):
        assert g == w, f"conn {ci} reply stream diverged"
    assert g_canon == w_canon
    st = g_node.stats
    assert st.serve_reads_coalesced > 0
    assert g_node.read_cache.hits > 0  # folded from worker acks
    x = st.extra
    assert x.get("serve_shard0_reads", 0) + \
        x.get("serve_shard1_reads", 0) == st.serve_reads_coalesced
    assert x.get("serve_shard0_cache_bytes", 0) + \
        x.get("serve_shard1_cache_bytes", 0) > 0


# ------------------------------------- replication-intake invalidation

def test_reads_racing_replicated_writes(tmp_path):
    """The satellite differential: node A serves cached hot-key reads
    while peer B streams writes to the SAME keys.  After each round
    lands, A's (cached) replies must match the just-written values
    byte-for-byte — a stale serve is a FAILURE, not a race."""
    async def main():
        a = Node(node_id=1, alias="a")
        b = Node(node_id=2, alias="b")
        app_a = await start_node(a, host="127.0.0.1", port=0,
                                 work_dir=str(tmp_path / "a"), **FAST)
        app_b = await start_node(b, host="127.0.0.1", port=0,
                                 work_dir=str(tmp_path / "b"), **FAST)
        ca = await Client().connect(app_a.advertised_addr)
        cb = await Client().connect(app_b.advertised_addr)
        try:
            assert await ca.cmd("meet", app_b.advertised_addr) == \
                Simple(b"OK")
            stale = 0
            for rnd in range(12):
                # B writes the hot keys (replicated stream into A)
                await cb.cmd("set", "hot", "v%d" % rnd)
                await cb.cmd("sadd", "hs", "m%d" % rnd)
                await cb.cmd("incr", "hc", 3)
                # wait until A landed B's writes (watermark-backed:
                # canonical convergence on the written keys)
                for _ in range(200):
                    if (await _pipeline(ca, [cmd(b"get", b"hot"),
                                             cmd(b"get", b"hot")])
                            )[0] == Bulk(b"v%d" % rnd):
                        break
                    await asyncio.sleep(0.02)
                # pipelined read chunk on A — the planned+cached path
                r = await _pipeline(ca, [
                    cmd(b"get", b"hot"), cmd(b"scnt", b"hs"),
                    cmd(b"sismember", b"hs", b"m%d" % rnd),
                    cmd(b"get", b"hc"), cmd(b"get", b"hot")])
                want = [Bulk(b"v%d" % rnd), Int(rnd + 1), Int(1),
                        Int(3 * (rnd + 1)), Bulk(b"v%d" % rnd)]
                if r != want:
                    stale += 1
                    raise AssertionError(
                        f"stale cached reply in round {rnd}: {r} != "
                        f"{want}")
            assert stale == 0
            # the cache actually served hits across the rounds (the
            # double-read per chunk guarantees at least one per round)
            assert a.read_cache.hits > 0
            assert a.read_cache.invalidations > 0
        finally:
            await ca.close()
            await cb.close()
            await app_a.close()
            await app_b.close()
    asyncio.run(main())


async def _pipeline(client, msgs):
    client.writer.write(b"".join(encode_msg(m) for m in msgs))
    await client.writer.drain()
    return await read_replies(client, bytearray(), len(msgs))


# ---------------------------------------------------------- command twins

def test_scnt_sismember_semantics(tmp_path):
    """The new read commands: absent keys, liveness, type errors, DEL
    and add-wins behavior — per-command (lone) path."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            assert await c.cmd("scnt", "s") == Int(0)
            assert await c.cmd("sismember", "s", "a") == Int(0)
            await c.cmd("sadd", "s", "a", "b", "c")
            assert await c.cmd("scnt", "s") == Int(3)
            assert await c.cmd("sismember", "s", "a") == Int(1)
            assert await c.cmd("sismember", "s", "z") == Int(0)
            await c.cmd("srem", "s", "b")
            assert await c.cmd("scnt", "s") == Int(2)
            assert await c.cmd("sismember", "s", "b") == Int(0)
            await c.cmd("del", "s")
            assert await c.cmd("scnt", "s") == Int(0)
            assert await c.cmd("sismember", "s", "a") == Int(0)
            # add-wins: re-adding after the delete resurrects visibility
            await c.cmd("sadd", "s", "z")
            assert await c.cmd("scnt", "s") == Int(1)
            # type errors mirror smembers'
            await c.cmd("set", "r", "v")
            r = await c.cmd("scnt", "r")
            assert isinstance(r, Err)
            r = await c.cmd("sismember", "r", "a")
            assert isinstance(r, Err)
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


# ------------------------------------------------------------- cache unit

class _FakeCols:
    def __init__(self):
        import numpy as np
        self.n = 8
        self.ct = np.zeros(8, dtype="i8")
        self.mt = np.zeros(8, dtype="i8")
        self.dt = np.zeros(8, dtype="i8")
        self.expire = np.zeros(8, dtype="i8")


class _FakeIdx:
    def lookup(self, key):
        return -1

    def lookup_batch(self, keys):
        import numpy as np
        return np.full(len(keys), -1, dtype="i8")


class _FakeKs:
    def __init__(self):
        self.keys = _FakeCols()
        self.key_index = _FakeIdx()


def test_cache_lru_cap_and_stamp():
    ks = _FakeKs()
    rc = ReadReplyCache(4096)
    rc.put(b"get", b"k1", b"", 1, ks, b"x" * 100)
    rc.put(b"get", b"k2", b"", 2, ks, b"y" * 100)
    assert rc.get(b"get", b"k1", b"", ks) == b"x" * 100
    assert rc.hits == 1
    # envelope stamp mismatch drops the entry
    ks.keys.mt[2] = 5
    assert rc.get(b"get", b"k2", b"", ks) is None
    assert rc.misses == 1 and len(rc) == 1
    # expiry-armed keys are never cached
    ks.keys.expire[3] = 10
    rc.put(b"get", b"k3", b"", 3, ks, b"z")
    assert rc.get(b"get", b"k3", b"", ks) is None
    # oversized entries (over cap/8) are skipped
    rc.put(b"get", b"k4", b"", 4, ks, b"w" * 1024)
    assert len(rc) == 1
    # LRU eviction under the byte cap
    for i in range(30):
        rc.put(b"get", b"e%d" % i, b"", 5, ks, b"v" * 64)
    assert rc.bytes <= 4096
    assert rc.get(b"get", b"e0", b"", ks) is None  # evicted first
    assert rc.get(b"get", b"e29", b"", ks) is not None  # newest kept


def test_cache_invalidation_paths():
    ks = _FakeKs()
    rc = ReadReplyCache(1 << 20)
    rc.put(b"get", b"k", b"", 1, ks, b"a")
    rc.put(b"smembers", b"k", b"", 1, ks, b"b")
    rc.put(b"hget", b"k", b"f1", 1, ks, b"c")
    rc.put(b"get", b"other", b"", 2, ks, b"d")
    rc.invalidate_key(b"k")
    assert rc.invalidations == 3
    assert rc.get(b"get", b"k", b"", ks) is None
    assert rc.get(b"get", b"other", b"", ks) == b"d"
    # bulk invalidation with more keys than entries clears outright
    rc.put(b"get", b"k", b"", 1, ks, b"a")
    rc.invalidate_keys([b"a", b"b", b"c", b"k", b"other"])
    assert len(rc) == 0 and rc.bytes == 0
    # disabled cache never stores
    off = ReadReplyCache(0)
    off.put(b"get", b"k", b"", 1, ks, b"a")
    assert len(off) == 0


def test_member_scoped_invalidation():
    """Element writes drop only the touched members' sismember/hget
    entries; whole-key kinds always drop; key delete drops everything
    (the member-scoped laws in docs/INVARIANTS.md)."""
    import asyncio
    node = Node(node_id=1)
    node.execute(cmd(b"sadd", b"s", b"a", b"b", b"c"))
    node.execute(cmd(b"hset", b"h", b"f1", b"v1", b"f2", b"v2"))
    from constdb_tpu.server.serve import ServeCoalescer
    rc = node.read_cache

    def chunk(*msgs):
        out = bytearray()
        ServeCoalescer(node).run_chunk(list(msgs), out)
        return bytes(out)

    chunk(cmd(b"sismember", b"s", b"a"), cmd(b"sismember", b"s", b"b"),
          cmd(b"scnt", b"s"), cmd(b"hget", b"h", b"f1"),
          cmd(b"hget", b"h", b"f2"))
    assert len(rc) == 5
    # sadd of b: drops sismember(b) + scnt (whole-key kind); a/hget live
    node.execute(cmd(b"sadd", b"s", b"b"))
    h0 = rc.hits
    r = chunk(cmd(b"sismember", b"s", b"a"), cmd(b"sismember", b"s", b"b"),
              cmd(b"scnt", b"s"))
    assert r == b":1\r\n:1\r\n:3\r\n"
    assert rc.hits == h0 + 1  # only sismember(a) survived
    # hset of f1: hget(f2) survives, hget(f1) refreshes
    node.execute(cmd(b"hset", b"h", b"f1", b"v9"))
    h0 = rc.hits
    r = chunk(cmd(b"hget", b"h", b"f1"), cmd(b"hget", b"h", b"f2"))
    assert r == b"$2\r\nv9\r\n$2\r\nv2\r\n"
    assert rc.hits == h0 + 1
    # srem flips the surviving member's reply through invalidation
    node.execute(cmd(b"srem", b"s", b"a"))
    r = chunk(cmd(b"sismember", b"s", b"a"), cmd(b"sismember", b"s", b"b"))
    assert r == b":0\r\n:1\r\n"
    # DEL drops every entry for the key
    node.execute(cmd(b"del", b"s"))
    r = chunk(cmd(b"sismember", b"s", b"a"), cmd(b"sismember", b"s", b"b"))
    assert r == b":0\r\n:0\r\n"


def test_read_run_defers_across_disjoint_writes(tmp_path):
    """A read run stays open across interleaved writes of OTHER keys
    (replies still in exact request order, reads see their exact
    stream-position state), and closes when a write touches a run key."""
    work = [[
        # r1 read, write other key, r1 read again, write r1 -> close,
        # read r1 after the write must see it
        [cmd(b"set", b"r1", b"old"), cmd(b"set", b"r2", b"x")],
        [cmd(b"get", b"r1"), cmd(b"set", b"r2", b"y"),
         cmd(b"get", b"r1"), cmd(b"set", b"r1", b"new"),
         cmd(b"get", b"r1"), cmd(b"get", b"r2")],
    ]]

    async def main():
        got = await drive_node(tmp_path / "a", 64, work)
        want = await drive_node(tmp_path / "b", 1, work)
        return got, want

    (g_raw, g_canon, g_repl, g_node), (w_raw, w_canon, w_repl, _w) = \
        asyncio.run(main())
    assert g_raw == w_raw
    assert g_canon == w_canon
    assert g_repl == w_repl
    # the deferral engaged: reads planned despite the interleaved writes
    assert g_node.stats.serve_reads_coalesced == 4


def test_cache_governor_accounting(tmp_path):
    """Cache bytes ride used_memory; the hard-watermark reclaim drops
    the cache (it is a rebuildable warm cache)."""
    node = Node(node_id=1)
    ks = _FakeKs()
    rc = node.read_cache
    rc.configure(1 << 20)
    base = node.governor.used_memory()
    rc.put(b"get", b"k", b"", 1, ks, b"v" * 1000)
    assert node.governor.used_memory() >= base + 1000
    node.governor.configure(maxmemory=1, soft_pct=85.0)
    node.governor.tick()  # hard watermark -> reclaim
    assert len(rc) == 0 and rc.bytes == 0


def test_wipe_clears_cache():
    node = Node(node_id=1)
    node.execute(cmd(b"set", b"k", b"v"))
    # fill via a coalesced chunk (lone commands bypass the cache)
    from constdb_tpu.server.serve import ServeCoalescer
    coal = ServeCoalescer(node, max_run=64)
    out = bytearray()
    coal.run_chunk([cmd(b"get", b"k"), cmd(b"get", b"k")], out)
    assert len(node.read_cache) == 1
    node.reset_for_full_resync()
    assert len(node.read_cache) == 0


# ------------------------------------------------------------------- INFO

def test_info_read_gauges(tmp_path):
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            await _pipeline(c, [cmd(b"set", b"k", b"v"),
                                cmd(b"set", b"k2", b"v2")])
            await _pipeline(c, [cmd(b"get", b"k"), cmd(b"get", b"k2")])
            await _pipeline(c, [cmd(b"get", b"k"), cmd(b"get", b"k2")])
            info = (await c.cmd("info")).val.decode()
            assert "serve_reads_coalesced:4" in info
            assert "serve_read_flushes:" in info
            assert "read_cache_hits:2" in info
            assert "read_cache_misses:2" in info
            assert "read_cache_invalidations:" in info
            import re
            m = re.search(r"read_cache_bytes:(\d+)", info)
            assert m and int(m.group(1)) > 0
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())
