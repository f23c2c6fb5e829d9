"""Coalesced client serving (server/serve.py + server/io.py).

The load-bearing claims, each pinned here:
  * a coalescing node is byte-identical to a CONSTDB_SERVE_BATCH=1 node —
    a multi-connection pipelined workload (writes, counters, reads, DELs,
    membership ops interleaved deterministically) produces the same reply
    byte stream per connection, the same canonical keyspace export, and
    the same repl_log entry sequence;
  * reads and non-plannable commands are ordered barriers: reply order
    and read-your-writes hold inside one pipelined chunk;
  * a lone command (single-message chunk) takes the exact per-command
    path — no micro-merge, no flush, zero added latency;
  * `ReplLog.push_many` is equivalent to a push loop (entries, sizes,
    eviction, prev_uuid chain, error on non-increasing uuids);
  * a parse error mid-pipeline no longer drops the completed replies
    already encoded for earlier commands (server/io.py CstError path),
    and the error bytes are counted in net_out_bytes;
  * INFO surfaces serve_msgs_coalesced / serve_flushes / serve_barriers
    and the sampled reply-latency percentiles.
"""

import asyncio
import random

import pytest

from constdb_tpu.resp.codec import RespParser, encode_msg
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, Simple
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.repl_log import ReplLog
from constdb_tpu.utils.hlc import SEQ_BITS

from cluster_util import FAST, Client

MS0 = 1_700_000_000_000


def u(i: int) -> int:
    return (MS0 + i) << SEQ_BITS


def stepping_clock():
    """Deterministic HLC clock: advances 1ms per call, so two nodes
    executing the same command sequence mint identical uuid streams —
    the precondition for byte-identical canonical exports."""
    ms = [MS0]

    def clock():
        ms[0] += 1
        return ms[0]
    return clock


def cmd(*parts) -> Arr:
    return Arr([p if isinstance(p, (Bulk, Int)) else
                Bulk(p if isinstance(p, bytes) else str(p).encode())
                for p in parts])


async def read_replies(client, parser_sink: bytearray, n: int) -> list:
    """Read exactly n replies; raw bytes accumulate into parser_sink."""
    out = []
    while len(out) < n:
        m = client.parser.next_msg()
        if m is not None:
            out.append(m)
            continue
        data = await asyncio.wait_for(client.reader.read(1 << 16), 10.0)
        if not data:
            raise ConnectionError("EOF")
        parser_sink += data
        client.parser.feed(data)
    return out


def mixed_workload(n_conns: int, rounds: int, seed: int = 9) -> list:
    """Per-connection chunk lists covering every plannable command plus
    every barrier class (reads, DEL, expiry, lists, admin), with some
    single-command chunks to exercise the lone-command path."""
    rng = random.Random(seed)
    work = [[] for _ in range(n_conns)]
    for _ in range(rounds):
        for ci in range(n_conns):
            chunk = []
            for _ in range(rng.choice((1, 1, 4, 8, 16, 24))):
                r = rng.random()
                k = b"k%02d" % rng.randrange(24)
                if r < 0.20:
                    chunk.append(cmd(b"set", b"r" + k, b"v%d" % rng.getrandbits(24)))
                elif r < 0.38:
                    chunk.append(cmd(b"incr", b"c" + k, rng.randrange(1, 9))
                                 if rng.random() < 0.5 else
                                 cmd(b"decr", b"c" + k))
                elif r < 0.52:
                    chunk.append(cmd(b"sadd", b"s" + k,
                                     b"m%d" % rng.randrange(8),
                                     b"m%d" % rng.randrange(8)))
                elif r < 0.60:
                    chunk.append(cmd(b"hset", b"h" + k,
                                     b"f%d" % rng.randrange(5),
                                     b"v%d" % rng.getrandbits(16)))
                elif r < 0.66:
                    chunk.append(cmd(b"srem", b"s" + k,
                                     b"m%d" % rng.randrange(8)))
                elif r < 0.70:
                    chunk.append(cmd(b"hdel", b"h" + k,
                                     b"f%d" % rng.randrange(5)))
                elif r < 0.76:
                    chunk.append(cmd(b"get", b"r" + k))
                elif r < 0.80:
                    chunk.append(cmd(b"smembers", b"s" + k))
                elif r < 0.84:
                    chunk.append(cmd(b"del", rng.choice(
                        (b"r", b"s", b"c", b"h")) + k))
                elif r < 0.88:
                    chunk.append(cmd(b"lpush", b"l" + k, b"x%d" % rng.getrandbits(16)))
                elif r < 0.90:
                    chunk.append(cmd(b"lrange", b"l" + k, 0, -1))
                elif r < 0.93:
                    # type conflict on purpose: sadd against a register
                    chunk.append(cmd(b"sadd", b"r" + k, b"m"))
                elif r < 0.96:
                    chunk.append(cmd(b"hget", b"h" + k, b"f1"))
                elif r < 0.98:
                    chunk.append(cmd(b"expireat", b"r" + k, u(1 << 20)))
                else:
                    chunk.append(cmd(b"desc", b"r" + k))
            work[ci].append(chunk)
    return work


async def drive_node(tmp_path, serve_batch, work, engine=None):
    """One node + len(work) client connections driven in deterministic
    lockstep (a conn's chunk fully replies before the next conn sends).
    `engine`: a MergeEngine INSTANCE for the node (default CPU reference;
    test_resident_steady.py passes a device-resident one and inspects
    its transfer gauges afterwards).  Returns (reply_bytes_per_conn,
    canonical, repl_entries, stats)."""
    node = Node(node_id=1, alias="n1", clock=stepping_clock(),
                **({"engine": engine} if engine is not None else {}))
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=str(tmp_path), serve_batch=serve_batch,
                           **FAST)
    # the cron's periodic hlc.tick fires on wall-clock timing and would
    # shift the two legs' uuid streams apart — only command execution may
    # tick in this differential
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
        canonical = node.canonical()
        repl = [(e.uuid, e.prev_uuid, e.name, e.size,
                 tuple((type(a).__name__, a.val) for a in e.args))
                for e in node.repl_log.entries()]
        return [bytes(r) for r in raw], canonical, repl, node.stats
    finally:
        for c in conns:
            await c.close()
        await app.close()


def test_multi_connection_differential(tmp_path):
    """The oracle: coalesced vs CONSTDB_SERVE_BATCH=1, same deterministic
    multi-connection workload — byte-identical reply streams, canonical
    export, and repl_log."""
    work = mixed_workload(n_conns=3, rounds=14)

    async def main():
        got = await drive_node(tmp_path / "a", 64, work)
        want = await drive_node(tmp_path / "b", 1, work)
        return got, want

    (g_raw, g_canon, g_repl, g_st), (w_raw, w_canon, w_repl, w_st) = \
        asyncio.run(main())
    for ci, (g, w) in enumerate(zip(g_raw, w_raw)):
        assert g == w, f"conn {ci} reply stream diverged"
    assert g_canon == w_canon
    assert g_repl == w_repl
    # the coalescing leg really coalesced; the pinned leg never did
    assert g_st.serve_msgs_coalesced > 0
    assert 0 < g_st.serve_flushes < g_st.serve_msgs_coalesced
    assert g_st.serve_barriers > 0
    assert w_st.serve_msgs_coalesced == 0 and w_st.serve_flushes == 0
    # same command accounting either way
    assert g_st.cmds_processed == w_st.cmds_processed


def test_reply_order_and_read_your_writes(tmp_path):
    """One pipelined chunk: replies come back strictly in request order
    and a read after a planned write observes it (the read barrier
    flushes the pending run first)."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            chunk = [cmd(b"set", b"k", b"v1"), cmd(b"incr", b"n", 3),
                     cmd(b"get", b"k"), cmd(b"set", b"k", b"v2"),
                     cmd(b"get", b"k"), cmd(b"incr", b"n"),
                     cmd(b"sadd", b"s", b"a"), cmd(b"smembers", b"s"),
                     cmd(b"srem", b"s", b"a"), cmd(b"smembers", b"s")]
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            r = await read_replies(c, bytearray(), len(chunk))
            assert r[0] == Simple(b"OK")
            assert r[1] == Int(3)
            assert r[2] == Bulk(b"v1")
            assert r[3] == Simple(b"OK")
            assert r[4] == Bulk(b"v2")
            assert r[5] == Int(4)
            assert r[6] == Int(1)
            assert [m.val for m in r[7].items] == [b"a"]
            assert r[8] == Int(1)
            assert r[9].items == []
            # every read was served by the planned read path (round 18:
            # reads are no longer barriers).  The first get/smembers
            # each observed a pending run and forced a read-your-writes
            # land; the second of each followed an ISOLATED write
            # (executed per-command by choice), so nothing was pending
            # and no flush was needed — still byte-exact
            assert node.stats.serve_reads_coalesced == 4
            assert node.stats.serve_read_flushes == 2
            assert node.stats.serve_barriers == 0
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_lone_command_takes_per_command_path(tmp_path):
    """A single-message chunk must bypass the planner entirely: no
    flushes, no merges, no coalescing — zero added latency."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            assert await c.cmd("set", "k", "v") == Simple(b"OK")
            assert await c.cmd("incr", "n") == Int(1)
            assert await c.cmd("get", "k") == Bulk(b"v")
            st = node.stats
            assert st.serve_flushes == 0
            assert st.serve_msgs_coalesced == 0
            assert st.merges == 0
            # a pipelined chunk on the same connection does coalesce
            chunk = [cmd(b"set", b"a%d" % i, b"v") for i in range(8)]
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            await read_replies(c, bytearray(), len(chunk))
            assert st.serve_msgs_coalesced == 8
            assert st.serve_flushes == 1
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_isolated_write_between_barriers_stays_per_command(tmp_path):
    """Inside a multi-message chunk, a plannable write with no plannable
    neighbor executes per-command — no one-row micro-merge."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            chunk = [cmd(b"get", b"x"), cmd(b"set", b"k", b"v"),
                     cmd(b"get", b"k")]
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            r = await read_replies(c, bytearray(), len(chunk))
            assert r[1] == Simple(b"OK") and r[2] == Bulk(b"v")
            assert node.stats.serve_flushes == 0
            assert node.stats.merges == 0
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_node_id_change_mid_pipeline(tmp_path):
    """NODE ID mid-chunk rebinds the identity the counter overlays are
    tracked under — the coalescer must drop its caches (a CTRL barrier
    invalidates everything), or post-change INCRs would keep extending
    the OLD node's slot total.  Differential against SERVE_BATCH=1."""
    async def drive(serve_batch):
        node = Node(node_id=1, clock=stepping_clock())
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=serve_batch,
                               **FAST)
        app._cron_task.cancel()
        c = await Client().connect(app.advertised_addr)
        try:
            chunk = [cmd(b"incr", b"c"), cmd(b"incr", b"c"),
                     cmd(b"node", b"id", 7),
                     cmd(b"incr", b"c"), cmd(b"incr", b"c"),
                     cmd(b"get", b"c")]
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            replies = await read_replies(c, bytearray(), len(chunk))
            canon = node.canonical()
            return replies, canon
        finally:
            await c.close()
            await app.close()

    async def main():
        return await drive(64), await drive(1)

    (g_rep, g_canon), (w_rep, w_canon) = asyncio.run(main())
    assert g_rep == w_rep
    assert g_canon == w_canon
    assert g_rep[-1] == Int(4)  # both slots visible in the sum


# --------------------------------------------------------------- repl_log


def test_push_many_equals_loop():
    def entries(log):
        return [(e.uuid, e.prev_uuid, e.name, e.size,
                 tuple(a.val for a in e.args))
                for e in log.entries()]

    cmds = [(u(i), b"set" if i % 3 else b"cntset",
             [Bulk(b"k%d" % (i % 5)), Bulk(b"v" * (i % 23)) if i % 3
              else Int(i * 7)])
            for i in range(1, 120)]
    # small cap so eviction engages mid-run
    a, b = ReplLog(cap_bytes=700), ReplLog(cap_bytes=700)
    for c in cmds:
        a.push(*c)
    b.push_many(cmds)
    assert entries(a) == entries(b)
    assert a.last_uuid == b.last_uuid
    assert a.evicted_up_to == b.evicted_up_to
    assert a.total_bytes == b.total_bytes
    assert a.uuids() == b.uuids()

    # split calls chain prev_uuid across the boundary like a loop would
    c1, c2 = ReplLog(10_000), ReplLog(10_000)
    for c in cmds[:40]:
        c1.push(*c)
    c2.push_many(cmds[:17])
    c2.push_many(cmds[17:40])
    assert entries(c1) == entries(c2)

    # non-increasing uuids refuse exactly like push
    with pytest.raises(ValueError):
        b.push_many([(b.last_uuid, b"set", [Bulk(b"k")])])
    with pytest.raises(ValueError):
        ReplLog().push_many([(u(2), b"set", [Bulk(b"k")]),
                             (u(2), b"set", [Bulk(b"k")])])
    # empty run is a no-op
    before = entries(b)
    b.push_many([])
    assert entries(b) == before


# ------------------------------------------------------------ error path


@pytest.mark.parametrize("serve_batch", (512, 1))
def test_parse_error_keeps_completed_replies(tmp_path, serve_batch):
    """A malformed frame mid-pipeline: completed commands still execute
    and their replies reach the client BEFORE the protocol error, and
    the error bytes are counted in net_out_bytes."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path),
                               serve_batch=serve_batch, **FAST)
        reader, writer = await asyncio.open_connection("127.0.0.1", app.port)
        try:
            good = encode_msg(cmd(b"set", b"k", b"v")) + \
                encode_msg(cmd(b"incr", b"n"))
            writer.write(good + b"!bogus\r\n")
            await writer.drain()
            data = b""
            while True:
                chunk = await asyncio.wait_for(reader.read(1 << 16), 5.0)
                if not chunk:
                    break
                data += chunk
            parser = RespParser()
            parser.feed(data)
            replies = parser.drain()
            assert replies[0] == Simple(b"OK"), replies
            assert replies[1] == Int(1)
            assert isinstance(replies[2], Err)
            # the write really landed before the teardown
            kid = node.ks.lookup(b"k")
            assert kid >= 0 and node.ks.register_get(kid) == b"v"
            assert node.stats.net_out_bytes >= len(data)
        finally:
            writer.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("serve_batch", (512, 1))
def test_replies_flush_before_sync_upgrade(tmp_path, serve_batch):
    """Commands pipelined BEFORE a SYNC in the same chunk: their replies
    must reach the client before the handshake reply takes the stream
    over (they used to be silently dropped)."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path),
                               serve_batch=serve_batch, **FAST)
        reader, writer = await asyncio.open_connection("127.0.0.1", app.port)
        try:
            sync = Arr([Bulk(b"sync"), Int(0), Int(99), Bulk(b"nx"),
                        Bulk(b"127.9.9.9:19"), Int(0), Int(0)])
            writer.write(encode_msg(cmd(b"set", b"k", b"v")) +
                         encode_msg(cmd(b"incr", b"n")) +
                         encode_msg(sync))
            await writer.drain()
            parser = RespParser()
            got = []
            while len(got) < 3:
                data = await asyncio.wait_for(reader.read(1 << 16), 10.0)
                assert data, got
                parser.feed(data)
                got.extend(parser.drain())
            assert got[0] == Simple(b"OK")
            assert got[1] == Int(1)
            # then the handshake reply — the connection is a link now
            assert isinstance(got[2], Arr) and got[2].items[0].val == b"sync"
            # the writes really landed and were logged (a third entry is
            # the handshake's replicated MEET introduction)
            assert node.ks.lookup(b"k") >= 0
            assert [e.name for e in node.repl_log.entries()][:2] == \
                [b"set", b"cntset"]
        finally:
            writer.close()
            await app.close()
    asyncio.run(main())


# ------------------------------------------------------------ bench smoke


def test_serve_bench_smoke():
    """bench.py --mode serve end-to-end on a tiny workload: JSON line
    present, oracle-verified (reply streams + export projection), and
    the coalescing leg really coalesced."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CONSTDB_BENCH_SERVE_OPS="1600",
               CONSTDB_BENCH_SERVE_CONNS="2",
               CONSTDB_BENCH_SERVE_PIPELINE="64",
               CONSTDB_BENCH_SERVE_KEYS="200",
               CONSTDB_BENCH_SERVE_REPS="1",
               CONSTDB_AUTO_NATIVE="0")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "--mode", "serve"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serve_requests_per_sec"
    assert out["verified"] is True
    assert out["ops"] == 1600
    assert out["value"] > 0 and out["per_command_baseline_rps"] > 0
    assert out["serve_msgs_coalesced"] > 0
    assert "reply_p99_ms" in out


# ------------------------------------------------------------------ INFO


def test_info_serve_stats(tmp_path):
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            chunk = [cmd(b"set", b"k%d" % i, b"v") for i in range(40)]
            chunk.append(cmd(b"get", b"k0"))
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            await read_replies(c, bytearray(), len(chunk))
            info = (await c.cmd("info", "stats")).val.decode()
            assert "serve_msgs_coalesced:40" in info
            assert "serve_flushes:1" in info
            assert "serve_barriers:" in info
            assert "serve_lat_p50_ms:" in info
            assert "serve_lat_p99_ms:" in info
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())
