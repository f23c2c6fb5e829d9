"""The loop-pass gather (server/io.py _PassGather) in front of the node's
one ServeCoalescer (docs/INVARIANTS.md "Client-serving coalescing").

What one pass of the event loop delivers, from any number of connections,
is planned as ONE chunk.  Pinned here, over N connections x pipeline depth
driven concurrently through real sockets, on `engine/cpu.py` and on
`TpuMergeEngine` (JAX-CPU), with all seven planned writes and eight
planned reads on colliding keys, barriers, a type conflict, an
expiry-armed key and a phase in which the maxmemory gate sheds:

  (a) each connection's replies equal, byte for byte, those of a
      per-command node fed the commands in the gathered order (the order
      the passes ran them, recorded at the gather);
  (b) the canonical exports are equal;
  (c) the uuid streams (repl_log entries) are equal;
  (d) a SYNC in the middle of a pass upgrades after the replies before it
      left;
  (e) a connection that closes inside a pass loses only its own replies;
  (f) `serve_gather_*`, `serve_lone_cmds` and `span_gather_us` move as the
      passes say;
and: a connection with CLIENT TRACKING state keeps its own path, a
malformed frame answers what parsed before it, depth-1 SETs of fifty
connections reach the device planes (tests/test_resident_steady.py holds
the store's equality there).
"""

import asyncio
import random

import pytest

from constdb_tpu.resp.codec import (RespParser, encode_into, encode_msg,
                                     make_parser)
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, NoReply, Simple
from constdb_tpu.server import info as info_mod
from constdb_tpu.server import serve as serve_mod
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node

from cluster_util import FAST, Client
from test_serve_coalesce import cmd, read_replies, stepping_clock, u

WRITES = 7      # set incr decr sadd srem hset hdel
READS = 8       # get scnt sismember smembers hget hgetall llen hlen


def gather_workload(n_conns: int, depth: int, rounds: int, seed: int) -> list:
    """work[conn][round] = a chunk of `depth` commands: every planned
    write and read on keys the connections share, barriers (del, lpush,
    desc), a type conflict and an expiry-armed key among them."""
    rng = random.Random(seed)
    work = []
    for _ in range(n_conns):
        chunks = []
        for _ in range(rounds):
            chunk = []
            for _ in range(depth):
                k = b"k%d" % rng.randrange(6)
                m = b"m%d" % rng.randrange(4)
                r = rng.randrange(40)
                if r < 6:
                    c = cmd(b"set", b"r" + k, b"v%d" % rng.getrandbits(20))
                elif r < 9:
                    c = cmd(b"incr", b"c" + k, rng.randrange(1, 9))
                elif r < 11:
                    c = cmd(b"decr", b"c" + k)
                elif r < 14:
                    c = cmd(b"sadd", b"s" + k, m, b"m%d" % rng.randrange(4))
                elif r < 16:
                    c = cmd(b"srem", b"s" + k, m)
                elif r < 19:
                    c = cmd(b"hset", b"h" + k, m, b"v%d" % rng.getrandbits(9))
                elif r < 21:
                    c = cmd(b"hdel", b"h" + k, m)
                elif r < 24:
                    c = cmd(b"get", rng.choice((b"r", b"c")) + k)
                elif r < 25:
                    c = cmd(b"scnt", b"s" + k)
                elif r < 26:
                    c = cmd(b"sismember", b"s" + k, m)
                elif r < 28:
                    c = cmd(b"smembers", b"s" + k)
                elif r < 29:
                    c = cmd(b"hget", b"h" + k, m)
                elif r < 31:
                    c = cmd(b"hgetall", b"h" + k)
                elif r < 32:
                    c = cmd(b"llen", b"l" + k)
                elif r < 33:
                    c = cmd(b"hlen", b"h" + k)
                elif r < 34:
                    c = cmd(b"del", rng.choice((b"r", b"s", b"c", b"h")) + k)
                elif r < 35:
                    c = cmd(b"lpush", b"l" + k, b"x%d" % rng.getrandbits(9))
                elif r < 36:
                    c = cmd(b"sadd", b"r" + k, b"m")     # type conflict
                elif r < 37:
                    c = cmd(b"expireat", b"r" + k, u(1 << 20))
                elif r < 38:
                    c = cmd(b"desc", b"r" + k)
                else:
                    c = cmd(b"get", b"h" + k)            # wrong-type read
                chunk.append(c)
            chunks.append(chunk)
        work.append(chunks)
    return work


def make_engine(kind: str):
    if kind == "cpu":
        return None
    pytest.importorskip("jax")
    from constdb_tpu.engine.tpu import TpuMergeEngine
    return TpuMergeEngine(resident=True, steady=True, warmup=0)


def new_node(kind: str) -> Node:
    eng = make_engine(kind)
    return Node(node_id=1, alias="n1", clock=stepping_clock(),
                **({"engine": eng} if eng is not None else {}))


def msgs_of(seg) -> list:
    ops, payloads = seg
    if ops is None:
        return list(payloads)
    return [serve_mod._nat_msg(op, pl) for op, pl in zip(ops, payloads)]


def record_gathered_order(app) -> tuple:
    """Wrap the app's hand-over and the gather's pass so the test learns
    the order the passes ran the commands in: -> (order, passes) where
    order = [(cid, msg)] and passes = [messages a pass held].  A pass
    reads its segments as the reader joined them (their connection
    beside them) or as tasks handed them over (their connection noted
    here)."""
    order, passes, owner = [], [], {}
    gather = app._gather
    run_chunk, run_pass = app._run_chunk, gather._run_pass

    async def recording_run_chunk(plane, g, seg, out, client):
        if g.keeps_own_path(client, *seg):
            order.extend((client.cid, m) for m in msgs_of(seg))
        else:
            owner[id(seg[1])] = client.cid
        return await run_chunk(plane, g, seg, out, client)

    def recording_run_pass():
        n = 0
        for ops, payloads, fut, client in gather.segs:
            cid = client.cid if fut is None else owner.pop(id(payloads))
            order.extend((cid, m) for m in msgs_of((ops, payloads)))
            n += len(payloads)
        passes.append(n)
        run_pass()

    app._run_chunk = recording_run_chunk
    gather._run_pass = recording_run_pass
    return order, passes


def shed_from_now_on(node: Node) -> None:
    """The maxmemory gate sheds every data-growing write from here."""
    node.governor.configure(1, 50.0)
    node.governor.check_every = 1
    node.governor.tick()


def repl_entries(node: Node) -> list:
    return [(e.uuid, e.prev_uuid, e.name,
             tuple((type(a).__name__, a.val) for a in e.args))
            for e in node.repl_log.entries()]


async def drive_gathered(tmp_path, kind: str, work: list, shed_work: list):
    """All connections at once, each a closed loop of its chunks."""
    node = new_node(kind)
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=str(tmp_path), **FAST)
    app._cron_task.cancel()     # only commands may tick the HLC
    order, passes = record_gathered_order(app)
    conns = [await Client().connect(app.advertised_addr) for _ in work]
    raw = [bytearray() for _ in work]

    async def loop_of(ci: int, chunks: list) -> None:
        c = conns[ci]
        for chunk in chunks:
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            await read_replies(c, raw[ci], len(chunk))

    try:
        await asyncio.gather(*(loop_of(i, w) for i, w in enumerate(work)))
        cut = len(order)
        shed_from_now_on(node)
        await asyncio.gather(*(loop_of(i, w)
                               for i, w in enumerate(shed_work)))
        node.ensure_flushed()
        cids = [c for c in sorted(app.client_conns)]
        return {"raw": [bytes(r) for r in raw], "order": order, "cut": cut,
                "passes": passes, "cids": cids,
                "reader": app.read_pump is not None,
                "canonical": node.canonical(), "repl": repl_entries(node),
                "stats": node.stats, "info": info_of(node)}
    finally:
        for c in conns:
            await c.close()
        await app.close()


def info_of(node: Node) -> dict:
    out: list = []
    info_mod._section_stats(node, out)
    return dict(out)


def replay_per_command(kind: str, got: dict) -> dict:
    """A per-command node fed the gathered order: -> the same fields."""
    node = new_node(kind)
    raw = {cid: bytearray() for cid in got["cids"]}
    for i, (cid, msg) in enumerate(got["order"]):
        if i == got["cut"]:
            shed_from_now_on(node)
        reply = node.execute(msg)
        if not isinstance(reply, NoReply):
            encode_into(raw[cid], reply)
    node.ensure_flushed()
    return {"raw": [bytes(raw[cid]) for cid in got["cids"]],
            "canonical": node.canonical(), "repl": repl_entries(node),
            "stats": node.stats}


CASES = [(n, d, "cpu") for n in (1, 2, 7, 64) for d in (1, 4)] + \
    [(n, d, "tpu") for n in (2, 7, 64) for d in (1, 4)]


@pytest.mark.parametrize("n_conns,depth,kind", CASES)
def test_gathered_node_equals_per_command_node_fed_the_gathered_order(
        tmp_path, n_conns, depth, kind):
    rounds = max(6, 96 // n_conns)
    seed = 1000 * n_conns + depth
    work = gather_workload(n_conns, depth, rounds, seed)
    shed = gather_workload(n_conns, depth, 2, seed + 1)
    got = asyncio.run(drive_gathered(tmp_path, kind, work, shed))
    want = replay_per_command(kind, got)
    total = n_conns * depth * (rounds + 2)
    assert len(got["order"]) == total
    for ci, (g, w) in enumerate(zip(got["raw"], want["raw"])):
        assert g == w, f"connection {ci}'s replies diverged"      # (a)
    assert got["canonical"] == want["canonical"]                  # (b)
    assert got["repl"] == want["repl"]                            # (c)
    assert got["stats"].cmds_processed == want["stats"].cmds_processed
    assert got["stats"].oom_shed_writes == want["stats"].oom_shed_writes > 0
    # (f) the counters say what the passes were
    st, info, passes = got["stats"], got["info"], got["passes"]
    assert st.serve_gather_passes == len(passes)
    assert st.serve_gather_msgs == sum(passes) == total
    assert st.serve_lone_cmds == sum(1 for p in passes if p == 1)
    assert st.serve_gather_conns * depth == total
    assert int(info["serve_gather_passes"]) == len(passes)
    assert int(info["span_gather_us"]) > 0
    # one entry a pass, and one a hand-over: none where the reader reads
    hand_overs = 0 if got["reader"] else total // depth
    assert int(info["span_gather_n"]) == len(passes) + hand_overs
    if n_conns == 1:
        assert set(passes) == {depth}     # its own chunks, as before
    elif n_conns >= 7:
        # connections did meet in a pass, and their writes in a run
        assert max(passes) > depth
        assert st.serve_msgs_coalesced > 0 and st.serve_flushes > 0


def test_sync_in_a_pass_upgrades_after_the_replies_before_it(tmp_path):
    """(d) one connection pipelines two writes and a SYNC while others
    send in the same pass: its two replies come first, then the
    handshake; the others' replies are their own."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        _order, passes = record_gathered_order(app)
        others = [await Client().connect(app.advertised_addr)
                  for _ in range(6)]
        reader, writer = await asyncio.open_connection("127.0.0.1", app.port)
        try:
            sync = Arr([Bulk(b"sync"), Int(0), Int(99), Bulk(b"nx"),
                        Bulk(b"127.9.9.9:19"), Int(0), Int(0)])
            for i, c in enumerate(others):
                c.writer.write(encode_msg(cmd(b"incr", b"n%d" % i)))
            writer.write(encode_msg(cmd(b"set", b"k", b"v")) +
                         encode_msg(cmd(b"incr", b"n")) + encode_msg(sync))
            await asyncio.gather(writer.drain(),
                                 *(c.writer.drain() for c in others))
            parser, got = RespParser(), []
            while len(got) < 3:
                data = await asyncio.wait_for(reader.read(1 << 16), 10.0)
                assert data, got
                parser.feed(data)
                got.extend(parser.drain())
            assert got[0] == Simple(b"OK") and got[1] == Int(1)
            assert isinstance(got[2], Arr) and got[2].items[0].val == b"sync"
            for c in others:
                assert await read_replies(c, bytearray(), 1) == [Int(1)]
            assert sum(passes) == 8 and node.ks.lookup(b"k") >= 0
        finally:
            writer.close()
            for c in others:
                await c.close()
            await app.close()
    asyncio.run(main())


def test_a_connection_closing_inside_a_pass_loses_only_its_own_replies(
        tmp_path):
    """(e) seven connections send, one of them closes at once: its write
    may land, its reply goes nowhere, and the six others read theirs."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        conns = [await Client().connect(app.advertised_addr)
                 for _ in range(7)]
        try:
            for rnd in range(5):
                for i, c in enumerate(conns):
                    c.writer.write(encode_msg(cmd(b"incr", b"n%d" % i)))
                if rnd == 2:
                    gone = conns.pop(3)
                    gone.writer.transport.abort()
                for c in conns:
                    await c.writer.drain()
                for c in conns:
                    assert await read_replies(c, bytearray(), 1) == \
                        [Int(rnd + 1)]
            assert node.stats.serve_gather_msgs >= 5 * 6
            await asyncio.sleep(0.05)
            assert len(app.client_conns) == 6
        finally:
            for c in conns:
                await c.close()
            await app.close()
    asyncio.run(main())


def test_a_tracking_connection_keeps_its_own_path(tmp_path):
    """HELLO 3 + CLIENT TRACKING on: the connection's chunks never join a
    pass (its replies reach its transport before a later write's push
    can), its reads are recorded, and a write of another connection in
    a gathered pass still invalidates them."""
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        _order, passes = record_gathered_order(app)
        t = await Client().connect(app.advertised_addr)
        w = await Client().connect(app.advertised_addr)
        try:
            for c in (cmd(b"hello", 3), cmd(b"client", b"tracking", b"on")):
                t.writer.write(encode_msg(c))
                await t.writer.drain()
                await read_replies(t, bytearray(), 1)
            assert passes == []                     # both on its own path
            t.writer.write(encode_msg(cmd(b"get", b"k")) +
                           encode_msg(cmd(b"get", b"j")))
            await t.writer.drain()
            await read_replies(t, bytearray(), 2)
            assert passes == [] and node.stats.serve_gather_passes == 0
            for i in range(3):
                w.writer.write(encode_msg(cmd(b"set", b"k", b"v%d" % i)) +
                               encode_msg(cmd(b"set", b"j", b"v%d" % i)))
                await w.writer.drain()
                await read_replies(w, bytearray(), 2)
            assert passes == [2, 2, 2]
            push = (await read_replies(t, bytearray(), 1))[0]
            assert b"invalidate" in encode_msg(push)
        finally:
            await t.close()
            await w.close()
            await app.close()
    asyncio.run(main())


def test_a_malformed_frame_in_a_pass_answers_what_parsed_before_it(tmp_path):
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), **FAST)
        others = [await Client().connect(app.advertised_addr)
                  for _ in range(4)]
        reader, writer = await asyncio.open_connection("127.0.0.1", app.port)
        try:
            for i, c in enumerate(others):
                c.writer.write(encode_msg(cmd(b"incr", b"n%d" % i)))
            writer.write(encode_msg(cmd(b"set", b"k", b"v")) +
                         encode_msg(cmd(b"incr", b"n")) + b"!bogus\r\n")
            await writer.drain()
            data = b""
            while True:
                got = await asyncio.wait_for(reader.read(1 << 16), 5.0)
                if not got:
                    break
                data += got
            parser = RespParser()
            parser.feed(data)
            replies = parser.drain()
            assert replies[0] == Simple(b"OK") and replies[1] == Int(1)
            assert isinstance(replies[2], Err)
            for c in others:
                assert await read_replies(c, bytearray(), 1) == [Int(1)]
        finally:
            writer.close()
            for c in others:
                await c.close()
            await app.close()
    asyncio.run(main())


def test_serve_batch_1_never_builds_the_gather(tmp_path):
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=1, **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            c.writer.write(encode_msg(cmd(b"set", b"k", b"v")) +
                           encode_msg(cmd(b"get", b"k")))
            await c.writer.drain()
            got = await read_replies(c, bytearray(), 2)
            assert got == [Simple(b"OK"), Bulk(b"v")]
            assert app._gather is None
            assert node.stats.serve_gather_passes == 0
            assert node.stages.snapshot()["gather"] == (0, 0)
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("native", (True, False))
def test_solo_writes_across_reads_form_one_run(native):
    """The company rule: commands that arrived alone on their connections
    (`solo`) always ride the run of their pass, across the reads between
    them; the same commands as one connection's pipeline keep the
    adjacency rule."""
    from constdb_tpu.server.serve import ServeCoalescer
    msgs = []
    for i in range(8):
        msgs.append(cmd(b"set", b"w%d" % i, b"v"))
        msgs += [cmd(b"get", b"g%d" % j) for j in range(3)]

    def run(solo):
        node = Node(node_id=1, clock=stepping_clock())
        out, spans = bytearray(), []
        coal = ServeCoalescer(node, max_run=512)
        if native:
            parser = make_parser()
            parser.feed(b"".join(encode_msg(m) for m in msgs))
            ops, payloads = parser.native_drain()
            coal.run_native_chunk(ops, payloads, out, spans, solo)
        else:
            coal.run_chunk(msgs, out, None, spans, solo)
        assert len(spans) == len(msgs) and spans[-1] == len(out)
        return bytes(out), node.stats, repl_entries(node), node.canonical()

    alone = run(bytes([1]) * len(msgs))
    piped = run(None)
    assert alone[0] == piped[0] and alone[2] == piped[2]
    assert alone[3] == piped[3]
    # all eight ride one run
    assert alone[1].serve_msgs_coalesced == 8 and alone[1].serve_flushes == 1
    assert piped[1].serve_msgs_coalesced == 0
    # and so does a pass's ONE write: no execute(), no version bump
    msgs[:] = [cmd(b"get", b"g0"), cmd(b"set", b"w", b"v"),
               cmd(b"get", b"g1")]
    one = run(bytes([1]) * 3)
    assert one[0] == run(None)[0]
    assert one[1].serve_msgs_coalesced == 1 and one[1].serve_flushes == 1
