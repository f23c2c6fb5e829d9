"""The reader (native/read.cpp, server/read_pump.py) over real sockets
against a CPU-engine node, and its native calls over socket pairs
(docs/INVARIANTS.md "Read-path laws").

Pinned here:

  * each connection's replies in order across passes, for fifty depth-1
    clients and for one pipelined client, every byte through the reader
    and no task step per connection (`span_gather_n` is one a pass);
  * a frame split across two reads, and a pipeline past the reader's
    256 kB a take;
  * an EOF or a reset inside a pass ends only that connection;
  * a SYNC with commands before it and bytes after it in one read: the
    replies first, the bytes after it on the link's parser;
  * the malformed-frame salvage: earlier replies leave first;
  * HELLO 3 / CLIENT TRACKING take the connection to its transport, with
    what the parser holds parsed ahead of what the transport reads;
  * under fsync=always a connection's next bytes wait for the group
    commit of the pass that holds its previous ones;
  * the per-command loop, the shard plane and the pure tier build no
    reader, and their transports read;
  * descriptor numbers reused across connections never cross bytes;
  * natively: one segment in flight, the hand-back of held bytes, the end
    after the bytes before it, descriptor reuse;
  * no reader thread outlives `ServerApp.close()`;
  * the seven counters are in INFO from boot.
"""

import asyncio
import os
import select
import socket
import struct
import time

import pytest

from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, Simple
from constdb_tpu.server.read_pump import COUNTERS
from constdb_tpu.utils.native_tables import load_ext

from cluster_util import Client
from test_reply_sender import (boot, info_of, parse_all, read_to_eof,
                               wait_for)
from test_serve_coalesce import cmd, read_replies


def reader_threads() -> int:
    """Threads of this process named as the extension names its reader."""
    n = 0
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                n += f.read().strip() == "cst-read"
        except OSError:
            pass    # the thread ended while we looked
    return n


def frames(*cmds) -> bytes:
    return b"".join(encode_msg(c) for c in cmds)


@pytest.mark.parametrize("n_conns,depth", [(1, 32), (50, 1)])
def test_each_connection_reads_its_replies_in_order(tmp_path, n_conns,
                                                     depth):
    rounds = 40 if n_conns == 1 else 12

    async def main():
        node, app = await boot(tmp_path)
        conns = [await Client().connect(app.advertised_addr)
                 for _ in range(n_conns)]
        sent = [0]
        try:
            async def loop_of(i: int, c) -> None:
                seen = []
                for _ in range(rounds):
                    data = frames(*[cmd(b"incr", b"n%d" % i)] * depth)
                    sent[0] += len(data)
                    c.writer.write(data)
                    await c.writer.drain()
                    seen += await read_replies(c, bytearray(), depth)
                assert seen == [Int(k + 1) for k in range(rounds * depth)]

            await asyncio.gather(*(loop_of(i, c) for i, c in enumerate(conns)))
            info = info_of(node)
            # every byte through the reader, none through a transport
            assert info["read_transport_reads"] == 0
            assert info["read_pump_bytes"] == sent[0]
            assert info["total_net_input_bytes"] == sent[0]
            assert info["read_pump_recvs"] >= n_conns * rounds
            assert info["read_pump_takes"] >= info["serve_gather_passes"]
            assert info["read_pump_wakes"] >= 1
            assert info["read_pump_handbacks"] == 0
            # the take joined the segments: no hand-over, no task step
            assert info["span_gather_n"] == info["serve_gather_passes"]
            assert info["span_read_take_n"] >= info["serve_gather_passes"]
            assert info["serve_gather_msgs"] == n_conns * rounds * depth
            if n_conns > 1:    # connections met in passes
                assert node.stats.serve_gather_msgs > \
                    node.stats.serve_gather_passes
        finally:
            for c in conns:
                await c.close()
            await app.close()
    asyncio.run(main())


def test_a_split_frame_and_a_pipeline_past_a_take(tmp_path):
    """Half a frame, then its rest: one reply.  Then 2,000 SETs of 200
    bytes in one write (~470 kB, past the reader's 256 kB a take): every
    reply, in order."""
    async def main():
        node, app = await boot(tmp_path)
        c = await Client().connect(app.advertised_addr)
        try:
            whole = frames(cmd(b"set", b"k", b"v" * 1000))
            c.writer.write(whole[:517])
            await c.writer.drain()
            await asyncio.sleep(0.05)
            assert node.stats.serve_gather_passes == 0
            c.writer.write(whole[517:])
            assert await read_replies(c, bytearray(), 1) == [Simple(b"OK")]
            n = 2000
            big = frames(*[cmd(b"set", b"k%d" % i, b"%0200d" % i)
                           for i in range(n)] + [cmd(b"get", b"k1999")])
            assert len(big) > 256 << 10
            c.writer.write(big)
            got = await read_replies(c, bytearray(), n + 1)
            assert got == [Simple(b"OK")] * n + [Bulk(b"%0200d" % 1999)]
            info = info_of(node)
            assert info["read_pump_bytes"] == len(whole) + len(big)
            assert info["read_transport_reads"] == 0
            assert info["serve_gather_passes"] >= 3
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("how", ["eof", "reset"])
def test_an_end_inside_a_pass_ends_only_that_connection(tmp_path, how):
    """Seven connections send; one of them half-closes (it still reads its
    reply, then the server's FIN) or resets at once.  The others read
    theirs, and the connection is gone from the node."""
    async def main():
        node, app = await boot(tmp_path)
        conns = [await Client().connect(app.advertised_addr)
                 for _ in range(7)]
        gone = conns[3]
        try:
            for rnd in range(4):
                for i, c in enumerate(conns):
                    c.writer.write(frames(cmd(b"incr", b"n%d" % i)))
                if rnd == 1:
                    gone = conns.pop(3)
                    if how == "eof":
                        gone.writer.write_eof()
                    else:
                        sock = gone.writer.get_extra_info("socket")
                        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                        struct.pack("ii", 1, 0))
                        gone.writer.transport.abort()
                for c in conns:
                    await c.writer.drain()
                for c in conns:
                    assert await read_replies(c, bytearray(), 1) == \
                        [Int(rnd + 1)]
                if rnd == 1 and how == "eof":
                    # its reply before the server's FIN
                    assert parse_all(await read_to_eof(gone.reader)) == \
                        [Int(2)]
            await wait_for(lambda: len(app.client_conns) == 6)
            info = info_of(node)
            assert info["read_transport_reads"] == 0
            assert info["read_pump_handbacks"] == 0
        finally:
            for c in conns + [gone]:
                await c.close()
            await app.close()
    asyncio.run(main())


def test_a_sync_hands_the_bytes_after_it_to_the_links_parser(tmp_path):
    async def main():
        node, app = await boot(tmp_path)
        seen = {}
        upgrade = app._upgrade_to_replica

        def spy(msg, reader, writer, parser):
            seen["queued"] = parser.drain()
            seen["buffered"] = parser.buffered
            parser.pushback(seen["queued"])
            upgrade(msg, reader, writer, parser)
        app._upgrade_to_replica = spy
        reader, writer = await asyncio.open_connection("127.0.0.1", app.port)
        try:
            sync = Arr([Bulk(b"sync"), Int(0), Int(99), Bulk(b"nx"),
                        Bulk(b"127.9.9.9:19"), Int(0), Int(0)])
            after = frames(cmd(b"replconf", b"ack", b"7"))
            half = frames(cmd(b"replconf", b"ack", b"8"))[:9]
            writer.write(frames(cmd(b"set", b"k", b"v"), cmd(b"incr", b"n"),
                                sync) + after + half)
            await writer.drain()
            got = []
            buf = bytearray()
            while len(got) < 3:
                data = await asyncio.wait_for(reader.read(1 << 16), 10.0)
                assert data, got
                buf += data
                got = parse_all(bytes(buf))
            assert got[:2] == [Simple(b"OK"), Int(1)]
            assert isinstance(got[2], Arr) and got[2].items[0].val == b"sync"
            assert seen["queued"] == [cmd(b"replconf", b"ack", b"7")]
            assert seen["buffered"] == 9
            info = info_of(node)
            assert info["read_pump_handbacks"] == 1
            assert info["reply_pump_posts"] == 1    # one pass, one slice
        finally:
            writer.close()
            await app.close()
    asyncio.run(main())


def test_the_malformed_salvage_answers_earlier_replies_first(tmp_path):
    async def main():
        node, app = await boot(tmp_path)
        c = await Client().connect(app.advertised_addr)
        try:
            assert await c.cmd(b"incr", b"n") == Int(1)
            c.writer.write(frames(cmd(b"incr", b"n"), cmd(b"incr", b"n")) +
                           b"!bogus\r\n")
            await c.writer.drain()
            got = parse_all(await read_to_eof(c.reader))
            assert got[:2] == [Int(2), Int(3)]
            assert len(got) == 3 and isinstance(got[2], Err)
            await wait_for(lambda: not app.client_conns)
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("state", ["hello3", "tracking"])
def test_state_takes_the_connection_to_its_transport(tmp_path, state):
    """The commands that give the connection its state, a read behind them
    in the same write, and half a frame: the parser's bytes come before
    what the transport reads next, the transport reads from there, and a
    tracked key's invalidation push follows the replies."""
    async def main():
        node, app = await boot(tmp_path)
        c = await Client().connect(app.advertised_addr)
        await wait_for(lambda: app.client_conns)
        mine = app.client_conns[max(app.client_conns)]
        w = await Client().connect(app.advertised_addr)
        try:
            c.writer.write(frames(cmd(b"set", b"a", b"1"),
                                  cmd(b"set", b"b", b"2")))
            assert await read_replies(c, bytearray(), 2) == \
                [Simple(b"OK")] * 2
            assert mine.read_id
            first = [cmd(b"hello", 3)]
            if state == "tracking":
                first.append(cmd(b"client", b"tracking", b"on"))
            tail = frames(cmd(b"get", b"b"))
            c.writer.write(frames(*first, cmd(b"get", b"a")) + tail[:7])
            await c.writer.drain()
            await wait_for(lambda: not mine.read_id)
            c.writer.write(tail[7:] + frames(cmd(b"incr", b"n")))
            await c.writer.drain()
            got = await read_replies(c, bytearray(), len(first) + 3)
            assert got[len(first):] == [Bulk(b"1"), Bulk(b"2"), Int(1)]
            assert not mine.read_id and mine.reply_id
            if state == "tracking":
                assert got[1] == Simple(b"OK")
                assert await w.cmd(b"set", b"a", b"3") == Simple(b"OK")
                push = (await read_replies(c, bytearray(), 1))[0]
                assert b"invalidate" in encode_msg(push)
            info = info_of(node)
            assert info["read_pump_handbacks"] == 1
            assert info["read_transport_reads"] >= 1
        finally:
            await c.close()
            await w.close()
            await app.close()
    asyncio.run(main())


def test_fsync_always_holds_the_next_segment_until_the_group_commit(
        tmp_path):
    async def main():
        node, app = await boot(tmp_path, aof=True, aof_fsync="always",
                               aof_dir=str(tmp_path / "aof"))
        gate = asyncio.Event()
        commits = []
        barrier = node.oplog.ack_barrier

        async def gated():
            commits.append(node.stats.serve_gather_passes)
            await gate.wait()
            await barrier()
        node.oplog.ack_barrier = gated
        c = await Client().connect(app.advertised_addr)
        try:
            c.writer.write(frames(cmd(b"incr", b"n")))
            await c.writer.drain()
            await wait_for(lambda: commits)
            # the next bytes wait in the socket: no take delivers them
            c.writer.write(frames(cmd(b"incr", b"n")))
            await c.writer.drain()
            await asyncio.sleep(0.2)
            assert node.stats.serve_gather_passes == 1
            assert info_of(node)["read_pump_bytes"] == \
                len(frames(cmd(b"incr", b"n")))
            gate.set()
            assert await read_replies(c, bytearray(), 2) == [Int(1), Int(2)]
            assert node.stats.serve_gather_passes == 2
            assert commits == [1, 2]
        finally:
            gate.set()
            await c.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("mode", ["per_command", "shards", "pure"])
def test_paths_without_the_reader_read_through_the_transport(
        tmp_path, monkeypatch, mode):
    kw = {"per_command": {"serve_batch": 1}, "shards": {"serve_shards": 2},
          "pure": {}}[mode]
    if mode == "pure":
        monkeypatch.setenv("CONSTDB_NO_NATIVE", "1")

    async def main():
        node, app = await boot(tmp_path, **kw)
        c = await Client().connect(app.advertised_addr)
        try:
            assert app.read_pump is None
            c.writer.write(frames(cmd(b"set", b"k", b"v"), cmd(b"get", b"k")))
            await c.writer.drain()
            assert await read_replies(c, bytearray(), 2) == \
                [Simple(b"OK"), Bulk(b"v")]
            assert await c.cmd(b"incr", b"n") == Int(1)
            info = info_of(node)
            assert info["read_transport_reads"] >= 2
            assert info["read_pump_bytes"] == info["read_pump_takes"] == 0
            assert not app.client_conns[max(app.client_conns)].read_id
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_descriptor_reuse_never_crosses_connections(tmp_path):
    """A connection sends half a command and leaves; the next one, often
    given the same descriptor number, reads its own reply only."""
    async def main():
        node, app = await boot(tmp_path)
        setup = await Client().connect(app.advertised_addr)
        for i in range(40):
            assert await setup.cmd(b"set", b"k%d" % i, b"v%d" % i) == \
                Simple(b"OK")
        fds = []
        try:
            for i in range(40):
                gone = await Client().connect(app.advertised_addr)
                await wait_for(lambda: len(app.client_conns) == 2)
                gone.writer.write(frames(cmd(b"get", b"k%d" % i))[:6])
                await gone.writer.drain()
                await asyncio.sleep(0.001 * (i % 3))
                gone.writer.transport.abort()
                await wait_for(lambda: len(app.client_conns) == 1)
                c = await Client().connect(app.advertised_addr)
                await wait_for(lambda: len(app.client_conns) == 2)
                fds.append(app.client_conns[max(app.client_conns)]
                           .writer.get_extra_info("socket").fileno())
                c.writer.write(b"*2\r\n")
                await c.writer.drain()
                await asyncio.sleep(0.001 * (i % 2))
                c.writer.write(frames(cmd(b"get", b"k%d" % i))[4:])
                assert await read_replies(c, bytearray(), 1) == \
                    [Bulk(b"v%d" % i)]
                await c.close()
                await wait_for(lambda: len(app.client_conns) == 1)
            assert len(set(fds)) < len(fds)    # numbers were reused
            assert info_of(node)["read_transport_reads"] == 0
        finally:
            await setup.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("serve_batch", [512, 1])
def test_no_reader_thread_outlives_close(tmp_path, serve_batch):
    async def main():
        before = reader_threads()
        node, app = await boot(tmp_path, serve_batch=serve_batch)
        try:
            c = await Client().connect(app.advertised_addr)
            assert await c.cmd(b"incr", b"n") == Int(1)
            await c.close()
            if serve_batch > 1:
                assert reader_threads() == before + 1
            else:   # the per-command loop reads through its transport
                assert app.read_pump is None
                assert reader_threads() == before
        finally:
            await app.close()
        assert reader_threads() == before
    asyncio.run(main())


@pytest.mark.parametrize("native", [True, False])
def test_the_seven_counters_are_in_info_from_boot(tmp_path, monkeypatch,
                                                  native):
    if not native:
        monkeypatch.setenv("CONSTDB_NO_NATIVE", "1")

    async def main():
        node, app = await boot(tmp_path)
        try:
            assert (app.read_pump is not None) == native
            fields = info_of(node)
            assert len(COUNTERS) == 7
            for name in COUNTERS:
                assert fields[name] == 0, name
            assert fields["span_read_take_n"] == 0
        finally:
            await app.close()
    asyncio.run(main())


# ------------------------------------------------------- the native calls


@pytest.fixture
def reader():
    ext = load_ext()
    if ext is None:
        pytest.skip("the extension is not built")
    h = ext.read_new()
    efd = ext.read_start(h)
    yield ext, h, efd
    ext.read_stop(h)


def signalled(efd: int, timeout: float = 5.0) -> bool:
    return bool(select.select([efd], [], [], timeout)[0])


def take_all(ext, h, efd, want: int, timeout: float = 5.0) -> list:
    got = []
    deadline = time.monotonic() + timeout
    while len(got) < want and time.monotonic() < deadline:
        if signalled(efd, 0.05):
            got += ext.read_take(h)
    return got


def test_native_one_segment_in_flight_and_the_end_after_the_bytes(reader):
    ext, h, efd = reader
    a, b = socket.socketpair()
    try:
        rid = ext.read_open(h, b.fileno())
        a.sendall(b"one")
        assert take_all(ext, h, efd, 1) == [(rid, b"one")]
        # in flight: the next bytes stay in the socket until the release
        a.sendall(b"two")
        time.sleep(0.05)
        assert ext.read_take(h) == []
        a.shutdown(socket.SHUT_WR)
        ext.read_release(h, [rid])
        assert take_all(ext, h, efd, 1) == [(rid, b"two")]
        ext.read_release(h, [rid])
        assert take_all(ext, h, efd, 1) == [(rid, None)]
        takes, nbytes, recvs, wakes, _us, backs = ext.read_stats(h)
        assert nbytes == 6 and recvs >= 3 and backs == 0
        assert ext.read_detach(h, rid, False) is None
    finally:
        a.close()
        b.close()


def test_native_detach_hands_back_what_the_reader_holds(reader):
    ext, h, efd = reader
    a, b = socket.socketpair()
    try:
        rid = ext.read_open(h, b.fileno())
        a.sendall(b"held bytes")
        assert signalled(efd)
        # read, not taken: the switch hands them back, in order
        assert ext.read_detach(h, rid, True) == b"held bytes"
        assert ext.read_stats(h)[5] == 1
        # the reader is off it: later bytes are the socket's own
        a.sendall(b"later")
        time.sleep(0.05)
        assert ext.read_take(h) == []
        assert b.recv(16) == b"later"
    finally:
        a.close()
        b.close()


def test_native_descriptor_reuse_reads_only_the_new_socket(reader):
    ext, h, efd = reader
    for i in range(20):
        a, b = socket.socketpair()
        rid = ext.read_open(h, b.fileno())
        a.sendall(b"x%d" % i)
        assert take_all(ext, h, efd, 1) == [(rid, b"x%d" % i)]
        assert ext.read_detach(h, rid, False) is None
        b.close()
        a.close()
    takes = ext.read_stats(h)[0]
    assert takes >= 20
