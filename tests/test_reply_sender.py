"""The reply sender (native/reply.cpp, server/reply_pump.py) over real
sockets against a CPU-engine node (docs/INVARIANTS.md "Reply-path laws").

Pinned here:

  * each connection reads its replies in order across passes, for a
    pipelined client and for fifty depth-1 clients, all through the sender;
  * a client that stops reading spills: the sender hands the unsent bytes
    back, the transport writes them in order, `writer.drain()` parks the
    connection, the outbuf cap disconnects it, and the counters say so;
  * connections that come and go, reusing descriptor numbers, never read
    another connection's reply;
  * a close the server starts (a malformed frame, the node's shutdown)
    still delivers what the sender held before the EOF;
  * replies pipelined before a SYNC leave before the handshake reply;
  * a RESP3 / tracking connection leaves the sender behind what it held,
    and its pushes and replies stay in order on the transport;
  * the pure tier (no extension) writes through the transport, byte for
    byte what the sender writes;
  * no sender thread outlives `ServerApp.close()`;
  * the six counters are in INFO from boot.
"""

import asyncio
import os
import socket

import pytest

from constdb_tpu.resp.codec import RespParser, encode_msg
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, Simple
from constdb_tpu.server import info as info_mod
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.reply_pump import COUNTERS

from cluster_util import FAST, Client
from test_serve_coalesce import cmd, read_replies

BIG = 256 << 10


def info_of(node: Node) -> dict:
    out: list = []
    info_mod._section_stats(node, out)
    return dict(out)


def sender_threads() -> int:
    """Threads of this process named as the extension names its sender."""
    n = 0
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                n += f.read().strip() == "cst-reply"
        except OSError:
            pass    # the thread ended while we looked
    return n


async def boot(tmp_path, **kw):
    node = Node(node_id=1)
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=str(tmp_path), **FAST, **kw)
    return node, app


def value(i: int, size: int = BIG) -> bytes:
    """A value that names its key in every byte run."""
    return (b"<%d>" % i * (size // 4 + 1))[:size]


def server_side(app, n_before: int):
    """The ClientConn of the connection accepted after `n_before`."""
    return app.client_conns[max(app.client_conns)] \
        if max(app.client_conns, default=0) > n_before else None


async def wait_for(cond, timeout: float = 10.0) -> None:
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while not cond():
        assert loop.time() < deadline, "condition never held"
        await asyncio.sleep(0.005)


def small_sndbuf(conn) -> None:
    """Make the server's side of a connection (its ClientConn) block
    early, so the sender spills, or still holds bytes, on replies of a
    few hundred kB."""
    sock = conn.writer.get_extra_info("socket")
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)


async def connect_small(app) -> Client:
    """A client whose receive buffer is small from the handshake on."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    sock.setblocking(False)
    await asyncio.get_running_loop().sock_connect(sock, ("127.0.0.1",
                                                         app.port))
    c = Client()
    c.reader, c.writer = await asyncio.open_connection(sock=sock)
    return c


async def read_to_eof(reader) -> bytes:
    data = bytearray()
    while True:
        try:
            got = await asyncio.wait_for(reader.read(1 << 16), 10.0)
        except ConnectionResetError:    # an abort may reset
            return bytes(data)
        if not got:
            return bytes(data)
        data += got


def parse_all(data: bytes) -> list:
    p = RespParser()
    p.feed(data)
    return p.drain()


@pytest.mark.parametrize("n_conns,depth", [(1, 32), (50, 1)])
def test_each_connection_reads_its_replies_in_order(tmp_path, n_conns,
                                                     depth):
    rounds = 40 if n_conns == 1 else 12

    async def main():
        node, app = await boot(tmp_path)
        conns = [await Client().connect(app.advertised_addr)
                 for _ in range(n_conns)]
        raw = [bytearray() for _ in conns]
        try:
            async def loop_of(i: int, c) -> None:
                seen = []
                for _ in range(rounds):
                    c.writer.write(b"".join(
                        encode_msg(cmd(b"incr", b"n%d" % i))
                        for _ in range(depth)))
                    await c.writer.drain()
                    seen += await read_replies(c, raw[i], depth)
                assert seen == [Int(k + 1) for k in range(rounds * depth)]

            await asyncio.gather(*(loop_of(i, c) for i, c in enumerate(conns)))
            info = info_of(node)
            # every reply went through the sender, none through a transport
            assert info["reply_transport_writes"] == 0
            assert info["reply_pump_posts"] >= n_conns * rounds
            assert info["reply_pump_bytes"] == sum(len(r) for r in raw)
            assert info["reply_pump_spills"] == 0
            assert info["reply_pump_wakes"] >= 1
            if n_conns > 1:    # connections met in passes
                assert node.stats.serve_gather_msgs > \
                    node.stats.serve_gather_passes
        finally:
            for c in conns:
                await c.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("capped", [False, True])
def test_a_stalled_reader_spills_in_order_and_parks(tmp_path, capped):
    n_keys, rounds = 16, 4

    async def main():
        node, app = await boot(
            tmp_path, client_outbuf_max=(BIG if capped else 128 << 20))
        c = await connect_small(app)
        await wait_for(lambda: app.client_conns)
        mine = server_side(app, 0)
        small_sndbuf(mine)
        raw = bytearray()
        try:
            c.writer.write(b"".join(encode_msg(cmd(b"set", b"k%d" % i,
                                                   value(i)))
                                    for i in range(n_keys)))
            await c.writer.drain()
            assert await read_replies(c, raw, n_keys) == \
                [Simple(b"OK")] * n_keys
            assert mine.on_pump
            # pipeline 64 GETs of 256 kB and stop reading
            gets = b"".join(encode_msg(cmd(b"get", b"k%d" % i))
                            for i in range(n_keys))
            for _ in range(rounds):
                c.writer.write(gets)
            await c.writer.drain()
            await wait_for(lambda: info_of(node)["reply_pump_spills"] >= 1)
            if capped:
                # the hand-back passed the cap: disconnected loudly
                await wait_for(
                    lambda: node.stats.client_outbuf_disconnects == 1)
                data = await read_to_eof(c.reader)
                got = parse_all(data)
                assert got == [Bulk(value(i % n_keys))
                               for i in range(len(got))]
                assert len(got) < n_keys * rounds
                return
            await wait_for(lambda: not mine.on_pump)
            tr = mine.writer.transport
            await wait_for(lambda: tr.get_write_buffer_size() > (1 << 18))
            # a reply after the spill takes the transport, and the task
            # parks in drain() behind the high-water mark: of two more
            # GETs it reads at most the first
            for key in (b"k0", b"k1"):
                c.writer.write(encode_msg(cmd(b"get", key)))
                await c.writer.drain()
                await asyncio.sleep(0.15)
            assert node.stats.serve_gather_msgs <= n_keys * (rounds + 1) + 1
            # the client reads again: every reply, in order
            n = n_keys * rounds + 2
            got = await read_replies(c, bytearray(), n)
            assert got == [Bulk(value(i % n_keys)) for i in range(n)]
            # a drained transport gives the connection back to the sender
            await wait_for(lambda: tr.get_write_buffer_size() == 0)
            posts = info_of(node)["reply_pump_posts"]
            assert await c.cmd(b"incr", b"n") == Int(1)
            assert mine.on_pump
            assert info_of(node)["reply_pump_posts"] == posts + 1
            assert info_of(node)["reply_transport_writes"] >= 1
            assert node.stats.client_outbuf_disconnects == 0
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_descriptor_reuse_never_crosses_connections(tmp_path):
    """A connection asks for 256 kB and leaves at once, before reading;
    the next connection, often given the same descriptor number, must
    read its own reply and nothing else."""
    async def main():
        node, app = await boot(tmp_path)
        setup = await Client().connect(app.advertised_addr)
        assert await setup.cmd(b"set", b"big", value(7)) == Simple(b"OK")
        for i in range(60):
            assert await setup.cmd(b"set", b"k%d" % i, b"v%d" % i) == \
                Simple(b"OK")
        fds = []
        try:
            for i in range(60):
                gone = await connect_small(app)
                await wait_for(lambda: len(app.client_conns) == 2)
                small_sndbuf(server_side(app, 0))
                gone.writer.write(encode_msg(cmd(b"get", b"big")))
                await gone.writer.drain()
                await asyncio.sleep(0.001 * (i % 3))
                gone.writer.transport.abort()
                await wait_for(lambda: len(app.client_conns) == 1)
                c = await Client().connect(app.advertised_addr)
                await wait_for(lambda: len(app.client_conns) == 2)
                fds.append(server_side(app, 0).writer.get_extra_info(
                    "socket").fileno())
                assert await c.cmd(b"get", b"k%d" % i) == Bulk(b"v%d" % i)
                await c.close()
                await wait_for(lambda: len(app.client_conns) == 1)
            assert len(set(fds)) < len(fds)    # numbers were reused
            assert info_of(node)["reply_pump_posts"] >= 120
        finally:
            await setup.close()
            await app.close()
    asyncio.run(main())


@pytest.mark.parametrize("how", ["malformed", "shutdown"])
def test_a_close_the_server_starts_delivers_what_the_sender_held(tmp_path,
                                                                  how):
    async def main():
        node, app = await boot(tmp_path)
        c = await connect_small(app)
        await wait_for(lambda: app.client_conns)
        mine = server_side(app, 0)
        try:
            assert await c.cmd(b"set", b"big", value(3, 4 * BIG)) == \
                Simple(b"OK")
            small_sndbuf(mine)
            if how == "malformed":
                c.writer.write(encode_msg(cmd(b"get", b"big")))
                await c.writer.drain()
                await wait_for(lambda: info_of(node)["reply_pump_posts"] == 2)
                c.writer.write(encode_msg(cmd(b"incr", b"n")) +
                               b"!bogus\r\n")
                await c.writer.drain()
                got = parse_all(await read_to_eof(c.reader))
                assert got[:2] == [Bulk(value(3, 4 * BIG)), Int(1)]
                assert len(got) == 3 and isinstance(got[2], Err)
                return
            c.writer.write(encode_msg(cmd(b"get", b"big")) +
                           encode_msg(cmd(b"incr", b"n")))
            await c.writer.drain()
            await wait_for(lambda: info_of(node)["reply_pump_posts"] == 2)
            closing = asyncio.ensure_future(app.close())
            got = parse_all(await read_to_eof(c.reader))
            assert got == [Bulk(value(3, 4 * BIG)), Int(1)]
            await asyncio.wait_for(closing, 10.0)
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_replies_before_a_sync_leave_before_the_handshake(tmp_path):
    async def main():
        node, app = await boot(tmp_path)
        c = await connect_small(app)
        await wait_for(lambda: app.client_conns)
        try:
            assert await c.cmd(b"set", b"big", value(5)) == Simple(b"OK")
            small_sndbuf(server_side(app, 0))
            sync = Arr([Bulk(b"sync"), Int(0), Int(99), Bulk(b"nx"),
                        Bulk(b"127.9.9.9:19"), Int(0), Int(0)])
            c.writer.write(encode_msg(cmd(b"get", b"big")) +
                           encode_msg(cmd(b"incr", b"n")) + encode_msg(sync))
            await c.writer.drain()
            got = await read_replies(c, bytearray(), 3)
            assert got[:2] == [Bulk(value(5)), Int(1)]
            assert isinstance(got[2], Arr) and got[2].items[0].val == b"sync"
            assert info_of(node)["reply_pump_posts"] == 2
        finally:
            await c.close()
            await app.close()
    asyncio.run(main())


def test_a_tracking_connection_leaves_the_sender_in_order(tmp_path):
    """A reply the sender still holds leaves before HELLO 3's; from there
    the connection's replies and invalidation pushes share its transport,
    in order."""
    async def main():
        node, app = await boot(tmp_path)
        t = await connect_small(app)
        await wait_for(lambda: app.client_conns)
        mine = server_side(app, 0)
        w = await Client().connect(app.advertised_addr)
        try:
            assert await w.cmd(b"set", b"big", value(9)) == Simple(b"OK")
            small_sndbuf(mine)
            t.writer.write(encode_msg(cmd(b"get", b"big")))
            await t.writer.drain()
            await wait_for(lambda: info_of(node)["reply_pump_posts"] == 2)
            for c in (cmd(b"hello", 3), cmd(b"client", b"tracking", b"on"),
                      cmd(b"get", b"k")):
                t.writer.write(encode_msg(c))
                await t.writer.drain()
            got = await read_replies(t, bytearray(), 4)
            assert got[0] == Bulk(value(9))
            assert isinstance(got[1], Arr) and got[2] == Simple(b"OK")
            assert not mine.on_pump and mine.reply_id
            before = info_of(node)["reply_transport_writes"]
            assert await w.cmd(b"set", b"k", b"v1") == Simple(b"OK")
            push = (await read_replies(t, bytearray(), 1))[0]
            assert b"invalidate" in encode_msg(push)
            t.writer.write(encode_msg(cmd(b"get", b"k")))
            await t.writer.drain()
            assert await read_replies(t, bytearray(), 1) == [Bulk(b"v1")]
            assert not mine.on_pump
            assert info_of(node)["reply_transport_writes"] == before + 1
        finally:
            await t.close()
            await w.close()
            await app.close()
    asyncio.run(main())


SCRIPT = [[cmd(b"set", b"a", b"1"), cmd(b"incr", b"n"), cmd(b"get", b"a")],
          [cmd(b"sadd", b"s", b"x", b"y"), cmd(b"smembers", b"s")],
          [cmd(b"get", b"missing")], [cmd(b"hset", b"h", b"f", b"v"),
                                      cmd(b"hgetall", b"h"),
                                      cmd(b"incr", b"a")]]


async def run_script(tmp_path) -> tuple:
    node, app = await boot(tmp_path)
    c = await Client().connect(app.advertised_addr)
    raw = bytearray()
    try:
        for chunk in SCRIPT:
            c.writer.write(b"".join(encode_msg(m) for m in chunk))
            await c.writer.drain()
            await read_replies(c, raw, len(chunk))
        return bytes(raw), info_of(node), app.reply_pump
    finally:
        await c.close()
        await app.close()


def test_the_pure_tier_writes_as_before(tmp_path, monkeypatch):
    with_sender = asyncio.run(run_script(tmp_path / "a"))
    monkeypatch.setenv("CONSTDB_NO_NATIVE", "1")
    pure = asyncio.run(run_script(tmp_path / "b"))
    assert with_sender[2] is not None and pure[2] is None
    assert pure[0] == with_sender[0]
    # one transport write a chunk, as before; nothing through a sender
    assert pure[1]["reply_transport_writes"] == len(SCRIPT)
    assert with_sender[1]["reply_transport_writes"] == 0
    assert with_sender[1]["reply_pump_posts"] == len(SCRIPT)
    assert with_sender[1]["reply_pump_bytes"] == len(with_sender[0])
    for name in COUNTERS:
        if name != "reply_transport_writes":
            assert pure[1][name] == 0


@pytest.mark.parametrize("serve_batch", [512, 1])
def test_no_sender_thread_outlives_close(tmp_path, serve_batch):
    async def main():
        before = sender_threads()
        node, app = await boot(tmp_path, serve_batch=serve_batch)
        try:
            c = await Client().connect(app.advertised_addr)
            assert await c.cmd(b"incr", b"n") == Int(1)
            await c.close()
            if serve_batch > 1:
                assert sender_threads() == before + 1
            else:   # the per-command loop writes through its transport
                assert app.reply_pump is None
                assert sender_threads() == before
        finally:
            await app.close()
        assert sender_threads() == before
    asyncio.run(main())


@pytest.mark.parametrize("native", [True, False])
def test_the_six_counters_are_in_info_from_boot(tmp_path, monkeypatch,
                                                native):
    if not native:
        monkeypatch.setenv("CONSTDB_NO_NATIVE", "1")

    async def main():
        node, app = await boot(tmp_path)
        try:
            assert (app.reply_pump is not None) == native
            c = await Client().connect(app.advertised_addr)
            text = (await c.cmd(b"info")).val.decode()
            await c.close()
            fields = dict(line.split(":", 1) for line in text.splitlines()
                          if ":" in line)
            assert len(COUNTERS) == 6
            for name in COUNTERS:
                assert fields[name] == "0", name
        finally:
            await app.close()
    asyncio.run(main())
