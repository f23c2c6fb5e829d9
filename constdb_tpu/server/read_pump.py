"""The client reader, seen from the event loop (native/read.cpp).

One thread owned by the extension takes the `recv` of gathered client
connections off the loop's thread.  It reads each connection's own dup of
the socket, and signals one eventfd when bytes wait.  The loop's callback
(`_on_ready`) takes what every connection delivered in ONE call, runs each
connection's parse under the `intake` stage, and appends the segments to
the loop-pass gather (server/io.py _PassGather) directly: no task step, no
future and no `call_soon` per connection.  Then it runs the pass.

A connection is read on ONE side at a time (docs/INVARIANTS.md "Read-path
laws"):

* **the reader** (`ClientConn.read_id`) — from the accept on, where the
  extension loads, the node gathers (serve_batch > 1, one shard) and the
  connection has no RESP3 / tracking state.  Its asyncio transport is
  paused from `connection_made` on (`ClientProtocol`), so it never reads.
* **the transport** — for every other connection, and from the moment a
  connection on the reader gains such state, sends `SYNC` or a malformed
  frame: `leave` takes it off the reader, closes the reader's dup and
  hands back the bytes the reader held, which are parsed ahead of
  anything the transport reads next.  The switch is one-way.

The connection's task stays for what the pass does not do: it parks in
`wait` while its connection is on the reader, and wakes with what the
callback hands it — a segment that needs its own path (the messages
parsed, as its own read would have), replies for its transport, the end of
the stream, or a failed pass.

One segment is in flight per connection: the reader reads a connection
again only once the loop releases it (`release`), after the pass that held
its bytes has handed their replies over (under `fsync=always`, after the
group commit).

Where the extension does not load, or the node does not gather, nothing
here is built and every client connection is read by its transport."""

from __future__ import annotations

import asyncio
from collections import deque

# INFO fields (server/info.py), every one from boot
COUNTERS = ("read_pump_takes", "read_pump_bytes", "read_pump_recvs",
            "read_pump_wakes", "read_pump_recv_us", "read_pump_handbacks",
            "read_transport_reads")


class ClientProtocol(asyncio.StreamReaderProtocol):
    """A client connection's protocol: the transport starts paused, and the
    connection's task resumes it unless the reader takes the connection;
    the loss of the transport wakes a task parked on the reader."""

    def __init__(self, reader, cb, loop) -> None:
        super().__init__(reader, cb, loop=loop)
        self.on_lost = None

    def connection_made(self, transport) -> None:
        # before the transport's first read is scheduled to run
        transport.pause_reading()
        super().connection_made(transport)

    def connection_lost(self, exc) -> None:
        super().connection_lost(exc)
        if self.on_lost is not None:
            self.on_lost()


class _Conn:
    __slots__ = ("client", "parser", "items", "waiter")

    def __init__(self, client, parser) -> None:
        self.client = client
        self.parser = parser
        self.items: deque = deque()
        self.waiter = None


class ReadPump:
    """One node's reader: the extension's thread plus the loop side of
    its take."""

    def __init__(self, ext, gather, stages) -> None:
        self._ext = ext
        self._h = ext.read_new()
        self._gather = gather        # server/io.py _PassGather
        self._stage = stages.stage
        self._conns: dict = {}       # connection id -> _Conn
        self._loop = None
        self._efd = -1

    def start(self, loop) -> None:
        self._efd = self._ext.read_start(self._h)
        self._loop = loop
        loop.add_reader(self._efd, self._on_ready)

    def close(self) -> None:
        """Stop and join the thread (ServerApp.close, after every
        connection ended)."""
        if self._loop is not None:
            self._loop.remove_reader(self._efd)
            self._loop = None
        self._ext.read_stop(self._h)

    def counters(self) -> list:
        """[(INFO field, value)] of the reader's own counters."""
        return list(zip(COUNTERS, self._ext.read_stats(self._h)))

    # ------------------------------------------------------ connections

    def open(self, client, sock, parser) -> None:
        """Put an accepted connection on the reader: the reader dups the
        socket now, so it never reads a later connection that reuses the
        descriptor's number."""
        rid = self._ext.read_open(self._h, sock.fileno())
        client.read_id = rid
        self._conns[rid] = _Conn(client, parser)

    async def wait(self, client):
        """What a pass left to the connection's task: `(held, msgs, err,
        out)` — a segment's parse for its own path and `out` replies for
        its transport — or None at the end of the stream.  Raises what
        failed the pass."""
        c = self._conns[client.read_id]
        if not c.items:
            c.waiter = self._loop.create_future()
            try:
                await c.waiter
            finally:
                c.waiter = None
        item = c.items.popleft()
        if isinstance(item, BaseException):
            raise item
        return item

    def hand(self, client, item) -> None:
        """Wake the connection's task with `item` (see `wait`)."""
        c = self._conns.get(client.read_id)
        if c is not None:
            c.items.append(item)
            if c.waiter is not None and not c.waiter.done():
                c.waiter.set_result(None)

    def release(self, ids) -> None:
        """The passes that held these connections' bytes handed their
        replies over: the reader reads them again."""
        self._ext.read_release(self._h, ids)

    def leave(self, client) -> bytes:
        """The connection's transport reads it from here: -> the bytes the
        reader held, which come before anything the transport reads."""
        return self._detach(client, True)

    def close_conn(self, client) -> None:
        """The connection's end: the reader closes its dup."""
        self._detach(client, False)

    def _detach(self, client, handback: bool) -> bytes:
        rid = client.read_id
        if not rid:
            return b""
        client.read_id = 0
        del self._conns[rid]
        return self._ext.read_detach(self._h, rid, handback) or b""

    # ------------------------------------------------------------ the take

    def _on_ready(self) -> None:
        """The eventfd: every connection that delivered since the last
        take joins this pass, in take order, or wakes its task; then the
        pass runs."""
        gather = self._gather
        free = []
        with self._stage("read_take"):
            for rid, data in self._ext.read_take(self._h):
                c = self._conns.get(rid)
                if c is None:
                    continue
                if data is None:            # the end of the stream
                    self.hand(c.client, None)
                    continue
                try:
                    if not gather.join(c.client, c.parser, data):
                        free.append(rid)    # a frame still incomplete
                except Exception as e:  # noqa: BLE001 - its task raises it
                    self.hand(c.client, e)
            if free:
                self.release(free)
            gather.run_pending()
