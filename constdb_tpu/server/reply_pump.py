"""The reply sender, seen from the event loop (native/reply.cpp).

One thread owned by the extension takes the `send` of client replies off
the loop's thread: a pass of the loop hands its connections' replies over
in one call (`post`), and the thread writes them, in order, each to its
connection's own dup of the socket.  The thread never takes the GIL.

A connection is on ONE path at a time (docs/INVARIANTS.md "Reply-path
laws"):

* **the sender** (`ClientConn.on_pump`) — from the accept on, where the
  extension loads and the connection has no RESP3 / tracking state;
* **the transport** — for a connection with RESP3 or CLIENT TRACKING state
  (its invalidation pushes are written by server/tracking.py), and after
  the sender SPILLED: a send that would block hands the unsent bytes back
  through an eventfd, the loop writes them to the transport, and from
  there `writer.drain()`, the high-water mark and the outbuf cap work as
  on any transport.  The connection returns to the sender once the
  transport's buffer is empty (`write`).

A switch to the transport (`to_transport`, `release`) first takes back
everything the sender still holds for the connection, after any send in
flight, and writes it to the transport ahead of what follows.  `release`
also closes the sender's dup: at a SYNC upgrade, a malformed frame, and
the connection's end — so the replies it held leave before the FIN.

Where the extension does not load, nothing here is built and every reply
is a `writer.write`, as it always was."""

from __future__ import annotations

# INFO fields (server/info.py), every one from boot
COUNTERS = ("reply_pump_posts", "reply_pump_bytes", "reply_transport_writes",
            "reply_pump_spills", "reply_pump_wakes", "reply_pump_send_us")


def has_transport_state(client) -> bool:
    """State whose writes go through the transport: RESP3 replies and
    tracking pushes (server/tracking.py _send).  Its reads do too
    (server/read_pump.py)."""
    return bool(client.tracking or client.resp3)


class ReplyPump:
    """One node's reply sender: the extension's thread plus the loop side
    of its hand-back."""

    def __init__(self, ext, stats, overflow) -> None:
        self._ext = ext
        self._h = ext.reply_new()
        self._stats = stats
        # writer -> disconnected?  The outbuf cap (server/io.py
        # _outbuf_overflow), checked where a hand-back lands
        self._overflow = overflow
        self._clients: dict = {}     # connection id -> ClientConn
        self._loop = None
        self._efd = -1

    def start(self, loop) -> None:
        self._efd = self._ext.reply_start(self._h)
        self._loop = loop
        loop.add_reader(self._efd, self._on_spill)

    def close(self) -> None:
        """Stop and join the thread (ServerApp.close, after every
        connection ended)."""
        if self._loop is not None:
            self._loop.remove_reader(self._efd)
            self._loop = None
        self._ext.reply_stop(self._h)

    def counters(self) -> list:
        """[(INFO field, value)] of the sender's own counters."""
        posts, nbytes, wakes, spills, send_us = \
            self._ext.reply_stats(self._h)
        return [("reply_pump_posts", posts), ("reply_pump_bytes", nbytes),
                ("reply_pump_spills", spills), ("reply_pump_wakes", wakes),
                ("reply_pump_send_us", send_us)]

    # ------------------------------------------------------ connections

    def open(self, client, sock) -> None:
        """Put an accepted connection on the sender: the sender dups the
        socket now, so its bytes can never reach a later connection that
        reuses the descriptor's number."""
        rid = self._ext.reply_open(self._h, sock.fileno())
        client.reply_id = rid
        client.on_pump = True
        self._clients[rid] = client

    def post(self, buf, ids, ends) -> None:
        """One pass's replies: buf[ends[i-1]:ends[i]] to connection
        ids[i] (0 skips the span)."""
        self._stats.net_out_bytes += self._ext.reply_post(self._h, buf, ids,
                                                          ends)

    def write(self, client, out) -> None:
        """`out` to the client on whichever path it is on, switching when
        its state asks for it."""
        if client.on_pump:
            if not has_transport_state(client):
                self.post(out, (client.reply_id,), (len(out),))
                return
            self.to_transport(client)
        elif client.reply_id and not has_transport_state(client) and \
                client.writer.transport.get_write_buffer_size() == 0:
            held = self._ext.reply_resume(self._h, client.reply_id)
            if held is None:
                client.on_pump = True
                self.post(out, (client.reply_id,), (len(out),))
                return
            client.writer.write(held)
        self._stats.reply_transport_writes += 1
        self._stats.net_out_bytes += len(out)
        client.writer.write(out)

    def to_transport(self, client) -> None:
        """The connection's writes take its transport from here: what the
        sender still holds for it is written there first."""
        if client.on_pump:
            client.on_pump = False
            self._hand_back(client, self._ext.reply_detach(
                self._h, client.reply_id, False))

    def release(self, client) -> None:
        """The sender lets the connection go (SYNC upgrade, malformed
        frame, the connection's end): what it held is written to the
        transport, and its dup is closed."""
        rid = client.reply_id
        if rid:
            client.reply_id = 0
            client.on_pump = False
            self._clients.pop(rid, None)
            self._hand_back(client, self._ext.reply_detach(self._h, rid,
                                                           True))

    def _hand_back(self, client, held) -> None:
        w = client.writer
        if held is not None and w is not None and \
                not w.transport.is_closing():
            w.write(held)

    def _on_spill(self) -> None:
        """The eventfd: connections whose send would have blocked.  Their
        bytes go to their transports, and they stay there until it
        drains."""
        for rid, held in self._ext.reply_take_spills(self._h):
            client = self._clients.get(rid)
            if client is not None:
                client.on_pump = False
                self._hand_back(client, held)
                if client.writer is not None:
                    self._overflow(client.writer)
