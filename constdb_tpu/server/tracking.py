"""Client-assisted caching: RESP3 invalidation push tracking.

The PR 15 reply cache already computes a precise invalidation stream —
every mutation intake (per-op execute, replicated frames, coalesced
runs, delta/snapshot ingest, oplog replay) names exactly the keys whose
cached replies die.  This module forwards that stream over the wire to
RESP3 clients that opted in (`CLIENT TRACKING on`), so a client-side
near-cache (client/near_cache.py) can serve hot reads with zero server
round-trips while the key is quiet.

Two modes, mirroring Redis 6 server-assisted caching:

  * default: the server remembers which keys each tracked connection
    has READ (note_read — fed by commands.execute and the serve
    planner's read batches) and sends a one-shot invalidation push the
    first time such a key mutates.  The per-connection key set is
    capped (CONSTDB_TRACKING_MAX_KEYS): past the cap the server sends a
    flush-all push and starts the set over — bounded memory, never
    silently stale.
  * BCAST: no per-read bookkeeping; every mutation's key is broadcast
    to every subscriber whose prefix list matches.  The frame for a
    given flush is encoded ONCE per prefix class and shared across all
    subscribers in it through the PR 13 encode-once cache
    (node.wire_cache) — N subscribers cost one encode, like the
    replication fan-out.

Push frames are the RESP3 invalidation shape:

    >2\r\n $10\r\n invalidate\r\n *N\r\n $.. key ...   (keys)
    >2\r\n $10\r\n invalidate\r\n $-1\r\n              (flush-all)

Delivery discipline (docs/INVARIANTS.md "Tracking laws"):

  * invalidate-before-visible: keys are queued at the SAME hook the
    reply cache invalidates from — before the mutation lands — and
    flush under a dual batch/latency bound (CONSTDB_TRACKING_BATCH /
    CONSTDB_TRACKING_LATENCY_MS), like every other hot path.
  * the PR 12 outbuf cap is respected: a tracked connection whose
    write buffer is over CONSTDB_CLIENT_OUTBUF_MAX when a push flush
    fires is demoted to untracked LOUDLY — warning log, the
    tracking_demotions counter — and its transport is aborted, so the
    client observes a disconnect and the reconnect-flush law restores
    correctness.  Invalidation frames never buffer unbounded.
  * a connection's tracking state dies with the connection
    (unsubscribe from server/io.py's finally) — entries a client
    cached are only trustworthy while the connection that filled them
    is live.
"""

from __future__ import annotations

import logging

from ..resp.codec import encode_into
from ..resp.message import Bulk, NIL, Push

log = logging.getLogger("constdb.tracking")

# tracking modes (ClientConn.tracking)
TRACK_OFF = 0
TRACK_DEFAULT = 1
TRACK_BCAST = 2

_INVALIDATE = Bulk(b"invalidate")
_FLUSH_ALL_FRAME = None  # encoded lazily (stable bytes, shared)


def _flush_all_bytes() -> bytes:
    global _FLUSH_ALL_FRAME
    if _FLUSH_ALL_FRAME is None:
        buf = bytearray()
        encode_into(buf, Push([_INVALIDATE, NIL]))
        _FLUSH_ALL_FRAME = bytes(buf)
    return _FLUSH_ALL_FRAME


def _encode_keys_frame(keys) -> bytes:
    """The RESP3 invalidation push frame for a key list."""
    from ..resp.message import Arr
    buf = bytearray()
    encode_into(buf, Push([_INVALIDATE, Arr([Bulk(k) for k in keys])]))
    return bytes(buf)


class ClientConn:
    """Per-connection client state the command layer can see (ExecCtx
    .client): identity for CLIENT ID/LIST, the negotiated protocol
    (HELLO 3), and the tracking subscription.  Owned by server/io.py's
    connection loop; the registry holds references while tracking is
    on."""

    __slots__ = ("cid", "addr", "writer", "resp3", "tracking", "prefixes",
                 "tracked", "pend", "_timer", "created", "reply_id",
                 "on_pump", "read_id")

    def __init__(self, cid: int, addr: str, writer=None, created=0.0):
        self.cid = cid
        self.addr = addr
        self.writer = writer
        self.resp3 = False
        self.tracking = TRACK_OFF
        self.prefixes: tuple = ()
        self.tracked: set = set()   # default-mode keys the server records
        self.pend: dict = {}        # pending invalidation keys (ordered)
        self._timer = None          # armed latency-bound flush handle
        self.created = created
        # the reply sender's id for this connection (0: none), and whether
        # its replies go through the sender now (server/reply_pump.py)
        self.reply_id = 0
        self.on_pump = False
        # the reader's id for this connection while the reader reads it
        # (server/read_pump.py; 0: its transport reads it)
        self.read_id = 0

    def describe(self) -> str:
        mode = {TRACK_OFF: "off", TRACK_DEFAULT: "on",
                TRACK_BCAST: "bcast"}[self.tracking]
        return (f"id={self.cid} addr={self.addr} resp={3 if self.resp3 else 2}"
                f" tracking={mode}")


class TrackingRegistry:
    """Node-level invalidation fan-out to tracked client connections.

    Hot-path cost when nothing subscribes: one attribute test
    (`registry.active`) at each invalidation tap — the same shape as
    the reply cache's own `len(rc)` gate."""

    __slots__ = ("node", "active", "batch", "latency_s", "max_keys",
                 "key_map", "bcast", "clients", "_bseq", "_bpend",
                 "_btimer", "loop")

    def __init__(self, node):
        from ..conf import env_int
        self.node = node
        self.active = False
        self.batch = max(1, env_int("CONSTDB_TRACKING_BATCH", 128))
        self.latency_s = max(
            0, env_int("CONSTDB_TRACKING_LATENCY_MS", 2)) / 1000.0
        self.max_keys = max(1, env_int("CONSTDB_TRACKING_MAX_KEYS", 65536))
        self.key_map: dict = {}    # key -> set of default-mode ClientConn
        self.bcast: set = set()    # BCAST-mode ClientConn
        self.clients: set = set()  # every tracked ClientConn
        self._bseq = 0             # BCAST flush sequence (encode-once key)
        self._bpend: dict = {}     # pending BCAST keys (ordered, deduped)
        self._btimer = None
        self.loop = None           # armed by subscribe (the serving loop)

    # ------------------------------------------------------- subscription

    def subscribe(self, client: ClientConn, bcast: bool = False,
                  prefixes: tuple = ()) -> None:
        """CLIENT TRACKING on: register `client` in the requested mode
        (re-subscribing switches modes and drops the old state)."""
        if client.tracking != TRACK_OFF:
            self.unsubscribe(client)
        client.tracking = TRACK_BCAST if bcast else TRACK_DEFAULT
        client.prefixes = tuple(prefixes)
        self.clients.add(client)
        if bcast:
            self.bcast.add(client)
        if self.loop is None:
            import asyncio
            try:
                self.loop = asyncio.get_running_loop()
            except RuntimeError:
                self.loop = None  # sync tests: latency bound degrades
                #                   to flush-on-batch-bound only
        self.active = True

    def unsubscribe(self, client: ClientConn) -> None:
        """Tracking off / connection closed: drop every trace of the
        subscription (the connection-liveness half of the law)."""
        if client.tracking == TRACK_DEFAULT:
            km = self.key_map
            for key in client.tracked:
                conns = km.get(key)
                if conns is not None:
                    conns.discard(client)
                    if not conns:
                        del km[key]
        client.tracked.clear()
        client.pend.clear()
        if client._timer is not None:
            client._timer.cancel()
            client._timer = None
        client.tracking = TRACK_OFF
        client.prefixes = ()
        self.bcast.discard(client)
        self.clients.discard(client)
        if not self.clients:
            self.active = False
            self._bpend.clear()
            if self._btimer is not None:
                self._btimer.cancel()
                self._btimer = None

    @property
    def n_clients(self) -> int:
        return len(self.clients)

    # --------------------------------------------------------- read taps

    def note_read(self, client, key: bytes) -> None:
        """Default-mode bookkeeping: `client` has read `key`; the first
        mutation of `key` owes it a one-shot invalidation push.  Fed by
        commands.execute (READONLY commands) and the serve read planner
        (every read in a planned batch, cache hits included)."""
        if client is None or client.tracking != TRACK_DEFAULT:
            return
        tracked = client.tracked
        if key in tracked:
            return
        if len(tracked) >= self.max_keys:
            # bounded memory, never silently stale: flush the client's
            # whole near-cache and start the set over
            self._drop_client_keys(client)
            self._send(client, _flush_all_bytes())
            self.node.stats.tracking_invalidations_sent += 1
            return
        tracked.add(key)
        self.key_map.setdefault(key, set()).add(client)

    def _drop_client_keys(self, client) -> None:
        km = self.key_map
        for key in client.tracked:
            conns = km.get(key)
            if conns is not None:
                conns.discard(client)
                if not conns:
                    del km[key]
        client.tracked.clear()

    # -------------------------------------------------- invalidation taps

    def invalidate_key(self, key: bytes) -> None:
        """One mutated key — queue its push on every owed connection.
        Called from the same seams the reply cache invalidates at,
        BEFORE the mutation lands (invalidate-before-visible)."""
        conns = self.key_map.pop(key, None)
        if conns:
            for c in conns:
                c.tracked.discard(key)
                self._queue(c, key)
        if self.bcast:
            bp = self._bpend
            if key not in bp:
                bp[key] = None
                if len(bp) >= self.batch:
                    self._flush_bcast()
                elif self._btimer is None and self.loop is not None:
                    self._btimer = self.loop.call_later(
                        self.latency_s, self._flush_bcast)

    def invalidate_keys(self, keys) -> None:
        for key in keys:
            self.invalidate_key(bytes(key))

    def flush_all(self) -> None:
        """State-wipe events (full resync, slot import reset): every
        tracked client's near-cache is wholesale untrustworthy."""
        frame = _flush_all_bytes()
        st = self.node.stats
        for c in list(self.clients):
            c.pend.clear()
            if c.tracking == TRACK_DEFAULT:
                self._drop_client_keys(c)
            if self._send(c, frame):
                st.tracking_invalidations_sent += 1
        self._bpend.clear()

    def slots_lost(self, slots) -> None:
        """Cluster slot migration moved ownership away from this node
        (cluster/slots.py adopt hook): every tracked key hashing into a
        moved slot must be invalidated — subsequent writes land on the
        new owner and this node will never see them, so the one-shot
        promise could otherwise never be kept.  BCAST subscribers get a
        flush-all (their subscription is prefix-, not slot-scoped)."""
        if not self.active:
            return
        from ..cluster.slots import slot_of
        moved = [k for k in self.key_map if slot_of(k) in slots]
        for k in moved:
            # default-mode conns only: BCAST gets one flush-all below,
            # not a per-key frame AND a flush-all
            conns = self.key_map.pop(k, None)
            if conns:
                for c in conns:
                    c.tracked.discard(k)
                    self._queue(c, k)
        if self.bcast:
            frame = _flush_all_bytes()
            st = self.node.stats
            for c in list(self.bcast):
                if self._send(c, frame):
                    st.tracking_invalidations_sent += 1

    # ------------------------------------------------------ flush plumbing

    def _queue(self, client, key: bytes) -> None:
        pend = client.pend
        if key in pend:
            return
        pend[key] = None
        if len(pend) >= self.batch:
            self._flush_conn(client)
        elif client._timer is None and self.loop is not None:
            client._timer = self.loop.call_later(
                self.latency_s, self._flush_conn, client)

    def _flush_conn(self, client) -> None:
        if client._timer is not None:
            client._timer.cancel()
            client._timer = None
        pend = client.pend
        if not pend or client.tracking == TRACK_OFF:
            pend.clear()
            return
        keys = list(pend)
        pend.clear()
        if self._send(client, _encode_keys_frame(keys)):
            st = self.node.stats
            st.tracking_invalidations_sent += len(keys)
            st.tracking_pushes += 1

    def _flush_bcast(self) -> None:
        if self._btimer is not None:
            self._btimer.cancel()
            self._btimer = None
        bp = self._bpend
        if not bp or not self.bcast:
            bp.clear()
            return
        keys = list(bp)
        bp.clear()
        seq = self._bseq
        self._bseq = seq + 1
        # group subscribers by prefix class: every subscriber in a class
        # receives byte-identical frames, so the flush encodes ONCE per
        # class through the encode-once cache (first subscriber encodes
        # and publishes; the rest splice the published bytes)
        groups: dict = {}
        for c in self.bcast:
            groups.setdefault(c.prefixes, []).append(c)
        wc = self.node.wire_cache
        st = self.node.stats
        for prefixes, conns in groups.items():
            if prefixes:
                sel = [k for k in keys
                       if any(k.startswith(p) for p in prefixes)]
                if not sel:
                    continue
            else:
                sel = keys
            caps = ("tracking",) + prefixes
            payload = None
            for c in conns:
                if payload is None:
                    ent = wc.get(caps, seq)
                    if ent is not None:
                        payload = ent.payload
                    else:
                        payload = _encode_keys_frame(sel)
                        wc.put(caps, seq, seq + 1, payload,
                               readers=len(conns) - 1)
                if self._send(c, payload):
                    st.tracking_invalidations_sent += len(sel)
                    st.tracking_pushes += 1

    def _send(self, client, payload: bytes) -> bool:
        """Write one push frame to the connection, respecting the PR 12
        outbuf cap: an over-cap tracker demotes to untracked loudly and
        its transport aborts (the client sees a disconnect; the
        reconnect-flush law restores correctness).  Returns True iff the
        frame was written."""
        w = client.writer
        if w is None:
            return False
        tr = w.transport
        if tr.is_closing():
            return False
        app = self.node.app
        cap = getattr(app, "client_outbuf_max", 0) if app is not None else 0
        if cap and tr.get_write_buffer_size() > cap:
            self.unsubscribe(client)
            self.node.stats.tracking_demotions += 1
            log.warning(
                "tracked client %s over the outbuf cap (%d > %d): "
                "demoting to untracked and aborting the connection",
                client.describe(), tr.get_write_buffer_size(), cap)
            tr.abort()
            return False
        if client.on_pump:
            # the push must not overtake replies the sender still holds
            self.node.app.reply_pump.to_transport(client)
        try:
            w.write(payload)
        except (ConnectionError, RuntimeError):
            return False
        return True
