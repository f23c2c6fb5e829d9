"""Per-peer replication link: dial/adopt, sync handshake, pull+push loops.

Capability parity with the reference's `Replica` link + `Puller`/`Pusher`
state machines (reference src/replica/replica.rs:155-359, pull.rs, push.rs),
redesigned for one asyncio loop instead of tokio IO threads + main thread:
the loop IS the single-writer exec thread, so apply/push steps simply run
inline between awaits.

Wire protocol (RESP frames on one TCP stream, symmetric after handshake):
  dialer:   *[sync, 0, node_id, alias, my_addr, resume_uuid, caps]
  acceptor: *[sync, 1, node_id, alias, my_addr, resume_uuid, caps]
  (`caps` is a capability bitmask — CAP_* below; pre-capability peers
  send 6-item frames and parse as caps=0)
  then each side concurrently pushes its own stream and pulls the peer's:
    *[fullsync, size, repl_last_uuid]  + `size` raw snapshot bytes
    *[partsync]
    *[replicate, origin_nodeid, prev_uuid, uuid, cmd, args...]
    *[replbatch, origin_nodeid, first_prev_uuid, last_uuid, n, payload]
      — a RUN of n consecutive encodable ops, group-encoded once into a
      columnar payload (replica/wire.py); only sent to peers that
      advertised CAP_BATCH_STREAM, under the CONSTDB_WIRE_BATCH /
      CONSTDB_WIRE_LATENCY_MS dual bound.  Non-encodable ops
      (membership, key-scoped sweeps, malformed) break runs and ship as
      ordinary per-frame barriers; CONSTDB_WIRE_BATCH=1 degenerates to
      the byte-exact per-frame stream, as does any peer without the bit.
    *[replack, uuid, now_ms]
  delta anti-entropy (both peers advertise CAP_DELTA_SYNC; pusher-driven):
    *[digest, token, 0, fanout, leaves, rollup]       per-shard rollups
    *[digestack, token, 0, shard_ids]                 puller's mismatches
    *[digest, token, 1, fanout, leaves, shard_ids, leaf_digests]
    *[digestack, token, 1, bucket_ids]
    *[deltasync, size, repl_last_uuid, n_buckets] + `size` bytes — a
      snapshot-FORMAT stream holding only the divergent buckets' state

Sync decision (reference push.rs:91-111): partial iff the peer's resume
uuid is still gap-free in my repl_log; re-checked every round AND before
every frame, so a pusher that falls off its own ring mid-stream recovers on
the SAME connection instead of shipping a gapped frame and paying a
teardown + redial (the reference leaves this case as a TODO —
pull.rs:167-172; regression-tested in tests/test_link_pushloop.py).

Off-ring recovery is digest-driven when both peers allow it (`_send_delta`,
store/digest.py): instead of re-shipping the whole keyspace, pusher and
puller exchange a two-level digest over the crc32 shard partition —
per-shard rollups first, per-key-range leaf digests for shards that
mismatch — and only the divergent buckets stream, as a snapshot-format
delta applied through the same coalesced merge path.  Resync cost becomes
O(divergence) instead of O(keyspace).  The full snapshot remains the
fallback for: peers without CAP_DELTA_SYNC (they get the exact pre-delta
byte stream), state-clearing resyncs (needs_full → FULLSYNC reset), excess
divergence (CONSTDB_DELTA_MAX_DIVERGENCE), and any failed/timed-out
negotiation.

Connection ownership: one link per peer address.  The link dials when it
has no live connection; an inbound SYNC for the same address *adopts* its
connection into the link, closing any previous one.  Replication is
idempotent (watermark dup-skip), so a brief double-connection race is
harmless.
"""

from __future__ import annotations

import asyncio
import logging
import os
from typing import Optional, TYPE_CHECKING

import numpy as np

from ..errors import (CstError, InvalidSnapshot, InvalidSnapshotChecksum,
                      ReplicateCommandsLost)
from ..persist.snapshot import SectionDemux, batch_chunks
from ..resp.codec import RespParser, encode_into, encode_msg, make_parser
from ..resp.message import Arr, Bulk, Int, as_bytes, as_int
from ..server.commands import COLUMNAR_ENCODERS
from ..server.events import (EVENT_PULL_LANDED, EVENT_REPLICA_ACKED,
                             EVENT_REPLICATED)
from ..utils.hlc import now_ms
from . import wire
from .manager import ReplicaMeta

if TYPE_CHECKING:
    from ..server.io import ServerApp

log = logging.getLogger(__name__)

SYNC = b"sync"
FULLSYNC = b"fullsync"
PARTSYNC = b"partsync"
REPLICATE = b"replicate"
REPLBATCH = b"replbatch"
REPLACK = b"replack"
DIGEST = b"digest"
DIGESTACK = b"digestack"
DELTASYNC = b"deltasync"
CLUSTERTAB = b"clustertab"

# Handshake capability bits: items[6] of BOTH sync frames (dialer and
# reply).  A pre-capability peer sends 6-item frames and parses as 0 —
# absence is tolerated, never assumed to mean support (ADVICE.md round
# 5: the FULLSYNC reset flag silently downgraded on mixed-version
# meshes, recreating exactly the resurrection scenario it prevents).
CAP_FULLSYNC_RESET = 1   # honors FULLSYNC's 4th (state-wipe) field
CAP_DELTA_SYNC = 2       # answers digest frames / applies deltasync
CAP_BATCH_STREAM = 4     # decodes REPLBATCH columnar run frames
CAP_COMPRESS = 8         # validates the chunked compression framing
#                          (utils/compressio.py): REPLBATCH payloads
#                          over the floor + FULLSYNC/DELTASYNC windows
CAP_CLUSTER = 16         # decodes CLUSTERTAB slot-table gossip frames
#                          (cluster/slots.py).  Advertised ONLY when
#                          cluster mode is on — deliberately outside
#                          MY_CAPS, so a CONSTDB_CLUSTER=0 node (and
#                          every stream to/from a legacy peer) stays
#                          byte-exact pre-cluster (tests/test_cluster.py
#                          pins the stream)
MY_CAPS = CAP_FULLSYNC_RESET | CAP_DELTA_SYNC | CAP_BATCH_STREAM \
    | CAP_COMPRESS


def my_caps(app, meta=None) -> int:
    """The capability bitmask this node advertises in SYNC handshakes.
    CONSTDB_DELTA_SYNC=0 removes CAP_DELTA_SYNC so the kill switch
    disables BOTH legs: we never initiate deltas (push-loop gate) and
    conforming peers never ask us digest questions (no capability), so
    the node pays no responder-side digest folds either.
    CAP_BATCH_STREAM follows the same discipline — CONSTDB_WIRE_BATCH=1
    stops both sending batches (push-loop gate) and inviting them — and
    is additionally withheld when this node cannot or must not receive
    them: a shard-per-core receiver applies per-key inside the workers
    (server/serve_shards.py ShardApplier), CONSTDB_APPLY_BATCH=1 pins
    the whole replication intake to the exact per-frame apply path (a
    REPLBATCH would route through the columnar merge engine the pin
    exists to bypass), and a peer that once shipped a malformed payload
    is pinned to per-frame delivery (`meta.batch_wire_off`,
    replica/coalesce.py apply_wire_batch).
    CAP_COMPRESS follows the same two-leg discipline —
    CONSTDB_WIRE_COMPRESS=0 stops both compressing outbound AND
    inviting compressed frames — and is withheld per-peer after a
    malformed compressed frame (`meta.compress_wire_off`), so the
    redelivery window arrives plain."""
    caps = MY_CAPS
    if not getattr(app, "delta_sync", True):
        caps &= ~CAP_DELTA_SYNC
    if wire_batch_limit(app) <= 1 or apply_batch_limit(app) <= 1 or \
            getattr(app, "serve_plane", None) is not None or \
            (meta is not None and getattr(meta, "batch_wire_off", False)):
        caps &= ~CAP_BATCH_STREAM
    if not wire_compress_of(app) or \
            (meta is not None and
             getattr(meta, "compress_wire_off", False)):
        caps &= ~CAP_COMPRESS
    if getattr(getattr(app, "node", None), "cluster", None) is not None:
        # slot-table gossip rides the repl stream only between two
        # cluster-mode nodes; a disabled node advertises nothing and a
        # legacy peer is never sent a CLUSTERTAB frame (push-loop gate)
        caps |= CAP_CLUSTER
    return caps


def apply_batch_limit(app) -> int:
    """The node's replication-apply coalescing bound (<= 1 = the exact
    per-frame apply path, replica/coalesce.py)."""
    ab = getattr(app, "apply_batch", None)
    if ab is None:
        from ..conf import env_int
        return env_int("CONSTDB_APPLY_BATCH", 512)
    return ab


def wire_batch_limit(app) -> int:
    """Max frames per REPLBATCH run (1 = the exact per-frame stream)."""
    wb = getattr(app, "wire_batch", None)
    if wb is None:
        from ..conf import env_int
        return env_int("CONSTDB_WIRE_BATCH", 512)
    return wb


def wire_compress_of(app) -> bool:
    """Is negotiated replication compression on for this node (both
    legs: compress outbound to CAP_COMPRESS peers AND advertise the
    capability)?  CONSTDB_WIRE_COMPRESS=0 is the kill switch."""
    wc = getattr(app, "wire_compress", None)
    if wc is None:
        from ..conf import env_flag
        return env_flag("CONSTDB_WIRE_COMPRESS", True)
    return bool(wc)


def wire_compress_min(app) -> int:
    """Min REPLBATCH payload bytes before the negotiated stream
    compression engages (framing overhead beats the savings below)."""
    wm = getattr(app, "wire_compress_min", None)
    if wm is None:
        from ..conf import env_int
        return env_int("CONSTDB_WIRE_COMPRESS_MIN", 512)
    return wm


def wire_latency_of(app) -> float:
    """Seconds a drained frame may sit in the push loop's aggregated
    wire buffer before a socket flush is forced (idle cycles always
    flush at their end, so a lone write never waits this long)."""
    wl = getattr(app, "wire_latency", None)
    if wl is None:
        from ..conf import env_float
        return env_float("CONSTDB_WIRE_LATENCY_MS", 5.0) / 1000.0
    return wl


def backoff_delay(base: float, factor: float, cap: float, jitter: float,
                  node_id: int, addr: str, attempt: int,
                  salt: int = 0) -> float:
    """Reconnect delay before dial `attempt` (0-based count of
    CONSECUTIVE failures): bounded exponential growth with
    DETERMINISTIC jitter.  The jitter fraction derives from a splitmix64
    hash of (node_id, peer addr, attempt, salt) — two nodes dialing one
    returned peer still de-synchronize (no thundering herd), but a chaos
    scenario's retry cadence is a pure function of its inputs, so a
    failure replays exactly from its printed seed (random.random() here
    would make every replay walk a different schedule).  `salt` varies
    the jitter without touching the exponent (the dial loop feeds its
    iteration count, so even the flat connected-supervisor cadence
    drifts apart across nodes — see the lockstep note there)."""
    d = min(cap, base * (factor ** min(attempt, 32)))
    if jitter <= 0.0:
        return d
    import zlib
    x = (node_id * 0x9E3779B97F4A7C15 + zlib.crc32(addr.encode())
         + attempt * 1000003 + salt) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 31
    frac = (x & 0xFFFF) / 65535.0  # [0, 1]
    return d * (1.0 - jitter + 2.0 * jitter * frac)


_READ_CHUNK = 1 << 16
# push-loop wire buffer: flush to the socket at this many buffered bytes
# (backpressure bound; the latency bound is CONSTDB_WIRE_LATENCY_MS)
_WIRE_FLUSH_BYTES = 1 << 18
# per-frame drain unit (the legacy 64-frame drain cadence)
_RUN_FRAMES = 64
# runs shorter than this ship per-frame: a 1-op REPLBATCH buys no batch
# bookkeeping and costs header + payload framing over the plain frame
_MIN_WIRE_RUN = 2


class ReplicaLink:
    """Drives replication with one peer.  `start()` begins the dial loop;
    `adopt()` installs an inbound connection."""

    def __init__(self, app: "ServerApp", meta: ReplicaMeta):
        self.app = app
        self.node = app.node
        self.meta = meta
        meta.link = self
        self.closing = False
        self._dial_task: Optional[asyncio.Task] = None
        self._serve_task: Optional[asyncio.Task] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        # node.reset_epoch at connection install; a mismatch marks this
        # stream as pre-dating a local state wipe (see _pull_loop REPLACK)
        self._epoch = 0
        # capability bits the peer advertised in the live connection's
        # handshake (0 = pre-capability peer / no connection yet)
        self._peer_caps = 0
        # digest negotiation plumbing: the push loop initiates rounds and
        # awaits DIGESTACK replies, which arrive on the PULL loop — the
        # queue bridges them (fresh per connection, so a dead stream's
        # late acks can never answer a new round's question); the cache
        # pins the puller-side matrix across a round's two levels so both
        # comparisons see ONE consistent state cut
        self._digest_acks: Optional[asyncio.Queue] = None
        self._digest_cache = None
        self._delta_token = 0
        # held by _stream_file for a whole raw payload window: the pull
        # loop answers the peer's digest questions on the SAME writer,
        # and a whole-frame write is only atomic BETWEEN frames — a
        # DIGESTACK landing inside a FULLSYNC/DELTASYNC byte window
        # would corrupt the peer's spill download
        self._stream_lock = asyncio.Lock()
        # per-download spill-file serial: a reconnect/adopt overlap can
        # briefly run TWO pull loops for one peer, and a shared spill
        # path would interleave their downloads into one corrupt file
        # (caught by the chaos harness as a spurious InvalidSnapshot on
        # a perfectly healthy stream)
        self._spill_seq = 0
        # reconnect observability (INFO repl_link_state/repl_reconnects)
        # + the backoff ladder's position: consecutive dial failures
        # since the last live connection
        self._attempts = 0
        self._ever_connected = False
        self.reconnects = 0
        # replication flow-control observability (INFO replica<i> rows):
        # unacked stream bytes in the peer's window, and whether the
        # push loop is currently pausing the ring drain on it
        self.win_unacked = 0
        self.win_paused = False
        # broadcast-plane observability (INFO replica<i> rows): bytes
        # written to this peer, encode-cache reuse, and the negotiated
        # compression's raw-vs-wire accounting for this link's stream
        self.bytes_out = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.comp_raw_bytes = 0
        self.comp_wire_bytes = 0

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        if self._dial_task is None or self._dial_task.done():
            self._dial_task = asyncio.create_task(self._dial_loop())

    async def stop(self) -> None:
        self.closing = True
        for t in (self._dial_task, self._serve_task):
            if t is not None and not t.done():
                t.cancel()
        await self._close_conn()
        self.meta.link = None

    @property
    def connected(self) -> bool:
        return self._serve_task is not None and not self._serve_task.done()

    @property
    def state(self) -> str:
        """Link lifecycle for INFO (`repl_link_state`): `connected`, a
        first `dialing`, or `backoff:N` after N consecutive failures —
        the previously-implicit retry cadence, made observable (the
        chaos harness's fault accounting reads it too)."""
        if self.connected:
            return "connected"
        if self.meta.dial_suspended:
            return "suspended"
        if self.closing:
            return "closed"
        return f"backoff:{self._attempts}" if self._attempts else "dialing"

    # ------------------------------------------------------ byte accounting
    # replication traffic counts into the node's net totals plus dedicated
    # repl_* gauges (reference buf_read.rs:218-236 / buf_write.rs:165-183
    # count every socket byte; a node mid-catch-up is busiest exactly here)

    def _count_in(self, n: int) -> None:
        st = self.node.stats
        st.net_in_bytes += n
        st.repl_in_bytes += n

    def _write(self, writer, data: bytes) -> None:
        st = self.node.stats
        st.net_out_bytes += len(data)
        st.repl_out_bytes += len(data)
        self.bytes_out += len(data)
        writer.write(data)

    def _flush_wire(self, writer, out: bytearray) -> bytearray:
        """One aggregated steady-state stream write — a drain cycle's
        frames in one transport call instead of one per frame (the PR 5
        reply-buffer swap: ownership moves to the transport, which
        copies only what it cannot send immediately).  Counted into
        `repl_wire_bytes_out` so the bench's wire-bytes-per-op compare
        sees ONLY stream frames, not snapshots or acks."""
        self.node.stats.repl_wire_bytes_out += len(out)
        self._write(writer, out)
        return bytearray()

    def _encode_frames(self, out: bytearray, run: list) -> None:
        """The per-frame REPLICATE encoding, byte-exact with the pre-PR
        stream — the ONE definition both the legacy-peer branch and the
        demoted-run fallback share (the byte-exactness pin in
        tests/test_wire_batch.py covers every caller through it)."""
        nid = self.node.node_id
        for e in run:
            encode_into(out, Arr([
                Bulk(REPLICATE), Int(nid), Int(e.prev_uuid), Int(e.uuid),
                Bulk(e.name), *e.args]))

    def _encode_wire_run(self, out: bytearray, run: list, cursor: int,
                         compress: bool = False,
                         comp_min: int = 0) -> tuple:
        """Encode one drained run into `out`: maximal sub-runs of
        consecutive encodable ops become REPLBATCH frames
        (replica/wire.py), everything else — barriers, sub-runs below
        _MIN_WIRE_RUN, runs the codec demotes — ships as the exact
        per-frame REPLICATE frames.  `compress`: wrap payloads of at
        least `comp_min` bytes in the negotiated compression framing
        (utils/compressio.py), kept only when it actually shrinks them.
        Returns (cursor, batches, batch_frames, comp_raw, comp_wire) —
        the counts the encode-once cache republishes per reusing peer."""
        node = self.node
        nid = node.node_id
        st = node.stats
        enc_has = COLUMNAR_ENCODERS.__contains__
        batches = batch_frames = comp_raw = comp_wire = 0
        i, n = 0, len(run)
        while i < n:
            j = i
            while j < n and enc_has(run[j].name):
                j += 1
            if j - i >= _MIN_WIRE_RUN:
                sub = run[i:j]
                payload = wire.build_wire_batch(sub, nid)
                if payload is not None:
                    if compress and len(payload) >= comp_min:
                        from ..utils.compressio import compress_bytes
                        z = compress_bytes(payload, level=1)
                        if len(z) < len(payload):
                            comp_raw += len(payload)
                            comp_wire += len(z)
                            payload = z
                    encode_into(out, Arr([
                        Bulk(REPLBATCH), Int(nid), Int(sub[0].prev_uuid),
                        Int(sub[-1].uuid), Int(len(sub)),
                        Bulk(payload)]))
                    st.repl_wire_batches_out += 1
                    st.repl_wire_batch_frames_out += len(sub)
                    batches += 1
                    batch_frames += len(sub)
                    i = j
                    cursor = sub[-1].uuid
                    continue
                # demotion must be LOUD: count it and log it — a codec
                # that silently lags the encoder table would erase the
                # whole batching win without tripping a single test
                x = st.extra
                x["repl_wire_encode_demotions"] = \
                    x.get("repl_wire_encode_demotions", 0) + 1
                log.warning(
                    "push %s: wire codec demoted a run of %d encodable "
                    "ops to per-frame delivery", self.meta.addr, j - i)
            stop = j if j > i else i + 1
            self._encode_frames(out, run[i:stop])
            cursor = run[stop - 1].uuid
            i = stop
        if comp_raw:
            st.repl_comp_raw_bytes += comp_raw
            st.repl_comp_wire_bytes += comp_wire
        return cursor, batches, batch_frames, comp_raw, comp_wire

    async def _close_conn(self) -> None:
        w, self._writer = self._writer, None
        if w is not None:
            try:
                w.close()
                await w.wait_closed()
            except (ConnectionError, OSError):
                pass

    # ----------------------------------------------------------------- dial

    async def _dial_loop(self) -> None:
        """Reconnect-forever with BOUNDED EXPONENTIAL backoff (the
        reference retries at a flat 5s — replica/replica.rs:254-271; a
        flat cadence hammers a recovering peer from the whole mesh at
        once, and an implicit one is unobservable).  Consecutive
        failures walk base * factor^n up to the ceiling, with
        deterministic jitter (`backoff_delay`); any live connection —
        dialed or adopted — resets the ladder.  While connected this
        loop is just the reconnect supervisor, polling at the base
        cadence."""
        app = self.app
        it = 0
        while not self.closing and self.meta.alive and \
                not self.meta.dial_suspended:
            if not self.connected:
                try:
                    await self._dial_once()
                except (ConnectionError, OSError, CstError,
                        asyncio.TimeoutError) as e:
                    self._attempts += 1
                    log.debug("dial %s failed (attempt %d): %s",
                              self.meta.addr, self._attempts, e)
            it += 1
            if self.connected:
                # supervisor cadence: base delay, but still JITTERED
                # (per iteration) — two peers that dial each other in
                # the same instant each install their own connection
                # and close the other's; identical un-jittered sleeps
                # would redo that collision forever, in lockstep (the
                # chaos suite's connection-kill test caught exactly
                # this livelock when the jitter briefly covered only
                # the failure branch)
                delay = backoff_delay(
                    app.reconnect_delay, 1.0, app.reconnect_delay,
                    app.reconnect_jitter, self.node.node_id,
                    self.meta.addr, 0, salt=it)
            else:
                # _attempts was already bumped for the failure this
                # sleep follows, so rung 0 — the documented BASE delay
                # of the first retry — is attempts-1 (a drop without a
                # failed dial yet leaves attempts at 0: also the base)
                delay = backoff_delay(
                    app.reconnect_delay, app.reconnect_factor,
                    app.reconnect_max, app.reconnect_jitter,
                    self.node.node_id, self.meta.addr,
                    max(0, self._attempts - 1), salt=it)
            await asyncio.sleep(delay)

    async def _dial_once(self) -> None:
        host, port = self.meta.addr.rsplit(":", 1)
        epoch0 = self.node.reset_epoch  # watermark snapshot validity fence
        reader, writer = await self.app.open_peer_connection(host,
                                                             int(port))
        try:
            self._write(writer, encode_msg(Arr([
                Bulk(SYNC), Int(0), Int(self.node.node_id),
                Bulk(self.node.alias.encode()),
                Bulk(self.app.advertised_addr.encode()),
                Int(self.meta.uuid_he_sent),
                Int(my_caps(self.app, self.meta))])))
            await writer.drain()
            parser = make_parser()
            msg = await _read_msg(reader, parser,
                                  timeout=self.app.handshake_timeout,
                                  count=self._count_in)
            peer_resume = self._check_sync_reply(msg)
            if self.node.reset_epoch != epoch0:
                # a local state wipe landed mid-handshake: the resume
                # watermark we already sent is PRE-wipe, so the peer would
                # stream nothing and its drained beacon would advance our
                # zeroed watermark past ops the wipe discarded.  Abort;
                # the dial loop retries with the post-wipe watermark.
                raise CstError("local state wiped mid-handshake; redialing")
        except BaseException:
            writer.close()
            raise
        self._install(reader, writer, parser, peer_resume)

    def _check_sync_reply(self, msg) -> int:
        from ..resp.message import Err
        if isinstance(msg, Err) and msg.val.startswith(b"FORGOTTEN"):
            # the peer expelled us (FORGET): stop dialing it.  The flag is
            # cleared when someone re-MEETs us and dials in (adopt()).
            self.meta.dial_suspended = True
            log.info("peer %s rejected sync: forgotten; suspending dial",
                     self.meta.addr)
            raise CstError(f"forgotten by {self.meta.addr}")
        items = msg.items if isinstance(msg, Arr) else None
        if not items or as_bytes(items[0]).lower() != SYNC or \
                as_int(items[1]) != 1:
            raise CstError(f"bad sync reply from {self.meta.addr}: {msg!r}")
        self.meta.node_id = as_int(items[2])
        self.meta.alias = as_bytes(items[3]).decode("utf-8", "replace")
        self._peer_caps = as_int(items[6]) if len(items) > 6 else 0
        return as_int(items[5])

    # ---------------------------------------------------------------- adopt

    def adopt(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter,
              parser: RespParser, peer_resume: int,
              peer_caps: int = 0) -> None:
        """Install an inbound connection (the passive side of SYNC —
        reference replica.rs:16-40 steals the client's Conn).
        `peer_caps`: capability bits from the peer's SYNC frame (0 = a
        pre-capability peer)."""
        self.meta.dial_suspended = False  # the mesh re-admitted us
        self._peer_caps = peer_caps
        self._install(reader, writer, parser, peer_resume)

    def kick(self) -> None:
        """Drop the live connection (if any) so the dial loop — ours or the
        peer's — re-handshakes from the meta's CURRENT watermarks.  Used
        after a local state wipe (Node.reset_for_full_resync): an existing
        stream's positions describe state that no longer exists."""
        t = self._serve_task
        if t is not None and not t.done():
            t.cancel()
        w, self._writer = self._writer, None
        if w is not None:
            w.close()

    def _install(self, reader, writer, parser, peer_resume: int) -> None:
        self.meta.last_seen_ms = now_ms()
        self._attempts = 0  # any live connection resets the backoff ladder
        if self._ever_connected:
            # every re-established connection after the link's first —
            # dialed or adopted — is one reconnect (INFO repl_reconnects;
            # the chaos oracle checks this against its injected kills)
            self.reconnects += 1
            self.node.stats.repl_reconnects += 1
        self._ever_connected = True
        self._epoch = self.node.reset_epoch
        self._digest_acks = asyncio.Queue()
        self._digest_cache = None
        old_task, old_writer = self._serve_task, self._writer
        self._writer = writer
        self._serve_task = asyncio.create_task(
            self._serve(reader, writer, parser, peer_resume))
        if old_task is not None and not old_task.done():
            old_task.cancel()
        if old_writer is not None:
            old_writer.close()

    # ---------------------------------------------------------------- serve

    async def _serve(self, reader, writer, parser, peer_resume: int) -> None:
        push = asyncio.create_task(self._push_loop(writer, peer_resume))
        try:
            await self._pull_loop(reader, writer, parser)
        except (ConnectionError, OSError, asyncio.IncompleteReadError) as e:
            log.debug("link %s dropped: %s", self.meta.addr, e)
        except ReplicateCommandsLost as e:
            log.warning("link %s: %s — forcing full resync", self.meta.addr, e)
        except CstError as e:
            log.warning("link %s protocol error: %s", self.meta.addr, e)
        except asyncio.CancelledError:
            raise
        finally:
            push.cancel()
            if self._writer is writer:
                self._writer = None
            writer.close()

    # ----------------------------------------------------------------- push

    async def _push_loop(self, writer, peer_resume: int) -> None:
        """Outbound half (reference push.rs): full-vs-partial, then stream
        repl_log frames; REPLACK heartbeat.

        The send position is a LOCAL cursor, never read back from the
        shared meta.  During a reconnect/adopt overlap two push loops
        briefly coexist on one meta; with a shared cursor the dying loop
        keeps advancing it while writing to a dead socket, the new loop
        then skips those entries as already-sent, and its drained beacon
        advances the peer's pull watermark straight over the hole —
        silently lost ops mesh-wide (found by the round-5 chaos suite).
        A local cursor confines every advance to the connection it was
        actually written to; meta.uuid_i_sent is only mirrored for
        observability while this connection is still the live one."""
        node = self.node
        meta = self.meta
        # EVENT_PULL_LANDED wakes this loop when OUR pull side lands a
        # batch of the peer's stream, so the REPLACK below goes out once
        # per covering batch instead of a heartbeat later;
        # EVENT_REPLICA_ACKED wakes it when a REPLACK lands, so a
        # window-paused drain (below) resumes the moment the peer
        # catches up instead of a heartbeat later
        consumer = node.events.new_consumer(
            EVENT_REPLICATED | EVENT_PULL_LANDED | EVENT_REPLICA_ACKED)
        wire_batch = wire_batch_limit(self.app)
        wire_latency = wire_latency_of(self.app)
        wire_compress = wire_compress_of(self.app)
        wire_comp_min = wire_compress_min(self.app)
        # replication flow control (CONSTDB_REPL_WINDOW): stream bytes
        # written to this connection but not yet covered by the peer's
        # REPLACK watermark.  `inflight` holds (cursor_after_flush,
        # nbytes) per aggregated wire flush; entries retire as
        # uuid_i_acked passes their cursor.  When the total passes the
        # window the loop stops DRAINING THE RING for this peer —
        # memory stops growing here and in the transport — and resumes
        # on ack; a long stall degrades to ring eviction, recovered by
        # the certified delta/full resync path on this same connection.
        window = getattr(self.app, "repl_window", None)
        if window is None:
            from ..conf import env_int
            window = env_int("CONSTDB_REPL_WINDOW", 16 << 20)
        from collections import deque
        stage = node.stages.stage
        inflight: deque = deque()
        inflight_bytes = 0
        paused = False
        loop = asyncio.get_running_loop()
        try:
            synced = False  # peer_resume not yet honored
            # lint: pin[cursor] — the send cursor is OWNED by this loop
            # (docstring above: a local cursor confines every advance to
            # this connection); every use re-validates against the live
            # ring via can_resume_from/run_after, so the pre-await value
            # is the intended one, not a stale shared read
            cursor = 0
            last_ack = 0.0
            tab_rev = -1  # slot-table revision last gossiped on this conn
            while True:
                acked = meta.uuid_i_acked
                while inflight and inflight[0][0] <= acked:
                    inflight_bytes -= inflight.popleft()[1]
                self.win_unacked = inflight_bytes
                win_full = bool(window) and synced and \
                    inflight_bytes > window
                if win_full and not paused:
                    paused = self.win_paused = True
                    node.stats.repl_window_pauses += 1
                    log.warning(
                        "push %s: %d unacked stream bytes over "
                        "CONSTDB_REPL_WINDOW=%d; pausing ring drain "
                        "until the peer acks", meta.addr, inflight_bytes,
                        window)
                elif not win_full:
                    paused = self.win_paused = False
                if not paused and \
                        (not synced or
                         not node.repl_log.can_resume_from(cursor)):
                    resume = peer_resume if not synced else cursor
                    if node.repl_log.can_resume_from(resume):
                        # partial replay is always the lossless choice when
                        # the log covers the resume point: delete OPS are
                        # still in the ring even after their tombstones
                        # were physically collected (manager.min_uuid)
                        self._write(writer, encode_msg(Arr([Bulk(PARTSYNC)])))
                        cursor = resume
                    else:
                        # a peer excluded from the GC horizon (needs_full)
                        # whose resume point also fell off the ring may hold
                        # keys whose tombstones we already collected — a
                        # plain snapshot merge cannot delete them, so it
                        # must WIPE before merging (fullsync reset flag)
                        reset = meta.needs_full
                        if reset and not (self._peer_caps
                                          & CAP_FULLSYNC_RESET):
                            # a pre-capability peer would silently merge
                            # WITHOUT wiping — the exact resurrection
                            # scenario the reset flag exists to prevent.
                            # Refuse loudly instead of downgrading; the
                            # dial loop retries with backoff until the
                            # peer upgrades (or an operator intervenes).
                            log.error(
                                "push %s: peer needs a state-clearing "
                                "full resync but did not advertise the "
                                "fullsync-reset capability (mixed-"
                                "version mesh?); refusing to downgrade "
                                "to a non-wiping sync", meta.addr)
                            x = node.stats.extra
                            x["fullsync_reset_refused"] = \
                                x.get("fullsync_reset_refused", 0) + 1
                            writer.close()
                            return
                        # digest-driven partial resync where it is sound:
                        # an ordinary off-ring catch-up (incl. the
                        # mid-stream ring-falloff recovery, which re-enters
                        # this decision) against a CAP_DELTA_SYNC peer.
                        # A state-CLEARING resync must stay a full
                        # snapshot — the peer wipes first, so there is no
                        # surviving state to diff against.  _send_delta
                        # returns None when the negotiation demotes
                        # (threshold, timeout, malformed reply) and the
                        # exact full-sync path runs instead.
                        cursor = None
                        if not reset and \
                                (self._peer_caps & CAP_DELTA_SYNC) and \
                                getattr(self.app, "delta_sync", True):
                            cursor = await self._send_delta(writer)
                            if cursor is None:
                                # EVERY demotion exit counts — threshold,
                                # timeout, malformed reply — so INFO's
                                # repl_delta_demotions matches the
                                # invariant doc and a silently failing
                                # delta path is visible next to the
                                # climbing repl_full_syncs
                                x = node.stats.extra
                                x["repl_delta_demotions"] = \
                                    x.get("repl_delta_demotions", 0) + 1
                        if cursor is None:
                            cursor = await self._send_snapshot(
                                writer, reset=reset)
                    synced = True
                    meta.needs_full = False

                # Drain the log in RUNS, frames aggregated into ONE wire
                # buffer per socket flush (the PR 5 reply-buffer swap, on
                # the push side) under a dual bound: _WIRE_FLUSH_BYTES
                # (backpressure) and the wire latency (bytes keep moving
                # through a long catch-up drain).  An idle cycle always
                # flushes at its end, so a lone write ships immediately
                # with the exact per-frame latency.  Runs of consecutive
                # encodable ops group-encode into REPLBATCH frames when
                # the peer can decode them; everything else — legacy
                # peers, CONSTDB_WIRE_BATCH=1, barriers, demoted runs —
                # is the byte-exact per-frame stream.
                #
                # Broadcast fan-out (round 17): the FIRST loop to drain
                # a run publishes its finished wire bytes in the node's
                # encode-once cache; every other loop at the same cursor
                # and caps-class splices the published bytes instead of
                # re-encoding, so N-peer steady-state encode work is
                # O(ops), not O(N·ops).  The caps-class key pins every
                # knob that changes the bytes: "b"/"bz" for the plain/
                # compressed REPLBATCH stream, "f" for the byte-exact
                # per-frame rendering legacy and demoted peers share.
                batching = wire_batch > 1 and \
                    bool(self._peer_caps & CAP_BATCH_STREAM)
                compressing = batching and \
                    bool(self._peer_caps & CAP_COMPRESS) and \
                    wire_compress
                caps_class = ("bz" if compressing else "b") if batching \
                    else "f"
                cache = node.wire_cache
                if cache.enabled:
                    # ring-eviction coherence: entries below the
                    # resumable horizon can never be read again
                    cache.evict_below(node.repl_log.evicted_up_to)
                out = bytearray()
                t_flush = loop.time()

                def flush_out(buf: bytearray) -> bytearray:
                    # every aggregated stream flush is one window entry:
                    # acked when the peer's REPLACK watermark passes the
                    # cursor the flush ended at
                    nonlocal inflight_bytes
                    inflight.append((cursor, len(buf)))
                    inflight_bytes += len(buf)
                    with stage("repl_push"):
                        return self._flush_wire(writer, buf)

                while not paused:
                    # one drained run a step, under the stage: log
                    # read, encode cache, wire batch (the socket
                    # write is `flush_out`'s, staged there)
                    with stage("repl_push"):
                        hit = None
                        if cache.enabled:
                            # the splice honors the same emission
                            # floor run_after applies (encode_cache.get
                            # docstring: a published-but-not-yet-durable
                            # run must not be emitted through the cache
                            # side door)
                            fl = getattr(node.repl_log, "floor", None)
                            hit = cache.get(
                                caps_class, cursor,
                                below=fl() if callable(fl) else None)
                        if hit is not None:
                            # published by another peer's loop at this
                            # exact cursor: splice the finished bytes and
                            # republish the per-send wire counters from
                            # the entry
                            out += hit.payload
                            cursor = hit.end
                            self.cache_hits += 1
                            st = node.stats
                            st.repl_ops_out += hit.frames
                            st.repl_encode_cache_hits += 1
                            st.repl_wire_batches_out += hit.batches
                            st.repl_wire_batch_frames_out += \
                                hit.batch_frames
                            st.repl_comp_raw_bytes += hit.comp_raw
                            st.repl_comp_wire_bytes += hit.comp_wire
                            self.comp_raw_bytes += hit.comp_raw
                            self.comp_wire_bytes += hit.comp_wire
                        else:
                            # byte-capped runs: the flush bound below
                            # must get a chance to engage BEFORE a backlog
                            # of huge values is encoded into one frame/
                            # buffer (a lone oversized entry still ships
                            # whole, as per-frame always did)
                            run = node.repl_log.run_after(
                                cursor,
                                wire_batch if batching else _RUN_FRAMES,
                                _WIRE_FLUSH_BYTES)
                            if not run:
                                break
                            if run[0].prev_uuid > cursor:
                                # the ring evicted past our cursor while
                                # this loop yielded (the drain below):
                                # streaming the run would hand the peer a
                                # gap, blow up its pull loop
                                # (ReplicateCommandsLost) and force a
                                # teardown + redial + snapshot over a FRESH
                                # connection.  Recover IN PLACE instead:
                                # stop here and let the round decision
                                # re-send a full snapshot on this same
                                # stream (eviction past the cursor implies
                                # can_resume_from(cursor) is False).  This
                                # is the fallback the module header
                                # documents — the reference leaves the
                                # case unhandled (pull.rs:167-172).
                                log.warning(
                                    "push %s: repl_log evicted past send "
                                    "cursor mid-stream; resyncing in place",
                                    meta.addr)
                                break
                            seg = bytearray()
                            start = cursor
                            if batching:
                                (cursor, nb, nbf, craw,
                                 cwire) = self._encode_wire_run(
                                    seg, run, cursor, compress=compressing,
                                    comp_min=wire_comp_min)
                            else:
                                self._encode_frames(seg, run)
                                cursor = run[-1].uuid
                                nb = nbf = craw = cwire = 0
                            self.comp_raw_bytes += craw
                            self.comp_wire_bytes += cwire
                            node.stats.repl_ops_out += len(run)
                            if cache.enabled:
                                self.cache_misses += 1
                                node.stats.repl_encode_cache_misses += 1
                                cache.put(caps_class, start, cursor,
                                          bytes(seg), batches=nb,
                                          batch_frames=nbf, comp_raw=craw,
                                          comp_wire=cwire,
                                          readers=self._expected_readers(),
                                          frames=len(run))
                            out += seg
                    if len(out) >= _WIRE_FLUSH_BYTES or \
                            loop.time() - t_flush >= wire_latency:
                        out = flush_out(out)
                        await writer.drain()  # backpressure + yield
                        t_flush = loop.time()
                    if window and inflight_bytes > window:
                        # the window filled MID-drain: stop pulling the
                        # ring now; the top of the loop re-evaluates
                        # (and counts) the pause
                        break
                if out:
                    out = flush_out(out)
                if self._writer is writer:
                    meta.uuid_i_sent = cursor  # observability (INFO)
                if not paused and not node.repl_log.can_resume_from(cursor):
                    # fell off the ring mid-round: resync NOW (top of the
                    # loop) instead of sleeping out a heartbeat first
                    await writer.drain()
                    continue

                cl = node.cluster
                if cl is not None and (self._peer_caps & CAP_CLUSTER) \
                        and cl.rev != tab_rev:
                    # slot-table gossip: once per table CHANGE per
                    # connection (first round includes the initial
                    # table).  Gated on cl.rev, not the epoch: a
                    # per-slot join or a learned address can change the
                    # table without minting a new epoch, and peers need
                    # that news too.  Only to peers that advertised the
                    # capability — a legacy or disabled peer's stream
                    # carries zero cluster bytes (the byte-exact pin).
                    tab_rev = cl.rev
                    self._write(writer, encode_msg(Arr([
                        Bulk(CLUSTERTAB), Int(cl.epoch),
                        Bulk(cl.table.serialize())])))

                now = asyncio.get_running_loop().time()
                # durable-ack cap (persist/oplog.py): the advertised
                # pull watermark and coverage may only name intake
                # frames the op log has made durable — a torn tail must
                # never clip a frame a peer was already TOLD we hold
                # (its GC gates tombstone collection on these values).
                # Without an op log both caps are identity.
                oplog = node.oplog
                ack_val = meta.uuid_he_sent
                if oplog is not None:
                    # clamped to the last advertised value: a reconnect
                    # redelivery re-appends frames BELOW an ack already
                    # sent, but the original copies are in the durable
                    # prefix — regressing the advertisement would only
                    # confuse monotonicity monitors, never durability
                    ack_val = max(oplog.cap_ack(meta.node_id, ack_val),
                                  meta.uuid_he_acked)
                if (ack_val > meta.uuid_he_acked
                        or now - last_ack >= self.app.heartbeat):
                    # coverage is only computed when an ack actually
                    # goes out — it is an O(peers) scan and this loop
                    # wakes per delivered batch under firehose intake
                    coverage = node.replicas.cluster_coverage()
                    if oplog is not None:
                        coverage = oplog.cap_coverage(coverage)
                    # beacon: with the log fully drained, every uuid this
                    # node will EVER stream from now on exceeds its current
                    # HLC — peers may advance their pull watermark to it, so
                    # idle nodes don't pin the cluster GC horizon at 0.
                    # Item 5 is this node's CLUSTER COVERAGE (the uuid it
                    # holds every origin's stream up to) — the peer's GC
                    # gates third-party tombstone collection on it
                    # (manager.min_uuid; legacy receivers ignore extras).
                    drained = cursor >= node.repl_log.last_uuid
                    beacon = node.hlc.current if drained else 0
                    if beacon and oplog is not None:
                        # the beacon is the promise "every uuid I will
                        # EVER mint exceeds B" — with a durable op log,
                        # B is capped at the last group-committed HLC
                        # mark, or a crash could rewind the clock below
                        # an already-sent beacon and peers would dup-
                        # skip the re-minted window forever
                        # (persist/oplog.py beacon_cap)
                        beacon = min(beacon, oplog.beacon_cap)
                    self._write(writer, encode_msg(Arr([
                        Bulk(REPLACK), Int(ack_val), Int(now_ms()),
                        Int(beacon),
                        Int(coverage)])))
                    meta.uuid_he_acked = ack_val
                    last_ack = now
                await writer.drain()
                await consumer.wait(timeout=self.app.heartbeat)
        except (ConnectionError, OSError) as e:
            log.debug("push %s dropped: %s", self.meta.addr, e)
        finally:
            # the window gauges describe THIS connection's in-flight
            # bytes; left set, INFO would report a stale paused window
            # for a link that is reconnecting and not pushing at all
            self.win_unacked = 0
            self.win_paused = False
            consumer.close()

    def _expected_readers(self) -> int:
        """How many OTHER live links may reuse a run encoding published
        at this link's cursor — the encode-once cache's initial
        ref-count.  A heuristic (peers can connect later, classes can
        differ), so the cache's LRU byte bound is the safety net; what
        it guarantees is the cheap case: a single-peer node publishes
        nothing and pays nothing."""
        n = 0
        for m in self.node.replicas.live_peers():
            lk = m.link
            if lk is not None and lk is not self and not lk.closing:
                n += 1
        return n

    def _bulk_compress(self) -> bool:
        """Ship this peer's FULLSYNC/DELTASYNC window as the compressed
        snapshot container?  Negotiated (CAP_COMPRESS) and gated on the
        node-wide kill switch; a legacy or demoted peer gets the exact
        plain byte stream."""
        return bool(self._peer_caps & CAP_COMPRESS) and \
            wire_compress_of(self.app) and \
            not getattr(self.meta, "compress_wire_off", False)

    async def _send_snapshot(self, writer, reset: bool = False) -> int:
        """Fork-free full sync with bounded memory: acquire the node's
        SHARED on-disk dump (produced once, reused by every concurrently
        or subsequently syncing peer while the repl_log still covers its
        watermark — reference server.rs:221-250 reuse + push.rs:34-71
        send_file, minus the fork) and stream the file to the socket in
        fixed-size pieces.  Returns the dump's repl watermark — the push
        loop's new send cursor (the repl_log gap above it streams next,
        which `can_resume_from` guarantees is still present)."""
        # a CAP_COMPRESS peer gets the compressed-container VARIANT of
        # the shared dump — produced once, reused by every capable peer;
        # the receiver's snapshot loader sniffs the container magic, so
        # the FULLSYNC header and download path are unchanged on the
        # wire (and a legacy peer's stream stays byte-exact pre-PR)
        dump = await self.app.shared_dump.acquire(
            compressed=self._bulk_compress())
        self.node.stats.repl_full_syncs += 1
        await self._stream_file(writer, dump.path, encode_msg(Arr([
            Bulk(FULLSYNC), Int(dump.size), Int(dump.repl_last),
            Int(1 if reset else 0)])))
        return dump.repl_last

    async def _stream_file(self, writer, path: str, header: bytes) -> None:
        """`header` + the file's bytes to the socket in fixed-size
        pieces (the FULLSYNC and DELTASYNC transports share this).
        Open + reads off-loop: a resync burst on a loaded disk must not
        hiccup every client (ASYNC-BLOCK; the writes are socket-buffered
        and drain() yields between pieces).  The FIRST piece is read
        BEFORE the header goes out so the stream never shows a header
        with zero payload bytes behind it — the pre-executor code had no
        such window (header + first read happened in one task step) and
        the wire contract keeps it."""
        loop = asyncio.get_running_loop()
        f = await loop.run_in_executor(None, open, path, "rb")
        try:
            async with self._stream_lock:
                piece = await loop.run_in_executor(None, f.read,
                                                   _READ_CHUNK)
                self._write(writer, header)
                while piece:
                    self._write(writer, piece)
                    await writer.drain()
                    piece = await loop.run_in_executor(None, f.read,
                                                       _READ_CHUNK)
        finally:
            f.close()

    # ---------------------------------------------------- delta anti-entropy

    async def _local_digest(self, fanout: int, leaves: int) -> np.ndarray:
        """This node's (fanout, leaves) state digest matrix
        (store/digest.py): plane-aware — a shard-per-core node sums its
        workers' matrices (their keys partition the keyspace), a plain
        node folds its own keyspace after an engine flush."""
        node = self.node
        if node.serve_plane is not None:
            return await node.serve_plane.state_digest(fanout, leaves)
        node.ensure_flushed()
        from ..store.digest import state_digest_matrix
        # the FIRST digest on a long-lived store pays the per-item
        # Python crc32 backlog over every key and member — seconds at
        # north-star scale, which on-loop would stall serving and
        # REPLACK heartbeats past the peer's ack deadline.  Warm the
        # caches off-loop; rows landing mid-warm are picked up by the
        # (now tiny) incremental sync inside the fold below.
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, node.ks.warm_digest_caches)
        node.ensure_flushed()  # re-land anything that arrived mid-warm
        return state_digest_matrix(node.ks, fanout, leaves)

    async def _await_digest_ack(self, token: int, level: int
                                ) -> Optional[bytes]:
        """Next DIGESTACK payload for (token, level), bridged over from
        the pull loop; None on timeout/malformed (the caller demotes to
        a full snapshot).  Acks from abandoned rounds are discarded."""
        q = self._digest_acks
        if q is None:
            return None
        timeout = getattr(self.app, "handshake_timeout", 10.0)
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while True:
            left = deadline - loop.time()
            if left <= 0:
                return None
            try:
                items = await asyncio.wait_for(q.get(), left)
            except asyncio.TimeoutError:
                return None
            try:
                if as_int(items[1]) == token and as_int(items[2]) == level:
                    return as_bytes(items[3])
            except (CstError, IndexError):
                return None

    async def _refine_keys(self, writer, token: int, fanout: int,
                           leaves: int, mask: np.ndarray):
        """Level-2 refinement: exchange per-crc content stamps for the
        divergent buckets so only keys that actually differ stream —
        the whole-bucket export ships every innocent bystander sharing
        a bucket with a divergent key (~bucket_keys-1 per hit), which
        at the default grain is most of the delta payload.  Returns the
        delta batch, or None to fall back to the whole-bucket export
        (timeout / malformed reply — still a valid delta, just fatter)."""
        from ..store.digest import (KeyStampTable, bucket_key_sel,
                                    masked_key_count)
        node = self.node
        st = node.stats
        sel = bucket_key_sel(node.ks, fanout, leaves, mask)
        if masked_key_count(node.ks, fanout, leaves, mask,
                            key_sel=sel) < \
                getattr(self.app, "delta_stamp_min", 4096):
            # the stamp exchange costs ~12B per listed key; below this
            # scale the whole-bucket export is already small enough that
            # another round can't pay for itself (and may cost MORE than
            # the bytes it saves — pinned by the e2e resync-beats-full
            # assertion at tiny stores).  Gate on the cheap bucket-math
            # count BEFORE building the stamp table: its _key_accum hash
            # pass is O(keyspace), which the common small-divergence
            # delta would pay only to throw away.
            return None
        table = KeyStampTable(node.ks, fanout, leaves, mask, key_sel=sel)
        st.repl_digest_rounds += 1
        self._write(writer, encode_msg(Arr([
            Bulk(DIGEST), Int(token), Int(2), Int(fanout), Int(leaves),
            Bulk(table.crcs.astype("<u4").tobytes()),
            Bulk(table.stamps.astype("<u8").tobytes())])))
        await writer.drain()
        ack = await self._await_digest_ack(token, 2)
        if ack is None:
            log.warning("delta sync %s: no usable key-stamp reply; "
                        "falling back to the whole-bucket delta",
                        self.meta.addr)
            return None
        idx = np.frombuffer(ack, dtype="<i4")
        if len(idx) and (int(idx.min()) < 0 or
                         int(idx.max()) >= len(table.crcs)):
            log.warning("delta sync %s: out-of-range key-stamp reply; "
                        "falling back to the whole-bucket delta",
                        self.meta.addr)
            return None
        log.debug("delta sync %s: %d/%d stamped keys diverged",
                  self.meta.addr, len(idx), len(table.crcs))
        return table.export_batch(node.ks, idx.astype(np.int64))

    async def _send_delta(self, writer) -> Optional[int]:
        """Digest-driven partial resync (the tentpole of the delta
        anti-entropy protocol — see the module header).  Two rounds:
        per-shard rollups, then leaf digests for mismatching shards;
        the divergent buckets stream as a snapshot-format delta file.
        Returns the new send cursor (the delta's watermark), or None
        when the negotiation demoted to a full snapshot."""
        from ..persist.snapshot import NodeMeta, write_snapshot_file
        from ..store.digest import DIGEST_FANOUT, leaves_for
        node = self.node
        app = self.app
        st = node.stats
        meta = self.meta
        if self._digest_acks is None:
            self._digest_acks = asyncio.Queue()
        # watermarks FIRST, digest after: the digested state is then a
        # superset of every op <= repl_last — ops landing in between are
        # in the repl_log above it and replay after the delta, the same
        # redelivery class the shared full-sync dump documents
        # (persist/share.py; coalesced re-applies are idempotent).  The
        # REPLICA RECORDS are part of the same cut: a third-party frame
        # landing during the digest rounds below is in our state but in
        # NO bucket the (already-computed) digests flagged — a record
        # captured after the awaits would claim its origin's watermark
        # anyway, and the receiver's adoption would skip the frame's
        # redelivery forever (found by the chaos harness: one node held
        # a register's stale LWW loser mesh-wide-acked).
        repl_last = getattr(node.repl_log, "landed_last_uuid",
                            node.repl_log.last_uuid)
        records = node.replicas.records()
        fanout = DIGEST_FANOUT
        plane = node.serve_plane
        if plane is not None:
            n_keys = await plane.key_count()
        else:
            n_keys = node.ks.n_keys()
        leaves = leaves_for(n_keys, fanout,
                            max(1, getattr(app, "delta_bucket_keys", 8)))
        self._delta_token += 1
        token = self._delta_token
        matrix = await self._local_digest(fanout, leaves)
        st.repl_digest_rounds += 1
        self._write(writer, encode_msg(Arr([
            Bulk(DIGEST), Int(token), Int(0), Int(fanout), Int(leaves),
            Bulk(matrix.sum(axis=1, dtype=np.uint64)
                 .astype("<u8").tobytes())])))
        await writer.drain()
        ack = await self._await_digest_ack(token, 0)
        if ack is None:
            log.warning("delta sync %s: no usable rollup reply; demoting "
                        "to a full snapshot", meta.addr)
            return None
        shards = np.frombuffer(ack, dtype="<i8")
        buckets = np.zeros(0, dtype=np.int64)
        if len(shards):
            if int(shards.min()) < 0 or int(shards.max()) >= fanout:
                log.warning("delta sync %s: out-of-range shard ids in "
                            "reply; demoting to a full snapshot", meta.addr)
                return None
            shards64 = shards.astype(np.int64)
            st.repl_digest_rounds += 1
            self._write(writer, encode_msg(Arr([
                Bulk(DIGEST), Int(token), Int(1), Int(fanout), Int(leaves),
                Bulk(ack),
                Bulk(matrix[shards64].astype("<u8").tobytes())])))
            await writer.drain()
            ack = await self._await_digest_ack(token, 1)
            if ack is None:
                log.warning("delta sync %s: no usable leaf reply; "
                            "demoting to a full snapshot", meta.addr)
                return None
            buckets = np.frombuffer(ack, dtype="<i8").astype(np.int64)
            if len(buckets) and (int(buckets.min()) < 0 or
                                 int(buckets.max()) >= fanout * leaves):
                log.warning("delta sync %s: out-of-range bucket ids in "
                            "reply; demoting to a full snapshot", meta.addr)
                return None
        # divergence threshold: past this bucket fraction a delta stops
        # paying for itself (the leaf granularity targets ~bucket_keys
        # keys per bucket, so bucket fraction ~ key fraction); demote —
        # and name the shards being demoted, so an operator can see
        # WHERE the mesh diverged
        max_div = getattr(app, "delta_max_divergence", 0.5)
        if len(buckets) > max_div * fanout * leaves:
            dirty_shards = sorted(set((buckets // leaves).tolist()))
            log.warning(
                "delta sync %s: %d/%d buckets diverged (> %.0f%%); "
                "demoting shards %s to a full transfer", meta.addr,
                len(buckets), fanout * leaves, max_div * 100, dirty_shards)
            return None
        mask = np.zeros(fanout * leaves, dtype=bool)
        mask[buckets] = True
        nmeta = NodeMeta(node_id=node.node_id, alias=node.alias,
                         addr=getattr(app, "advertised_addr", ""),
                         repl_last_uuid=repl_last)
        chunk_keys = getattr(app, "snapshot_chunk_keys", 1 << 16)
        level = getattr(app, "snapshot_compress_level", 1)
        if plane is not None:
            # shard-per-core pusher: whole-bucket export via the workers
            # (per-key refinement would need a stamp fan-out RPC; the
            # bucket granularity is already O(divergence) on the wire)
            parts = await plane.export_bucket_payloads(
                fanout, leaves, mask, chunk_keys=chunk_keys)
        else:
            from ..store.digest import export_bucket_batch
            node.ensure_flushed()  # acks were awaited: re-sync the host
            batch = None
            if len(buckets):
                batch = await self._refine_keys(writer, token, fanout,
                                                leaves, mask)
            if batch is None:
                batch = export_bucket_batch(node.ks, fanout, leaves,
                                            mask)
            parts = [batch]
        path = os.path.join(app.work_dir,
                            f"delta.out.{meta.addr.replace(':', '_')}")
        loop = asyncio.get_running_loop()
        # file write off-loop (ASYNC-BLOCK): the captures are already
        # materialized, so the worker thread only encodes + writes
        # negotiated peers receive the delta as the compressed snapshot
        # container (the columnar bucket layout with uuid deltas is
        # highly compressible); the receiver's loader sniffs the magic
        container = getattr(self.app, "bulk_compress_level", 6) \
            if self._bulk_compress() else 0
        size = await loop.run_in_executor(
            None, lambda: write_snapshot_file(
                path, nmeta, records, parts, chunk_keys=chunk_keys,
                compress_level=level, container_level=container))
        if node.oplog is not None and node.oplog.policy != "no":
            # emit-only-durable (persist/oplog.py): every op whose
            # effect is in the captured bucket exports was appended
            # before the capture — group-commit AFTER the capture and
            # BEFORE the stream, so a peer can never hold an op a torn
            # tail could still lose (capture-THEN-commit: a commit
            # taken earlier would not cover ops landing during its own
            # fsync, which the state capture then picks up)
            await node.oplog.ack_barrier()
        try:
            await self._stream_file(writer, path, encode_msg(Arr([
                Bulk(DELTASYNC), Int(size), Int(repl_last),
                Int(len(buckets))])))
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
        st.repl_delta_syncs += 1
        st.repl_delta_bytes += size
        log.info("delta sync %s: %d/%d buckets diverged, %d bytes "
                 "streamed (watermark %d)", meta.addr, len(buckets),
                 fanout * leaves, size, repl_last)
        return repl_last

    # ----------------------------------------------------------------- pull

    async def _pull_loop(self, reader, writer, parser) -> None:
        """Inbound half (reference pull.rs): coalesce replicate frames
        into columnar micro-batches (replica/coalesce.py) and land them
        through the MergeEngine; non-mergeable frames apply per-key as
        barriers; snapshots load chunk-streamed as before.  `writer` is
        the same full-duplex stream's outbound half: digest questions
        from the peer's push loop are ANSWERED here (frames are encoded
        into single atomic writes, so interleaving with our own push
        loop's frames is safe).

        Flush cadence: the applier enforces the frame-count and latency
        bounds; this loop additionally flushes whenever the stream goes
        IDLE (no complete frame left in the parser) before blocking on
        the socket — a lone write lands with zero added latency, and
        batches only form when frames actually queue up."""
        if self.node.serve_plane is not None:
            # shard-per-core node: intake stays here, frames route to
            # the worker owning their key (server/serve_shards.py)
            applier = self.node.serve_plane.make_applier(
                self.meta,
                max_frames=getattr(self.app, "apply_batch", None),
                max_latency=getattr(self.app, "apply_latency", None),
                now=asyncio.get_running_loop().time)
        else:
            from .coalesce import CoalescingApplier
            applier = CoalescingApplier(
                self.node, self.meta,
                max_frames=getattr(self.app, "apply_batch", None),
                max_latency=getattr(self.app, "apply_latency", None),
                now=asyncio.get_running_loop().time)
        # the applier's intake buffer counts toward the governed memory
        # total for the connection's lifetime (server/overload.py)
        gov = self.node.governor
        src = lambda: applier.pending_bytes  # noqa: E731
        gov.register_source(src)
        try:
            await self._pull_frames(reader, writer, parser, applier)
        finally:
            gov.unregister_source(src)

    def _ingest(self, parser, applier):
        """The steady stream's share of one socket read, synchronously:
        every leading REPLICATE / REPLBATCH frame parsed and handed to the
        applier (dup-skip, gap check, buffering, or a wire batch's land).
        -> the first frame of another kind — the awaiting path's — or
        None when the parser holds no complete frame.  One `repl_ingest`
        stage entry per call, not per frame (utils/stagetime.py)."""
        with self.node.stages.stage("repl_ingest"):
            while True:
                msg = parser.next_msg()
                items = msg.items if isinstance(msg, Arr) else None
                if not items:
                    return msg
                kind = as_bytes(items[0]).lower()
                if kind == REPLICATE:
                    applier.apply(items)
                elif kind == REPLBATCH:
                    applier.apply_wire_batch(items)
                else:
                    return msg
                self.meta.last_seen_ms = now_ms()

    async def _pull_frames(self, reader, writer, parser, applier) -> None:
        from .coalesce import CoalescingApplier
        # a shard-routing applier genuinely awaits its workers: its
        # stream frames take the awaiting branches below
        sync = isinstance(applier, CoalescingApplier)
        while True:
            msg = self._ingest(parser, applier) if sync \
                else parser.next_msg()
            if msg is None:
                if applier.pending:
                    await applier.aflush()  # stream idle: land now
                data = await reader.read(_READ_CHUNK)
                if not data:
                    raise ConnectionError("EOF")
                self._count_in(len(data))
                parser.feed(data)
                continue
            self.meta.last_seen_ms = now_ms()
            items = msg.items if isinstance(msg, Arr) else None
            if not items:
                raise CstError(f"unexpected frame from {self.meta.addr}: {msg!r}")
            kind = as_bytes(items[0]).lower()
            if kind == REPLICATE:
                await applier.aapply(items)
            elif kind == REPLBATCH:
                # a group-encoded run: per-batch intake (dup/gap/cursor/
                # beacon once), decoded batch straight into the merge
                # engine (replica/coalesce.py apply_wire_batch).  Only
                # negotiated streams carry these — a ShardApplier (which
                # never advertises CAP_BATCH_STREAM) raises the protocol
                # error that tears this link down.
                await applier.aabatch(items)
            elif kind == REPLACK:
                uuid = as_int(items[1])
                if uuid > self.meta.uuid_i_acked:
                    self.meta.uuid_i_acked = uuid
                    self.node.events.trigger(EVENT_REPLICA_ACKED, uuid)
                if len(items) > 4:
                    # peer's cluster coverage (see manager.ReplicaMeta).
                    # LAST REPORT WINS, decreases included: coverage can
                    # legitimately REGRESS (a new peer joins the mesh
                    # and its stream is unpulled; a state wipe), and
                    # clamping upward would gate tombstone collection on
                    # a stale too-high value — the unsoundness this
                    # field exists to close.  Accepting a decrease is
                    # merely conservative (GC pauses until coverage
                    # recovers); a reconnect-overlap race delivering an
                    # old ack late lowers it briefly, same story.
                    self.meta.coverage = as_int(items[4])
                if len(items) > 3 and \
                        self._epoch == self.node.reset_epoch:
                    # peer's stream is complete below its beacon.  The
                    # epoch check drops beacons from a stream installed
                    # BEFORE a local state wipe: those would re-advance
                    # the zeroed pull watermark past ops the wipe
                    # discarded, silently skipping their re-delivery.
                    # The applier gates the advance behind any frames
                    # still pending (watermark-after-land).
                    applier.observe_beacon(as_int(items[3]))
            elif kind == FULLSYNC:
                await applier.aflush()  # barrier: snapshot handling
                #                         moves the watermark out-of-band
                await self._receive_snapshot(
                    reader, parser, size=as_int(items[1]),
                    repl_last=as_int(items[2]),
                    reset=bool(as_int(items[3])) if len(items) > 3 else False)
                applier.resync()
            elif kind == DELTASYNC:
                await applier.aflush()  # barrier, like FULLSYNC
                await self._receive_delta(
                    reader, parser, size=as_int(items[1]),
                    repl_last=as_int(items[2]),
                    buckets=as_int(items[3]) if len(items) > 3 else 0)
                applier.resync()
            elif kind == DIGEST:
                if not getattr(self.app, "delta_sync", True):
                    # CONSTDB_DELTA_SYNC=0 kills the responder leg too:
                    # we did not advertise CAP_DELTA_SYNC, so a
                    # conforming peer never asks — but a nonconforming
                    # one must not make us pay the O(keyspace) fold the
                    # operator switched off (it times out into its full-
                    # snapshot fallback)
                    log.warning("digest question from %s ignored: "
                                "CONSTDB_DELTA_SYNC=0", self.meta.addr)
                    continue
                if self._stream_lock.locked():
                    # our own push loop is mid raw-payload window on this
                    # writer: the answer would be dropped anyway (see
                    # _answer_digest's final check, which still guards
                    # the race where the lock is taken during the flush
                    # below) — skip EARLY, before paying the applier
                    # flush and the O(keyspace) digest fold just to
                    # discard the result
                    log.warning("digest question from %s skipped: local "
                                "push loop is mid-stream (peer will "
                                "demote to full sync)", self.meta.addr)
                    continue
                # the peer's push loop is asking where we diverge: the
                # answer must cover every frame already intaken, so land
                # them first (digest-over-pending would flag buckets the
                # pending flush is about to fix)
                await applier.aflush()
                await self._answer_digest(items, writer)
            elif kind == DIGESTACK:
                # reply to OUR push loop's digest question (bridged)
                if self._digest_acks is not None and len(items) >= 4:
                    self._digest_acks.put_nowait(items)
            elif kind == CLUSTERTAB:
                # slot-table gossip (cluster/slots.py): per-slot JOIN —
                # higher (slot_epoch, gid) wins per slot, so a stale or
                # concurrently-minted table merges instead of clobbering
                # (epoch-gated routing is what keeps a flapped owner
                # from resurrecting a stale assignment).  Only
                # cluster-mode peers send these (we advertised
                # CAP_CLUSTER); a disabled node treats one as the
                # protocol error it is, like any unknown frame.
                cl = self.node.cluster
                if cl is None:
                    raise CstError("clustertab frame on a non-cluster "
                                   "node (capability mismatch)")
                if len(items) > 2:
                    from ..cluster.slots import SlotTable
                    table = SlotTable.deserialize(as_bytes(items[2]))
                    if cl.adopt(table):
                        log.info("adopted slot table epoch %d from %s",
                                 table.epoch, self.meta.addr)
            elif kind == PARTSYNC:
                pass  # stream continues from our requested resume point
            else:
                raise CstError(f"unknown repl frame {kind!r}")

    async def _answer_digest(self, items: list, writer) -> None:
        """Answer one of the peer's digest questions (the puller leg of
        the delta anti-entropy protocol): compare the received digests
        against this node's own and reply with the mismatching shard ids
        (level 0) / flat bucket indices (level 1).  The level-0 matrix is
        CACHED for the round so both levels compare one consistent state
        cut — anything landing in between is either ours (the peer does
        not need to send it) or will redeliver through the stream."""
        from ..store.digest import MAX_BUCKETS
        token = as_int(items[1])
        level = as_int(items[2])
        fanout = as_int(items[3])
        leaves = as_int(items[4])
        if fanout < 1 or leaves < 1 or fanout * leaves > MAX_BUCKETS or \
                len(items) < 6:
            raise CstError(f"bad digest geometry from {self.meta.addr}: "
                           f"{fanout}x{leaves}")
        cache_key = (token, fanout, leaves)
        if level in (0, 1):
            cached = self._digest_cache
            if cached is not None and cached[0] == cache_key:
                matrix = cached[1]
            else:
                matrix = await self._local_digest(fanout, leaves)
                self._digest_cache = (cache_key, matrix)
        if level == 0:
            theirs = np.frombuffer(as_bytes(items[5]), dtype="<u8")
            if len(theirs) != fanout:
                raise CstError(f"digest rollup size mismatch from "
                               f"{self.meta.addr}")
            mine = matrix.sum(axis=1, dtype=np.uint64)
            reply = np.nonzero(mine != theirs)[0].astype("<i8").tobytes()
            if not reply:
                # every rollup matched: the peer skips level 1, so this
                # round is over — release the matrix now instead of
                # pinning up to 32MB on the long-lived link until the
                # next negotiation
                self._digest_cache = None
        elif level == 1 and len(items) >= 7:
            shards = np.frombuffer(as_bytes(items[5]), dtype="<i8")
            sub = np.frombuffer(as_bytes(items[6]), dtype="<u8")
            if len(sub) != len(shards) * leaves or \
                    (len(shards) and (int(shards.min()) < 0 or
                                      int(shards.max()) >= fanout)):
                raise CstError(f"digest refinement shape mismatch from "
                               f"{self.meta.addr}")
            shards64 = shards.astype(np.int64)
            mine = matrix[shards64]
            srow, leaf = np.nonzero(mine != sub.reshape(len(shards),
                                                        leaves))
            reply = (shards64[srow] * leaves + leaf).astype("<i8").tobytes()
            self._digest_cache = None  # matrix rounds complete
        elif level == 2 and len(items) >= 7:
            # per-key stamp refinement: which of the peer's listed keys
            # actually differ here (store/digest.py KeyStampTable)
            crcs = np.frombuffer(as_bytes(items[5]),
                                 dtype="<u4").astype(np.uint64)
            stamps = np.frombuffer(as_bytes(items[6]), dtype="<u8")
            if len(crcs) != len(stamps):
                raise CstError(f"key-stamp table shape mismatch from "
                               f"{self.meta.addr}")
            if self.node.serve_plane is not None:
                # sharded puller: per-key stamps would need a worker
                # fan-out — select every offered key instead (exactly
                # the whole-bucket byte cost, still convergent: the
                # re-merge of an equal key is idempotent)
                sel = np.arange(len(crcs), dtype=np.int64)
            else:
                self.node.ensure_flushed()
                from ..store.digest import stamp_mismatch_indices
                sel = stamp_mismatch_indices(self.node.ks, crcs, stamps)
            reply = sel.astype("<i4").tobytes()
        else:
            raise CstError(f"unknown digest level {level} from "
                           f"{self.meta.addr}")
        if self._stream_lock.locked():
            # our own push loop is mid raw-payload window on this
            # writer.  Blocking here could cross-deadlock two symmetric
            # resyncs (each side streaming, each pull loop parked on its
            # lock, nobody reading); drop the answer instead — the
            # peer's negotiation times out and demotes to a full
            # snapshot, the designed-safe fallback.
            log.warning("digest answer to %s dropped: local push loop "
                        "is mid-stream (peer will demote to full sync)",
                        self.meta.addr)
            return
        self._write(writer, encode_msg(Arr([
            Bulk(DIGESTACK), Int(token), Int(level), Bulk(reply)])))
        # no drain() here ON PURPOSE: the pull loop is this connection's
        # only reader, and parking it on flow control while the peer's
        # pull loop is symmetrically parked on ITS ack (two simultaneous
        # resyncs whose level-2 acks both exceed the socket buffers)
        # deadlocks the pair — neither side reads, neither drain ever
        # completes.  The ack is one bounded frame the negotiating peer
        # reads promptly; the transport buffers it in the meantime.

    async def _receive_snapshot(self, reader, parser, size: int,
                                repl_last: int, reset: bool = False) -> None:
        """Download to a spill file, then stream chunks through the
        MergeEngine, yielding between chunks to keep the loop live
        (reference pull.rs:35-85, at columnar scale).

        `reset`: the pusher excluded us from its GC horizon and our resume
        point fell off its repl_log — tombstones we never saw are gone, so
        a plain merge would let our stale keys resurrect mesh-wide.  Wipe
        local state first (Node.reset_for_full_resync) and rejoin from the
        snapshot like a fresh node."""
        self._spill_seq += 1
        path = os.path.join(
            self.app.work_dir,
            f"snapshot.{self.meta.addr.replace(':', '_')}"
            f".{self._spill_seq}")
        try:
            await self._download_spill(reader, parser, size, path)
            node = self.node
            if reset:
                log.warning("peer %s demands a state-clearing resync (we "
                            "were excluded from its GC horizon past the "
                            "repl_log window); wiping local state",
                            self.meta.addr)
                if node.serve_plane is not None:
                    await node.serve_plane.reset_for_resync(keep_link=self)
                else:
                    node.reset_for_full_resync(keep_link=self)
                # THIS stream stays valid: the snapshot below + the
                # gap-free frames that follow it re-establish our pull
                # position
                self._epoch = node.reset_epoch
            applied_rows, replica_rows = await self._apply_spill_loud(
                path, size)
            self._finish_sync(path, applied_rows, replica_rows, repl_last,
                              "snapshot")
            # the synced table is the node's until a wipe: no later
            # collection walks it again
            await self.app.freeze_heap()
        finally:
            # per-download spill names are never overwritten by a retry,
            # so EVERY exit — a torn download included — must drop the
            # file (ENOENT after the success path's unlink is fine)
            try:
                os.unlink(path)
            except OSError:
                pass

    async def _receive_delta(self, reader, parser, size: int,
                             repl_last: int, buckets: int) -> None:
        """Apply a digest-negotiated delta stream: the divergent
        buckets' whole state in snapshot format, merged through the same
        chunk-streamed path a full snapshot takes (merges are
        idempotent/commutative, so bucket-scoped re-merges are plain
        merges).  Watermark + replica-record adoption follow the same
        snapshot-backed discipline (_finish_sync): after the merge our
        state covers everything the pusher had at `repl_last`, because
        every bucket whose digests disagreed was just streamed and every
        bucket whose digests agreed already held identical state."""
        self._spill_seq += 1
        path = os.path.join(
            self.app.work_dir,
            f"delta.in.{self.meta.addr.replace(':', '_')}"
            f".{self._spill_seq}")
        try:
            await self._download_spill(reader, parser, size, path)
            applied_rows, replica_rows = await self._apply_spill_loud(
                path, size)
            self._finish_sync(path, applied_rows, replica_rows, repl_last,
                              f"delta ({buckets} buckets)")
        finally:
            try:  # see _receive_snapshot: every exit drops the spill
                os.unlink(path)
            except OSError:
                pass

    async def _download_spill(self, reader, parser, size: int,
                              path: str) -> None:
        """Download `size` raw stream bytes to a spill file."""
        loop = asyncio.get_running_loop()
        # spill-file open/close off-loop (ASYNC-BLOCK): close flushes the
        # buffered tail to disk, which on a loaded disk blocks for real;
        # the per-piece writes land in the page cache between awaits
        f = await loop.run_in_executor(None, open, path, "wb")
        try:
            remaining = size
            while remaining > 0:
                got = parser.take_raw(min(remaining, _READ_CHUNK))
                if not got:
                    got = await reader.read(min(remaining, _READ_CHUNK))
                    if not got:
                        raise ConnectionError("EOF during sync download")
                    self._count_in(len(got))
                f.write(got)
                remaining -= len(got)
        finally:
            try:
                await loop.run_in_executor(None, f.close)
            except asyncio.CancelledError:
                f.close()  # teardown path: close inline rather than leak
                raise

    async def _apply_spill_loud(self, path: str, size: int):
        """`_apply_spill` with the compression-demotion discipline: a
        raw window that arrived as a compressed container but failed
        validation demotes THIS peer's compression loudly
        (repl_compress_demotions counting + compress_wire_off, so the
        CAP_COMPRESS invitation disappears from the next handshake and
        the retried window arrives plain).  Deliberately NOT counted
        into repl_wire_demotions: the chaos accounting law ties that
        gauge to injected REPLBATCH corruption, and a window can fail
        validation without any peer malice (e.g. a reconnect-overlap
        race interleaving two downloads) — the demotion is then merely
        conservative (speed, never state).  The watermark is untouched
        either way — `_finish_sync` only runs on success, so the whole
        window redelivers idempotently after the teardown."""
        try:
            return await self._apply_spill(path, size)
        except (InvalidSnapshot, InvalidSnapshotChecksum):
            # head sniff off-loop (ASYNC-BLOCK), like every other spill
            # read on this path
            loop = asyncio.get_running_loop()
            head = b""
            try:
                f = await loop.run_in_executor(None, open, path, "rb")
                try:
                    head = await loop.run_in_executor(None, f.read, 8)
                finally:
                    f.close()
            except OSError:
                pass
            from ..utils.compressio import is_compressed
            if is_compressed(head):
                x = self.node.stats.extra
                x["repl_compress_demotions"] = \
                    x.get("repl_compress_demotions", 0) + 1
                self.meta.compress_wire_off = True
                log.error(
                    "compressed sync window from %s failed validation; "
                    "demoting this peer to plain transfers and retrying "
                    "from the untouched watermark", self.meta.addr)
            raise

    async def _apply_spill(self, path: str, size: int):
        """Merge a downloaded snapshot-format spill file through
        whichever apply machinery this node runs — the serve plane
        (workers ARE the store), the process-parallel sharded ingest, or
        the plain chunk-streamed path.  -> (applied_rows, replica_rows)."""
        node = self.node
        if node.serve_plane is not None:
            # shard-per-core node: sections fan out to the serve workers
            # by key hash (server/serve_shards.py) — they ARE the store
            return await self._apply_snapshot_via_plane(path)
        if (shards := self.app.snapshot_ingest_shards(size)) > 1:
            log.info("sharded snapshot ingest from %s: %d bytes over %d "
                     "shard workers", self.meta.addr, size, shards)
            return await self._apply_snapshot_sharded(path, shards)
        return await self._apply_snapshot_plain(path)

    def _finish_sync(self, path: str, applied_rows: int, replica_rows,
                     repl_last: int, what: str) -> None:
        """Post-apply bookkeeping shared by full and delta syncs: the
        stream just re-based us to the pusher's state at `repl_last`."""
        node = self.node
        if replica_rows:
            # transitive mesh join (reference pull.rs:136-153) + watermark
            # adoption, now that the state backing them is fully merged
            node.replicas.merge_records(replica_rows,
                                        my_addr=self.app.advertised_addr,
                                        adopt_watermarks=True)
        if repl_last > self.meta.uuid_he_sent:
            self.meta.uuid_he_sent = repl_last
        node.hlc.observe(repl_last)
        if node.oplog is not None:
            # bulk-delivered state is NOT in the durable op log: stop
            # persisting watermark records (they would claim coverage
            # the log cannot replay) and schedule a rewrite to re-base
            # the log on a snapshot covering it (persist/oplog.py)
            node.oplog.note_bulk_sync()
        log.info("loaded %s from %s: %d rows", what, self.meta.addr,
                 applied_rows)
        try:
            os.unlink(path)
        except OSError:
            pass

    async def _apply_batches(self, batches) -> int:
        """Merge a stream of columnar batches into the node under the
        grouped-apply cadence: accumulate up to `sync_merge_group` chunks
        and merge them in ONE engine call (Node.merge_batches → engine
        merge_many: aligned groups fold in a fused [R, N] device pass;
        unaligned ones still share one state roundtrip per family —
        reference pull.rs:66-74 batches ≤32 entries per apply for the same
        reason).  Adaptive liveness: if a call overruns the budget the
        group shrinks, then chunks SPLIT (batch_chunks re-chunks any
        batch) so a CPU-engine catch-up never wedges the event loop on
        one 64Ki-key merge.  Shared by the plain snapshot apply AND the
        sharded-ingest consolidation.  Returns rows applied."""
        node = self.node
        applied_rows = 0
        group: list = []
        max_group = max(1, self.app.sync_merge_group)
        budget = self.app.sync_merge_budget
        target = 1
        # ramp UP from small sub-chunks so the first call can never wedge
        # the loop, regardless of engine speed: fast calls first grow the
        # split size to whole chunks, then the group size to max_group;
        # slow calls walk the same ladder back down
        split_keys = max(0, self.app.sync_initial_split)
        did_split = False  # did the CURRENT group actually get sub-chunked?
        loop = asyncio.get_running_loop()

        async def apply_group() -> None:
            nonlocal applied_rows, target, split_keys, did_split
            if not group:
                return
            t0 = loop.time()
            node.merge_batches(group)
            dt = loop.time() - t0
            applied_rows += sum(b.n_rows for b in group)
            if dt > budget:
                if target > 1:
                    target = max(1, target // 2)
                elif split_keys == 0:
                    split_keys = 1 << 15
                else:
                    split_keys = max(1024, split_keys // 2)
            elif dt < budget / 4:
                if split_keys and did_split:
                    # splitting is ACTIVE: widen the sub-chunks first
                    split_keys <<= 1
                    if split_keys >= (1 << 17):
                        split_keys = 0  # chunks applied whole from here on
                elif target < max_group:
                    # chunks already apply whole (stream chunks smaller
                    # than the split, or the split ramped out): grow the
                    # GROUP — doubling an inactive split would burn the
                    # whole ramp budget without changing a single call
                    target = min(max_group, target * 2)
            group.clear()
            did_split = False
            await asyncio.sleep(0)

        for payload in batches:
            if split_keys and payload.n_keys > split_keys:
                for sub in batch_chunks(payload, split_keys):
                    # per sub-chunk, not per payload: apply_group resets
                    # the flag at every group boundary, and the LATER
                    # groups of this payload's sub-chunks must still
                    # classify as split-active (else the controller grows
                    # the group while splitting is still happening,
                    # inverting the documented ramp order)
                    did_split = True
                    group.append(sub)
                    if len(group) >= target:
                        await apply_group()
            else:
                group.append(payload)
            if len(group) >= target:
                await apply_group()
        await apply_group()
        return applied_rows

    async def _apply_snapshot_via_plane(self, path: str):
        """Snapshot apply on a shard-per-core serving node: decoded
        sections fan out to the serve workers by key hash
        (ServeShardPlane.ingest_batches awaits per section, so the loop
        stays live), node/replica sections are handled exactly like the
        plain path."""
        plane = self.node.serve_plane
        f = await asyncio.get_running_loop().run_in_executor(
            None, open, path, "rb")
        demux = SectionDemux(f)
        try:
            applied_rows = await plane.ingest_batches(demux.batches())
        finally:
            f.close()
        self._adopt_peer_id(demux)
        return applied_rows, demux.replica_rows

    def _adopt_peer_id(self, demux: SectionDemux) -> None:
        """Backfill the peer's node id from its snapshot meta (a peer
        met by address only identifies itself here)."""
        if demux.meta is not None and demux.meta.node_id \
                and not self.meta.node_id:
            self.meta.node_id = demux.meta.node_id

    async def _apply_snapshot_plain(self, path: str):
        """Single-keyspace snapshot apply (the default path).  Replica
        records are held until the WHOLE snapshot is applied —
        merge_records adopts the recorded pull watermarks, which are
        only backed by state once every chunk has merged (SectionDemux
        defers them until its generator is exhausted)."""
        # spill-file open off-loop (ASYNC-BLOCK); section reads stay
        # inline — they are small page-cache slices between awaits
        f = await asyncio.get_running_loop().run_in_executor(
            None, open, path, "rb")
        demux = SectionDemux(f)
        try:
            applied_rows = await self._apply_batches(demux.batches())
        finally:
            f.close()
        self._adopt_peer_id(demux)
        return applied_rows, demux.replica_rows

    async def _apply_snapshot_sharded(self, path: str, shards: int):
        """Process-parallel snapshot apply (store/sharded_keyspace.py):
        fan RAW batch sections out by key hash to shard worker processes
        — they decode, hash, and merge in parallel while this loop keeps
        serving — then consolidate each shard's merged (deduplicated)
        state into the serving keyspace through the node's own engine,
        re-chunked through the grouped-apply cadence so no single merge
        wedges the event loop."""
        from ..store.sharded_keyspace import ShardedKeySpace
        node = self.node
        loop = asyncio.get_running_loop()
        sks = ShardedKeySpace(n_shards=shards, mode="process",
                              engine_spec="cpu",
                              group=max(1, self.app.sync_merge_group))
        x = node.stats.extra
        x["sharded_ingests"] = x.get("sharded_ingests", 0) + 1
        x["sharded_ingest_workers"] = shards
        applied_rows = 0
        replica_rows: list = []
        try:
            # spill-file open off-loop, like every other blocking step of
            # this path (submit/flush/export below)
            f = await loop.run_in_executor(None, open, path, "rb")
            demux = SectionDemux(f, raw_batches=True)
            try:
                for payload in demux.batches():
                    # submit can block on the pool's bounded in-flight
                    # window — run it off-loop so pulls/acks keep
                    # flowing while completions land
                    await loop.run_in_executor(None, sks.submit_raw,
                                               payload)
            finally:
                f.close()
            self._adopt_peer_id(demux)
            replica_rows = demux.replica_rows
            await loop.run_in_executor(None, sks.flush)
            # consolidation rides the SAME adaptive grouped-apply cadence
            # as the plain path — a whole-shard export through a slow
            # engine must not wedge the loop any more than a snapshot
            # chunk may.  Streamed shard by shard with free=True: the
            # worker's copy of a shard is dropped the moment its export
            # lands, so peak residency is the serving keyspace plus ONE
            # shard, not 2x the whole snapshot.
            applied_rows = 0
            for s in range(shards):
                b = await loop.run_in_executor(
                    None, sks.export_shard_batch, s, True)
                if b.n_rows or b.del_keys:
                    applied_rows += await self._apply_batches(iter([b]))
        finally:
            await loop.run_in_executor(None, sks.close)
        return applied_rows, replica_rows


async def _read_msg(reader: asyncio.StreamReader, parser: RespParser,
                    timeout: Optional[float] = None, count=None):
    """Next complete RESP message from the stream; `count` observes raw
    byte arrivals (replication byte accounting)."""
    while True:
        msg = parser.next_msg()
        if msg is not None:
            return msg
        coro = reader.read(_READ_CHUNK)
        data = await (asyncio.wait_for(coro, timeout) if timeout else coro)
        if not data:
            raise ConnectionError("EOF")
        if count is not None:
            count(len(data))
        parser.feed(data)
