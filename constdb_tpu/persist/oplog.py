"""Durable op log (AOF): group-commit append-only segments with
certified crash recovery.

Every repl-log append — client writes and replicated intake alike — is
mirrored here as a crc-framed record, so a `kill -9` between snapshot
dumps no longer loses acknowledged writes.  The design leans on three
things the codebase already certifies:

  * **payloads ARE the columnar wire encoding** (replica/wire.py): a
    serve-coalescer run is group-encoded ONCE into the exact REPLBATCH
    payload the push loops would build (and the finished encoding is
    published into the encode-once cache, replica/encode_cache.py, so
    the fan-out splices it instead of re-encoding); a received REPLBATCH
    payload is spliced into the log verbatim (it was just crc-validated
    by the decoder).  Everything else — barriers, lone writes, demoted
    runs — mirrors as per-frame RESP records.
  * **boot replay routes through the real apply path**: batch records
    decode with `decode_wire_batch` and land via
    `Node.merge_stream_batch`; frame records group-encode through the
    same `COLUMNAR_ENCODERS`/`BatchBuilder` machinery the live
    replication coalescer uses, with non-encodable frames applying as
    `apply_replicated` barriers.  There is no second apply
    implementation to drift.
  * **watermark/state consistency cuts** (docs/INVARIANTS.md): replica
    watermark records (WMARK) are appended AFTER the frames they cover
    — `uuid_he_sent` only advances at land, and frames mirror at land —
    so any valid log PREFIX (which is all torn-tail repair can leave)
    contains every frame its surviving watermark records claim.  A
    recovered node can never claim pull coverage of frames its log
    never held.

Record framing (little-endian):

    segment header   b"CSTAOF1\\n"
    record*          u32 len | u32 crc32(body) | body
    body             u8 type + payload

    BATCH payload    uvarint origin, base, last, n  + wire payload
    FRAME payload    uvarint origin, uuid           + RESP Arr(name,*args)
    WMARK payload    uvarint own_landed_uuid        + REPLICAS section

Torn-tail repair: recovery scans to the last valid record boundary and
truncates the torn suffix LOUDLY (`aof_tail_truncated` gauge + log
line).  A record either validates whole (length bound + crc + known
type) or ends the valid prefix — a bit-flipped or half-written record
is never replayed (tests/test_oplog.py sweeps every offset).

Group-commit fsync (`CONSTDB_AOF_FSYNC`):

    always    a serve chunk is acknowledged only after its covering
              fsync lands — server/io.py awaits `ack_barrier()` before
              flushing replies, riding the serve coalescer's existing
              end-of-chunk flush barrier, so one fsync covers the whole
              pipelined chunk (group commit).
    everysec  a background fsync every second (the cron tick drives
              it); a power loss can cost up to the last second.
    no        the OS decides (records are still written through).

**Emit-only-durable law**: the push stream never advertises an op the
log has not yet made durable — `durable_floor()` plugs into the repl
log's floor discipline (the same gating MergedReplLog uses for
minted-but-unlanded writes), so a peer can never hold an op this node
could still lose to a torn tail.  Crash recovery therefore loses, at
most, ops that (a) were never fsync-acknowledged and (b) no peer ever
saw — exactly the set the chaos oracle prunes from its journal
obligation (chaos/oracle.py `prune_origin`).

Log-rewrite compaction (`CONSTDB_AOF_REWRITE_PCT`): when the log grows
past the configured fraction over its post-rewrite base size, the node
captures a consistent state cut on the loop, switches appends to a
fresh segment GENERATION, writes the cut as a durable base snapshot
(the same tmp + rename + parent-fsync recipe every dump site uses —
persist/snapshot.py), commits the new generation in the meta file, and
deletes the old generation.  A crash at any point replays base + every
surviving generation in order — idempotent CRDT merges make the overlap
harmless.

Out-of-log state (full/delta sync, bulk ingest) cannot be replayed from
the log; `note_bulk_sync()` suppresses watermark records (a WMARK
claiming bulk-delivered coverage would skip redelivery of state the log
never held) and schedules an immediate rewrite to re-base the log on a
snapshot that covers it.  A state WIPE (`on_wipe`) discards every
record and fences recovery so peers full-sync a crashed-mid-resync node
instead of resurrecting pre-wipe state.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
import zlib
from typing import Optional

from ..errors import CstError
from ..utils.varint import VarintReader, read_uvarint, write_uvarint

log = logging.getLogger(__name__)

MAGIC = b"CSTAOF1\n"
REC_BATCH = 1
REC_FRAME = 2
REC_WMARK = 3

_REC_TYPES = frozenset((REC_BATCH, REC_FRAME, REC_WMARK))
# a stored record larger than this is corruption, not data
_MAX_RECORD = 1 << 30
# drain the per-segment append buffer to the OS past this many bytes
_BUF_FLUSH = 1 << 16
# min consecutive encodable serve-run ops before the run mirrors as ONE
# columnar batch record (mirrors replica/link.py _MIN_WIRE_RUN)
_MIN_BATCH_RUN = 2

FSYNC_POLICIES = ("always", "everysec", "no")
_EVERYSEC = 1.0
# force a WMARK record (and with it a fresh durable HLC mark) once the
# clock has advanced this far since the last one, even with no
# watermark movement — HLC uuids carry wall-ms in their high bits, so
# this is ~0.5s of clock travel
_WMARK_HLC_STRIDE = 500 << 22
# boot-replay bulk-merge rounds (CONSTDB_RECOVER_BULK): decoded records
# accumulate until this many columnar rows, then land through ONE
# engine merge_many call.  The budget is pinned AT the host
# micro-strategy ceiling (engine/hostbatch.py HOST_MICRO_MAX): one row
# past it and the CPU engine routes the round onto its per-row
# reference loop — the very path bulk replay exists to avoid — so
# rounds close BEFORE a push would cross it, never after
_REPLAY_ROUND_ROWS = 1 << 15
# op-stream frames buffered per columnar encode in bulk replay: larger
# than the live coalescer's 512 because boot replay has no latency
# bound — fewer, wider group-encode runs (serial replay buffers
# nothing: one apply per record, the reference path)
_REPLAY_BULK_FRAMES = 1 << 13
# progress log cadence during a long replay (ops between lines), so a
# multi-minute restart is observable instead of silent
_REPLAY_PROGRESS_EVERY = 200_000


class OpLogError(CstError):
    """Unreadable op log (bad header/meta) — quarantine class."""


def _pack_record(rtype: int, payload: bytes) -> bytes:
    body = bytes([rtype]) + payload
    return (len(body).to_bytes(4, "little")
            + zlib.crc32(body).to_bytes(4, "little") + body)


def scan_segment(path: str, classes: tuple = (), raw: bool = False):
    """-> (records, valid_bytes, total_bytes).  `records` is the maximal
    valid prefix as (rtype, payload bytes); `valid_bytes` is the offset
    of the first invalid byte (== total when the file is whole).  A
    missing/short/wrong magic header raises OpLogError — that file is
    UNREADABLE, not torn (the boot-quarantine satellite distinguishes
    the two).

    The per-record walk (framing + crc + rtype gate) runs in the native
    extension when built — one C call per segment instead of ~9us of
    interpreter dispatch per record.  `classes`: the six RESP message
    classes (`_frame_ctx()[1:]`); when given AND the native scanner is
    available, REC_FRAME records whose payload decodes cleanly come
    back pre-decoded as `(REC_FRAME, origin, uuid, name, args)`
    5-tuples (no payload bytes object, no second parse pass) — any
    anomaly degrades that record to the raw `(rtype, payload)` shape
    so the Python reference decode accepts-or-skips it unchanged.
    `raw` (bulk replay only): flat all-bulk command frames decode to
    PLAIN BYTES args instead of Bulk objects — the columnar encoders
    unwrap every argument anyway, so the wrappers are pure overhead
    there; the arg coercions (resp/message.py as_bytes/as_int/as_uint)
    pass bytes through, and _ReplayApplier re-wraps before any
    reference apply."""
    with open(path, "rb") as f:
        data = f.read()
    n = len(data)
    if n < len(MAGIC) or data[:len(MAGIC)] != MAGIC:
        raise OpLogError(f"bad oplog segment header: {path}")
    from ..resp.codec import _ext
    ext = _ext()
    if ext is not None and hasattr(ext, "aof_scan"):
        flags = (1,) if (raw and classes) else ()
        records, pos = ext.aof_scan(data, len(MAGIC), _MAX_RECORD,
                                    *classes, *flags)
        return records, pos, n
    records = []
    pos = len(MAGIC)
    while pos + 8 <= n:
        ln = int.from_bytes(data[pos:pos + 4], "little")
        if ln < 1 or ln > _MAX_RECORD or pos + 8 + ln > n:
            break
        crc = int.from_bytes(data[pos + 4:pos + 8], "little")
        body = data[pos + 8:pos + 8 + ln]
        if zlib.crc32(body) != crc or body[0] not in _REC_TYPES:
            break
        records.append((body[0], body[1:]))
        pos += 8 + ln
    return records, pos, n


# ------------------------------------------------------------------ meta

def _write_meta(path: str, fields: dict) -> None:
    """Atomic + durable tiny key=value meta file (the rename recipe
    every dump site shares — persist/snapshot.py)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        for k, v in fields.items():
            f.write(f"{k}={v}\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    from .snapshot import _fsync_parent_dir
    _fsync_parent_dir(path)


def _read_meta(path: str) -> dict:
    out: dict = {}
    try:
        with open(path) as f:
            for line in f:
                k, sep, v = line.strip().partition("=")
                if sep:
                    out[k] = v
    except OSError:
        pass
    return out


class RecoveryInfo:
    """What boot replay found and did (INFO Durability mirrors this)."""

    __slots__ = ("source", "frames", "batches", "batch_frames", "wmarks",
                 "skipped", "tail_truncated", "truncated_bytes",
                 "quarantined", "wmark_unsafe", "local_max",
                 "replayed_max", "fence", "hlc_mark", "mode", "shards",
                 "merge_rounds", "restore_to", "restore_skipped")

    def __init__(self) -> None:
        self.source = "empty"
        self.frames = 0
        self.batches = 0
        self.batch_frames = 0
        self.wmarks = 0
        self.skipped = 0           # corrupt/erroring ops never replayed
        self.tail_truncated = 0    # segments whose tail was torn
        self.truncated_bytes = 0
        self.quarantined = 0       # unreadable segments renamed aside
        # True when adopting the log's watermark records would be
        # UNSOUND: a quarantined base snapshot / segment may have held
        # frames a surviving WMARK claims, so recovery keeps watermarks
        # at zero and lets the peers resync us instead of skipping
        # redelivery (the consistency-cut law, inverted)
        self.wmark_unsafe = False
        self.local_max = 0         # newest LOCAL-origin uuid replayed
        self.replayed_max = 0      # newest uuid of ANY origin replayed
        self.fence = 0
        # newest durable HLC mark (WMARK records): the highest beacon
        # any peer can have seen — recovery re-observes it so post-
        # crash mints can never dip below a pre-crash beacon promise
        self.hlc_mark = 0
        # how the replay ran (INFO Recovery gauges): "serial" is the
        # per-record reference path, "bulk" the merge-round path,
        # "bulk+shards<N>" the concurrent per-segment plane replay
        self.mode = "serial"
        self.shards = 1            # replay concurrency actually used
        self.merge_rounds = 0      # bulk merge_many rounds landed
        self.restore_to = 0        # point-in-time target uuid (0 = full)
        self.restore_skipped = 0   # ops past the target, not replayed


class OpLog:
    """One node's durable op log (module docstring).  All append entry
    points run on the event loop (the single writer); only fsync leaves
    it (asyncio.to_thread), against raw unbuffered file objects."""

    def __init__(self, aof_dir: str, n_segments: int = 1,
                 fsync_policy: str = "everysec",
                 rewrite_pct: int = 100,
                 rewrite_min_bytes: int = 16 << 20,
                 checkpoint_secs: float = 0.0,
                 checkpoint_min_bytes: int = 1 << 20,
                 node=None) -> None:
        if fsync_policy not in FSYNC_POLICIES:
            raise ValueError(f"CONSTDB_AOF_FSYNC must be one of "
                             f"{FSYNC_POLICIES}, not {fsync_policy!r}")
        self.dir = aof_dir
        self.n_segments = max(1, n_segments)
        self.policy = fsync_policy
        self.rewrite_pct = max(0, rewrite_pct)
        self.rewrite_min_bytes = max(1 << 20, rewrite_min_bytes)
        # incremental checkpoints (CONSTDB_CHECKPOINT_SECS): the rewrite
        # machinery time-triggered — a periodic consistent base snapshot
        # + generation cut so a restart replays only the tail.  0 = off
        # (growth-triggered rewrites still run).  min_bytes keeps an
        # idle node from churning snapshots on a clock cadence alone.
        self.checkpoint_secs = max(0.0, checkpoint_secs)
        self.checkpoint_min_bytes = max(0, checkpoint_min_bytes)
        self.node = node
        os.makedirs(aof_dir, exist_ok=True)
        meta = _read_meta(self.meta_path(aof_dir))
        self.generation = int(meta.get("gen", 0) or 0)
        self._files: list = []
        self._bufs: list[bytearray] = []
        self.sizes: list[int] = []
        self._open_generation(self.generation, resume=True)
        # size of the log right after the last rewrite (the growth base)
        self.base_size = int(meta.get("base_size", 0) or 0) \
            or self.size_bytes()
        # durability tracking: pending local ops not yet covered by the
        # policy's durability point (fsync, or plain write under "no").
        # FIFO in append order; the floor is the min pending uuid.
        from collections import deque
        # pending entries carry a monotone sequence stamp so a settle
        # releases exactly the entries its capture covered — concurrent
        # commits (an ack barrier in flight while a rewrite/shutdown
        # sync runs) must never release entries appended after their
        # own capture
        self._seq = 0
        self._pend: deque = deque()          # (seq, local uuid)
        self._pend_min: Optional[int] = None
        # replicated-intake records not yet durable, per origin: the
        # REPLACK/coverage cap (cap_ack/cap_coverage) — a pull
        # watermark may only be ADVERTISED once the frames it covers
        # are in the log's durable prefix, or a torn tail could clip
        # frames a peer already believes we hold (and its GC would
        # collect tombstones we then never see again)
        self._intake_pend: dict[int, deque] = {}
        # cached min uuid per origin's pending deque: cap_ack runs on
        # EVERY ack-loop wake (per delivered batch under firehose), so
        # the cap must be O(1) — maintained at append, recomputed only
        # when a settle releases entries (reconnect redeliveries can
        # append BELOW the current min, so the deque is not monotonic
        # and d[0] alone is not the answer)
        self._intake_min: dict[int, int] = {}
        # durable HLC mark: the newest hlc value stored in a WMARK
        # record a completed group commit covers.  Outgoing REPLACK
        # beacons are CAPPED at it (replica/link.py): a beacon is the
        # promise "every uuid I will ever mint from now on exceeds B" —
        # a crash that rewinds the clock below an uncapped beacon makes
        # peers dup-skip the re-minted window forever (found by the
        # chaos everysec cell: a torn-crashed node re-minted uuids
        # below its own pre-crash beacon after a peer's clock jump had
        # pulled its HLC far ahead of its durable state).  Recovery
        # re-observes the mark, so every beacon any peer ever saw is
        # below every post-crash mint.
        self.beacon_cap = 0
        self._wmark_pend: deque = deque()   # (seq, hlc mark)
        self._last_wmark_hlc = 0
        self.synced_sizes: list[int] = list(self.sizes)
        self._dirty = False            # bytes written since last fsync
        self._oldest_dirty_ts = 0.0
        self._last_sync = time.monotonic()
        self.last_fsync_lag_ms = 0.0
        self.fsyncs = 0
        self.rewrites = 0
        self.tail_truncated = 0
        self.appended_ops = 0
        self.spliced_batches = 0       # intake payloads mirrored verbatim
        self.encoded_batches = 0       # serve runs group-encoded here
        self._wmark_ok = meta.get("wmark_ok", "1") != "0"
        self._last_wmark_sig = None
        self._rewrite_asap = meta.get("dirty", "0") == "1"
        # last checkpoint/rewrite cut, persisted in the meta so the
        # INFO gauges (checkpoint_last_uuid / checkpoint_age_s) and
        # --restore-to survive a restart
        try:
            self.checkpoint_uuid = int(meta.get("ckpt_uuid", 0) or 0)
            self.checkpoint_ts = float(meta.get("ckpt_ts", 0) or 0.0)
        except ValueError:
            self.checkpoint_uuid, self.checkpoint_ts = 0, 0.0
        # cadence is measured on the monotonic clock from boot (a node
        # restored from an old checkpoint must not cut immediately just
        # because the persisted wall ts is stale)
        self._last_ckpt_mono = time.monotonic()
        # chaos fault injection: name of the rewrite stage to crash at
        # ("switch" | "snapshot" | "meta"); "" = no fault.  The chaos
        # crash-mid-checkpoint cell sets it, drives one rewrite, then
        # kill -9s the node — certifying every on-disk interleaving of
        # the generation switch / meta commit / old-gen delete replays
        # idempotently.
        self._ckpt_fault = ""
        self._rewriting = False
        self._rewrite_buf_bytes = 0
        self._sync_lock = asyncio.Lock() if _has_loop() else None
        self._closed = False

    # ------------------------------------------------------------ paths

    @staticmethod
    def meta_path(aof_dir: str) -> str:
        return os.path.join(aof_dir, "aof.meta")

    @staticmethod
    def seg_path(aof_dir: str, gen: int, seg: int) -> str:
        return os.path.join(aof_dir, f"aof.g{gen}.s{seg}.log")

    @staticmethod
    def base_snapshot_path(aof_dir: str, gen: int) -> str:
        return os.path.join(aof_dir, f"aof.g{gen}.base.snapshot")

    @classmethod
    def list_generations(cls, aof_dir: str) -> list[int]:
        gens = set()
        try:
            names = os.listdir(aof_dir)
        except OSError:
            return []
        for name in names:
            if name.startswith("aof.g") and name.endswith(".log"):
                try:
                    gens.add(int(name[5:].split(".", 1)[0]))
                except ValueError:
                    pass
        return sorted(gens)

    def size_bytes(self) -> int:
        return sum(self.sizes) + sum(map(len, self._bufs))

    def used_buffer_bytes(self) -> int:
        """Governed memory (server/overload.py source): un-drained
        append buffers plus the rewrite capture's working estimate."""
        return sum(map(len, self._bufs)) + self._rewrite_buf_bytes

    # ----------------------------------------------------------- opening

    def _open_generation(self, gen: int, resume: bool = False) -> None:
        self._files = []
        self._bufs = []
        self.sizes = []
        for s in range(self.n_segments):
            path = self.seg_path(self.dir, gen, s)
            fresh = not (resume and os.path.exists(path))
            f = open(path, "ab", buffering=0)
            if fresh and f.tell() == 0:
                f.write(MAGIC)
            self._files.append(f)
            self._bufs.append(bytearray())
            self.sizes.append(f.tell())
        self.generation = gen
        self.synced_sizes = list(self.sizes)

    # ------------------------------------------------------ append surface

    def _append(self, seg: int, rec: bytes) -> None:
        buf = self._bufs[seg]
        buf += rec
        if not self._dirty:
            self._dirty = True
            self._oldest_dirty_ts = time.monotonic()
        if len(buf) >= _BUF_FLUSH:
            self._drain(seg)

    def _drain(self, seg: int) -> None:
        buf = self._bufs[seg]
        if buf:
            self._files[seg].write(buf)
            self.sizes[seg] += len(buf)
            self._bufs[seg] = bytearray()

    def _drain_all(self) -> None:
        for s in range(self.n_segments):
            self._drain(s)

    def _track_local(self, uuid: int) -> None:
        self._seq += 1
        self._pend.append((self._seq, uuid))
        if self._pend_min is None or uuid < self._pend_min:
            self._pend_min = uuid

    def _track_intake(self, origin: int, uuid: int) -> None:
        from collections import deque
        d = self._intake_pend.get(origin)
        if d is None:
            d = self._intake_pend[origin] = deque()
        self._seq += 1
        d.append((self._seq, uuid))
        m = self._intake_min.get(origin)
        if m is None or uuid < m:
            self._intake_min[origin] = uuid

    def cap_ack(self, origin: int, ack: int) -> int:
        """The REPLACK watermark this node may ADVERTISE for `origin`'s
        stream: never past its first undurable intake record — a peer
        told we hold a frame must stay told the truth through any torn
        tail (the persisted-coverage half of emit-only-durable).
        O(1): this runs on every ack-loop wake (replica/link.py)."""
        m = self._intake_min.get(origin)
        if m is None:
            return ack
        return min(ack, m - 1)

    def cap_coverage(self, coverage: int) -> int:
        """Same rule for the CLUSTER COVERAGE claim (REPLACK item 5):
        third-party tombstone GC gates on it, so it may only name the
        durable prefix."""
        for m in self._intake_min.values():
            coverage = min(coverage, m - 1)
        return coverage

    def append_local(self, uuid: int, name: bytes, args: list,
                     seg: Optional[int] = None) -> None:
        """One locally-executed write, mirrored at repl-log push time
        (Node.replicate_cmd / the sharded ack mirror)."""
        if self._closed:
            return
        self._append(self._local_seg if seg is None else seg,
                     _pack_record(REC_FRAME, self._frame_payload(
                         self.node.node_id, uuid, name, args)))
        self._track_local(uuid)
        self.appended_ops += 1

    @property
    def _local_seg(self) -> int:
        """Single-loop nodes log everything in segment 0; a sharded
        node's parent-loop (barrier-plane) writes take the LAST segment
        — the same index MergedReplLog gives its `local` segment."""
        return self.n_segments - 1

    def append_local_run(self, entries: list, prev_uuid: int,
                         seg: Optional[int] = None,
                         publish: bool = True, builder=None) -> None:
        """A serve-coalescer run of `(uuid, name, args)` just pushed via
        ReplLog.push_many.  Group-encoded ONCE into the exact columnar
        wire payload the push loops would build (replica/wire.py); the
        finished REPLBATCH frame is PUBLISHED into the encode-once cache
        so the peer fan-out splices these very bytes instead of
        re-encoding (caps-class "b").  Runs the codec rejects mirror as
        per-frame records — the same demotion the wire path applies.

        `builder`: the serve flush's ALREADY-FILLED BatchBuilder — its
        rows are the wire rows modulo the element-add dt-check flag
        (fresh client uuids make the rule provably inert locally, but a
        RECEIVER must still evaluate it), so serializing it through a
        chk-fixing view skips the whole re-encode
        (tests/test_oplog.py pins byte-equality with the from-scratch
        encoding)."""
        if self._closed or not entries:
            return
        node = self.node
        payload = None
        if len(entries) >= _MIN_BATCH_RUN:
            if builder is not None:
                payload = _encode_serve_builder(builder, prev_uuid,
                                                node.node_id)
            if payload is None:
                payload = _encode_run(entries, prev_uuid, node.node_id)
        s = self._local_seg if seg is None else seg
        if payload is None:
            for uuid, name, args in entries:
                self._append(s, _pack_record(REC_FRAME, self._frame_payload(
                    node.node_id, uuid, name, args)))
                self._track_local(uuid)
            self.appended_ops += len(entries)
            return
        last = entries[-1][0]
        out = bytearray()
        write_uvarint(out, node.node_id)
        write_uvarint(out, prev_uuid)
        write_uvarint(out, last)
        write_uvarint(out, len(entries))
        out += payload
        self._append(s, _pack_record(REC_BATCH, bytes(out)))
        # ONE pending marker per run: the floor is the min unsynced
        # local uuid, and a capture releases whole runs — per-entry
        # markers would only burn hot-path time for the same floor
        self._track_local(entries[0][0])
        self.appended_ops += len(entries)
        self.encoded_batches += 1
        if publish:
            self._publish_run(prev_uuid, last, len(entries), payload)

    def _publish_run(self, prev: int, last: int, n: int,
                     payload: bytes) -> None:
        """Hand the finished encoding to the broadcast plane: the push
        loops' caps-class entries at this exact cursor are the full
        REPLBATCH wire frames wrapping this payload — byte-identical to
        what replica/link.py _encode_wire_run would build for the same
        run (build_wire_batch is a pure function of the run, and the
        compressed variant mirrors its keep-only-if-smaller rule) — so
        the fan-out splices the log's encoding instead of re-doing it."""
        node = self.node
        cache = getattr(node, "wire_cache", None)
        if cache is None or not cache.enabled:
            return
        app = node.app
        if node.replicas is None or app is None:
            return
        from ..replica.link import (CAP_BATCH_STREAM, CAP_COMPRESS,
                                    REPLBATCH, wire_compress_min,
                                    wire_compress_of)
        compress_on = wire_compress_of(app)
        readers = {"b": 0, "bz": 0}
        for m in node.replicas.live_peers():
            link = m.link
            if link is None or not getattr(link, "connected", False):
                continue
            caps = getattr(link, "_peer_caps", 0)
            if not caps & CAP_BATCH_STREAM or m.batch_wire_off:
                continue
            if compress_on and caps & CAP_COMPRESS \
                    and not m.compress_wire_off:
                readers["bz"] += 1
            else:
                readers["b"] += 1
        if not (readers["b"] or readers["bz"]):
            return
        from ..resp.codec import encode_into
        from ..resp.message import Arr, Bulk, Int

        def frame_for(body: bytes) -> bytes:
            out = bytearray()
            encode_into(out, Arr([
                Bulk(REPLBATCH), Int(node.node_id), Int(prev), Int(last),
                Int(n), Bulk(body)]))
            return bytes(out)

        if readers["b"]:
            cache.put("b", prev, last, frame_for(payload), batches=1,
                      batch_frames=n, readers=readers["b"])
        if readers["bz"]:
            comp_raw = comp_wire = 0
            body = payload
            comp_min = wire_compress_min(app)
            if len(payload) >= comp_min:
                from ..utils.compressio import compress_bytes
                z = compress_bytes(payload, level=1)
                if len(z) < len(payload):
                    comp_raw, comp_wire = len(payload), len(z)
                    body = z
            cache.put("bz", prev, last, frame_for(body), batches=1,
                      batch_frames=n, comp_raw=comp_raw,
                      comp_wire=comp_wire, readers=readers["bz"])

    def append_frame(self, origin: int, uuid: int, name: bytes,
                     args: list, seg: int = 0) -> None:
        """One replicated-intake frame (the coalescing applier's buffer
        and barriers; a sharded node's ShardApplier routes by shard)."""
        if self._closed:
            return
        self._append(seg, _pack_record(
            REC_FRAME, self._frame_payload(origin, uuid, name, args)))
        self._track_intake(origin, uuid)
        self.appended_ops += 1

    def append_batch(self, origin: int, base: int, last: int, n: int,
                     payload: bytes, seg: int = 0) -> None:
        """One received REPLBATCH payload, spliced verbatim — it IS the
        columnar wire encoding and was just crc-validated by the
        decoder (replica/coalesce.py apply_wire_batch)."""
        if self._closed:
            return
        out = bytearray()
        write_uvarint(out, origin)
        write_uvarint(out, base)
        write_uvarint(out, last)
        write_uvarint(out, n)
        out += payload
        self._append(seg, _pack_record(REC_BATCH, bytes(out)))
        # the whole run (base, last] is undurable until the next commit:
        # the pending marker is its first covered uuid
        self._track_intake(origin, base + 1)
        self.appended_ops += n
        self.spliced_batches += 1

    @staticmethod
    def _frame_payload(origin: int, uuid: int, name: bytes,
                       args: list) -> bytes:
        from ..resp.codec import encode_into
        from ..resp.message import Arr, Bulk
        out = bytearray()
        write_uvarint(out, origin)
        write_uvarint(out, uuid)
        encode_into(out, Arr([Bulk(name), *args]))
        return bytes(out)

    def maybe_wmark(self) -> None:
        """Append a replica watermark/coverage record when the
        watermarks moved.  Captured on the loop BEFORE the next fsync
        cut, and suppressed while out-of-log bulk state is pending a
        rewrite (a WMARK claiming bulk-delivered coverage would skip
        redelivery of state the log never held — module docstring)."""
        if self._closed or not self._wmark_ok or self.node is None:
            return
        node = self.node
        if node.replicas is None:
            return
        records = node.replicas.records()
        for r in records:
            # durable cap: a WMARK lives in the LOCAL segment while the
            # frames it covers may live in another — file order alone
            # cannot make that cut consistent across segments, so the
            # persisted watermark names only fsync-covered frames
            r.uuid_he_sent = self.cap_ack(r.node_id, r.uuid_he_sent)
        landed = getattr(node.repl_log, "landed_last_uuid",
                         node.repl_log.last_uuid)
        if self._pend_min is not None:
            # the own-stream claim gets the same durable cap: on a
            # sharded node the covered local entries live in OTHER
            # segments, so file order alone cannot protect the cut
            landed = min(landed, self._pend_min - 1)
        hlc_now = node.hlc.current
        sig = (landed, tuple((r.addr, r.node_id, r.add_t, r.del_t,
                              r.uuid_he_sent, r.uuid_he_acked)
                             for r in records))
        # a WMARK also refreshes the durable HLC mark (the beacon cap —
        # see beacon_cap), so one is forced when the clock moved
        # meaningfully even if no watermark changed: an idle-but-alive
        # node must keep its beacon promise renewable
        if sig == self._last_wmark_sig and \
                hlc_now - self._last_wmark_hlc < _WMARK_HLC_STRIDE:
            return
        self._last_wmark_sig = sig
        self._last_wmark_hlc = hlc_now
        from .snapshot import _encode_replicas
        out = bytearray()
        write_uvarint(out, landed)
        write_uvarint(out, hlc_now)
        out += _encode_replicas(records)
        self._append(self._local_seg, _pack_record(REC_WMARK, bytes(out)))
        self._seq += 1
        self._wmark_pend.append((self._seq, hlc_now))

    # ---------------------------------------------------------- durability

    def durable_floor(self) -> Optional[int]:
        """The repl-log emission floor (MergedReplLog floor semantics:
        entries with uuid >= floor are invisible to the push stream):
        the smallest LOCAL uuid not yet covered by this policy's
        durability point.  None = everything durable, no gate."""
        return self._pend_min

    def install_floor(self) -> None:
        """Compose the durability floor into the node's repl log —
        called at arm time and re-called whenever the log object is
        replaced (state wipe, plane reset)."""
        rl = self.node.repl_log
        prev = getattr(rl, "floor", None)
        mine = self.durable_floor
        if prev is None:
            rl.floor = mine
        else:
            def combined(_prev=prev, _mine=mine):
                a, b = _prev(), _mine()
                if a is None:
                    return b
                if b is None:
                    return a
                return min(a, b)
            rl.floor = combined

    def _capture(self):
        """Pre-fsync cut, on the loop: drain buffers so every pending
        record is OS-visible, then remember how many pending entries
        (local and per-origin intake) the fsync will cover."""
        self._drain_all()
        self._dirty = False
        oldest = self._oldest_dirty_ts
        marks = (self._seq, list(self.sizes), self.generation)
        return marks, list(self._files), oldest

    def _settle(self, marks, oldest: float,
                fsynced: bool = True) -> None:
        """Post-fsync bookkeeping, on the loop: exactly the pending
        entries the capture covered (seq stamp at or below it) are
        durable now — release them from the floor/ack caps and wake the
        push loops past them.  Seq-bounded release is what makes
        concurrent commits safe: a settle never releases an entry
        appended after its own capture, and an entry already released
        by an overlapping commit is simply gone."""
        upto, sizes, gen = marks
        released = 0
        pend = self._pend
        while pend and pend[0][0] <= upto:
            pend.popleft()
            released += 1
        self._pend_min = min(u for _s, u in pend) if pend else None
        for origin in list(self._intake_pend):
            d = self._intake_pend[origin]
            dropped = False
            while d and d[0][0] <= upto:
                d.popleft()
                released += 1
                dropped = True
            if not d:
                del self._intake_pend[origin]
                del self._intake_min[origin]
            elif dropped:
                # one scan per settle, not per ack wake (cap_ack)
                self._intake_min[origin] = min(u for _s, u in d)
        wp = self._wmark_pend
        while wp and wp[0][0] <= upto:
            self.beacon_cap = max(self.beacon_cap, wp.popleft()[1])
        if gen == self.generation and len(sizes) == len(self.synced_sizes):
            self.synced_sizes = [max(a, b) for a, b in
                                 zip(self.synced_sizes, sizes)]
        now = time.monotonic()
        self._last_sync = now
        if fsynced:
            if oldest:
                self.last_fsync_lag_ms = round((now - oldest) * 1000.0, 3)
            self.fsyncs += 1
        node = self.node
        if node is not None and released:
            from ..server.events import EVENT_REPLICATED
            node.events.trigger(EVENT_REPLICATED)

    def _pending(self) -> bool:
        return bool(self._pend) or bool(self._intake_pend)

    def sync_now(self) -> None:
        """Blocking group commit (shutdown, tests, the wipe path)."""
        marks, files, oldest = self._capture()
        for f in files:
            try:
                os.fsync(f.fileno())
            except (OSError, ValueError):  # pragma: no cover
                pass  # closed under us — see _fsync_all
        self._settle(marks, oldest)

    async def _sync_async(self) -> None:
        if self._sync_lock is None:
            self._sync_lock = asyncio.Lock()
        async with self._sync_lock:
            if not self._dirty and not self._pending():
                return
            marks, files, oldest = self._capture()

            def _fsync_all():
                for f in files:
                    try:
                        os.fsync(f.fileno())
                    except (OSError, ValueError):
                        # rewrite/on_wipe/close swapped the generation
                        # and closed this file mid-commit (fileno() on
                        # a closed file is ValueError).  Settling is
                        # still sound: every closer either fsynced the
                        # captured bytes first (rewrite, close — their
                        # sync_now covers this capture's drain) or
                        # discarded the log wholesale (on_wipe), so
                        # nothing this capture covered can be torn away
                        pass

            await asyncio.to_thread(_fsync_all)
            self._settle(marks, oldest)

    @property
    def ack_barrier_needed(self) -> bool:
        """Does the next reply flush have to wait on a group commit?
        Only under `always` — and only when something is pending."""
        return self.policy == "always" and not self._closed and \
            (self._dirty or self._pending())

    async def ack_barrier(self) -> None:
        """The `always` ack gate (server/io.py): replies for a chunk
        reach the socket only after the fsync covering the chunk's
        appends lands — one fsync per pipelined chunk, group commit."""
        await self._sync_async()

    async def cron(self, app) -> None:
        """Driven from the server cron tick: everysec group commits,
        watermark records, policy=no write-through, rewrite checks."""
        if self._closed:
            return
        self.maybe_wmark()
        if self.policy == "no":
            # durability point == the OS write: drain and release
            # (no fsync — that is the policy's contract)
            marks, _files, oldest = self._capture()
            self._settle(marks, oldest, fsynced=False)
        elif self._dirty or self._pending():
            if self.policy == "everysec":
                if time.monotonic() - self._last_sync >= _EVERYSEC:
                    await self._sync_async()
            elif self.policy == "always":
                # idle-node belt and braces: an append whose connection
                # died before the ack barrier must not sit unsynced
                # forever (the barrier is the normal path)
                if time.monotonic() - self._last_sync >= _EVERYSEC:
                    await self._sync_async()
        if self._rewrite_asap or self.rewrite_due() or \
                self.checkpoint_due():
            await self.rewrite(app)

    # ---------------------------------------------------- out-of-log state

    def note_bulk_sync(self) -> None:
        """Out-of-log state landed (full/delta sync, bulk ingest): the
        log alone can no longer reproduce this node.  Watermark records
        are suppressed until a rewrite re-bases the log on a snapshot
        covering the bulk state (module docstring); the next cron tick
        runs that rewrite."""
        if self._closed:
            return
        self._wmark_ok = False
        self._rewrite_asap = True
        try:
            _write_meta(self.meta_path(self.dir), self._meta_fields())
        except OSError:  # pragma: no cover - fs-dependent
            pass

    def on_wipe(self, fence: int) -> None:
        """State wipe (reset_for_full_resync): every logged record
        describes discarded state — replaying any of it would resurrect
        keys whose tombstones are gone mesh-wide.  Discard the log,
        fence recovery at the pre-wipe watermark (peers full-sync a
        node that crashes before the post-wipe rewrite lands), and
        reinstall the floor on the freshly-swapped repl log."""
        if self._closed:
            return
        gen = self.generation + 1
        for f in self._files:
            try:
                f.close()
            except OSError:
                pass
        self._bufs = [bytearray() for _ in range(self.n_segments)]
        self._pend.clear()
        self._pend_min = None
        self._intake_pend.clear()
        self._intake_min.clear()
        self._wmark_pend.clear()
        self._dirty = False
        self._wmark_ok = False
        self._rewrite_asap = True
        self._last_wmark_sig = None
        self._open_generation(gen)
        # _meta_fields, not a raw dict: the persisted node_id must
        # survive the wipe, or a crash before the re-basing rewrite
        # boots with prescan_node_id()==0 (snapshot="" and
        # boot_snap_ok=0 rule out both snapshot fallbacks) and sharded
        # workers would stamp origin 0 into new writes
        _write_meta(self.meta_path(self.dir), self._meta_fields(
            gen=gen, base_size=0, snapshot="", boot_snap_ok=0,
            fence=fence))
        self._gc_generations(keep_from=gen)
        self.base_size = self.size_bytes()
        self.install_floor()

    def _meta_fields(self, **over) -> dict:
        fields = dict(gen=self.generation, base_size=self.base_size,
                      snapshot=os.path.basename(self._base_snapshot())
                      if self._base_snapshot() else "",
                      boot_snap_ok=1,
                      fence=0,
                      node_id=getattr(self.node, "node_id", 0) or 0,
                      wmark_ok=int(self._wmark_ok),
                      dirty=int(self._rewrite_asap),
                      ckpt_uuid=self.checkpoint_uuid,
                      ckpt_ts=f"{self.checkpoint_ts:.3f}")
        fields.update(over)
        return fields

    def _base_snapshot(self) -> str:
        path = self.base_snapshot_path(self.dir, self.generation)
        return path if os.path.exists(path) else ""

    def _gc_generations(self, keep_from: int) -> None:
        for g in self.list_generations(self.dir):
            if g >= keep_from:
                continue
            for s in range(64 + 2):
                p = self.seg_path(self.dir, g, s)
                if os.path.exists(p):
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
            p = self.base_snapshot_path(self.dir, g)
            if os.path.exists(p):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    # -------------------------------------------------------------- rewrite

    def rewrite_due(self) -> bool:
        if not self.rewrite_pct or self._rewriting or self._closed:
            return False
        size = self.size_bytes()
        if size < self.rewrite_min_bytes:
            return False
        return size > self.base_size * (1.0 + self.rewrite_pct / 100.0)

    def checkpoint_due(self) -> bool:
        """Time-triggered incremental checkpoint (CONSTDB_CHECKPOINT_*):
        due once the cadence elapsed AND the post-checkpoint tail has
        grown past the floor — the rewrite IS the checkpoint (consistent
        snapshot + generation cut), so a restart replays only the
        tail."""
        if not self.checkpoint_secs or self._rewriting or self._closed:
            return False
        if time.monotonic() - self._last_ckpt_mono < self.checkpoint_secs:
            return False
        return self.size_bytes() - self.base_size >= \
            self.checkpoint_min_bytes

    def _fault(self, stage: str) -> None:
        """Chaos fault point inside rewrite() (see _ckpt_fault)."""
        if self._ckpt_fault == stage:
            self._ckpt_fault = ""
            raise RuntimeError(f"injected checkpoint fault: {stage}")

    async def rewrite(self, app) -> None:
        """Compact snapshot + tail atomically (module docstring): cut on
        the loop, switch generations so new appends survive, write the
        base snapshot durably off-loop, commit the meta, drop the old
        generation."""
        if self._rewriting or self._closed:
            return
        self._rewriting = True
        node = self.node
        try:
            from ..engine.base import batch_from_keyspace
            from .snapshot import NodeMeta, write_snapshot_file
            plane = node.serve_plane
            # the rewrite working set rides the PERMANENT governor
            # source arm() installed — used_buffer_bytes includes
            # _rewrite_buf_bytes, so registering it again here would
            # double-count every oplog byte for the rewrite's duration
            self._rewrite_buf_bytes = 1 << 20
            gen = self.generation + 1
            # switch BEFORE the capture — the load-bearing order: every
            # op that lands from here on appends to the NEW generation
            # and survives the old one's deletion whether or not the
            # capture caught its effect.  A sharded capture AWAITS the
            # worker exports, and ops landing during those awaits used
            # to append to the OLD generation while missing the base —
            # the rewrite then deleted their only durable record
            # (acked, fsynced, emitted — found by the sharded chaos
            # cell as mesh-vs-reference divergence).
            self.sync_now()
            for f in self._files:
                try:
                    f.close()
                except OSError:
                    pass
            self._open_generation(gen)
            self._fault("switch")
            if plane is not None:
                repl_last = node.repl_log.landed_last_uuid
                records = node.replicas.records()
                captures = await plane.export_batches()
            else:
                node.ensure_flushed()
                repl_last = getattr(node.repl_log, "landed_last_uuid",
                                    node.repl_log.last_uuid)
                records = node.replicas.records()
                captures = [batch_from_keyspace(node.ks)]
            meta = NodeMeta(node_id=node.node_id, alias=node.alias,
                            addr=getattr(app, "advertised_addr", ""),
                            repl_last_uuid=repl_last)
            snap = self.base_snapshot_path(self.dir, gen)
            await asyncio.to_thread(
                write_snapshot_file, snap, meta, records, captures,
                chunk_keys=getattr(app, "snapshot_chunk_keys", 1 << 16),
                fsync=True)
            self._fault("snapshot")
            self._wmark_ok = True
            self._rewrite_asap = False
            self._last_wmark_sig = None
            self.base_size = self.size_bytes()
            # the cut this base represents — a restart from it replays
            # only records past repl_last (the checkpoint gauges)
            self.checkpoint_uuid = repl_last
            self.checkpoint_ts = time.time()
            self._last_ckpt_mono = time.monotonic()
            _write_meta(self.meta_path(self.dir), self._meta_fields(
                gen=gen, base_size=self.base_size,
                snapshot=os.path.basename(snap)))
            self._fault("meta")
            self._gc_generations(keep_from=gen)
            self.rewrites += 1
            log.info("aof rewrite #%d: base %s at uuid %d, log reset "
                     "(gen %d)", self.rewrites, snap, repl_last, gen)
        except (OSError, RuntimeError) as e:
            log.error("aof rewrite failed (will retry): %s", e)
            self._rewrite_asap = True
        finally:
            self._rewrite_buf_bytes = 0
            self._rewriting = False

    def close(self) -> None:
        if self._closed:
            return
        if self.policy != "no":
            self.sync_now()
        else:
            self._drain_all()
        self._closed = True
        for f in self._files:
            try:
                f.close()
            except OSError:
                pass


def _has_loop() -> bool:
    try:
        asyncio.get_running_loop()
        return True
    except RuntimeError:
        return False


# ----------------------------------------------------------------- encode

def _encode_run(entries: list, prev_uuid: int, node_id: int
                ) -> Optional[bytes]:
    """Group-encode a serve run of `(uuid, name, args)` into one
    columnar wire payload via the REAL wire codec (replica/wire.py
    build_wire_batch over stub repl-log entries).  None = the codec
    demoted the run (per-frame records instead)."""
    from ..replica.wire import build_wire_batch
    from ..server.repl_log import ReplEntry
    stubs = []
    prev = prev_uuid
    for uuid, name, args in entries:
        stubs.append(ReplEntry(uuid, prev, name, args, 0))
        prev = uuid
    return build_wire_batch(stubs, node_id)


class _WireView:
    """A serve BatchBuilder seen through wire-pattern glasses: element
    ADD rows get their dt-check mark set (the serve encoders leave it
    False — locally provably inert, but the wire format must tell the
    receiver to evaluate the rule).  Everything else is the same rows
    by reference."""

    __slots__ = ("keys", "enc", "ct", "mt", "dt", "reg_runs",
                 "cnt_rows", "el_rows", "tns_rows")

    def __init__(self, bb) -> None:
        self.keys = bb.keys
        self.enc = bb.enc
        self.ct = bb.ct
        self.mt = bb.mt
        self.dt = bb.dt
        self.reg_runs = bb.reg_runs
        self.cnt_rows = bb.cnt_rows
        self.el_rows = [
            (ki, m, v, at, an, dlt, at != 0)
            for ki, m, v, at, an, dlt, _chk in bb.el_rows]
        self.tns_rows = bb.tns_rows


def _encode_serve_builder(bb, prev_uuid: int, node_id: int
                          ) -> Optional[bytes]:
    """Serialize the serve flush's filled builder straight into the
    wire payload (skipping the from-scratch re-encode); None = a row
    fell outside the wire patterns — the caller falls back."""
    from ..replica import wire
    try:
        return wire._encode_builder(_WireView(bb), node_id, prev_uuid)
    except (wire._PatternError, *wire._ENC_ERRORS):
        return None


# ---------------------------------------------------------------- recovery

class _ReplayApplier:
    """Boot-replay twin of the live coalescing applier: frame records
    buffer per command and group-encode through the SAME
    COLUMNAR_ENCODERS/BatchBuilder machinery; non-encodable frames apply
    as apply_replicated barriers.  Erroring ops are logged and SKIPPED
    (recovery must never crash-loop on one bad op), counted into
    RecoveryInfo.

    Two landing strategies (CONSTDB_RECOVER_BULK):

      * serial (`bulk=False`): every record applies individually —
        frames through `Node.apply_replicated`, REPLBATCH records
        through one `Node.merge_stream_batch` call each.  This is the
        per-record reference path the bench oracle compares against;
        no buffering, no coalescing, strict log order.
      * bulk (`bulk=True`, the default): finalized batches accumulate
        into MERGE ROUNDS of ~_REPLAY_ROUND_ROWS columnar rows and land
        through one `Node.merge_batches` call per round (the engine's
        merge_many group path — the same WIDE strategy snapshot ingest
        rides).  CRDT merges commute, so batch order within a round is
        free; the ONE order-sensitive step is `finalize()`'s
        element-plane key-delete rule, which reads LIVE key dt columns
        — so a flush carrying checked element rows forces the pending
        round to land first iff the round holds a dt RAISE for one of
        the flush's OWN keys (`_round_dt_keys`; disjoint key sets
        commute).  Non-encodable barriers land buffer + round before
        applying — except KEY_SCOPED barriers whose key has no
        pending rows, which commute with everything pending and apply
        in place (the live coalescer's exact scoping discipline).
    """

    def __init__(self, node, info: RecoveryInfo,
                 bulk: bool = False) -> None:
        # frame() runs once per REC_FRAME record: bind its lookup
        # tables here instead of importing them per record
        from ..resp.message import Bulk, as_bytes
        from ..server.commands import (COLUMNAR_ENCODERS,
                                       KEY_SCOPED_BARRIERS,
                                       STATE_FREE_BARRIERS)
        self._as_bytes = as_bytes
        self._bulk_cls = Bulk
        self._encoders = COLUMNAR_ENCODERS
        self._key_scoped = KEY_SCOPED_BARRIERS
        self._state_free = STATE_FREE_BARRIERS
        self.node = node
        self.info = info
        self.bulk = bulk
        self._buf: dict[bytes, list] = {}
        self._frames = 0
        self._rows_bound = 0    # upper bound on the buffer's batch rows
        self._round: list = []      # finalized batches pending one merge
        self._round_rows = 0
        self._round_dt_keys: set = set()  # keys the round raises dts of
        self._pending_keys: set = set()   # keys with buffered/round rows
        self._next_progress = _REPLAY_PROGRESS_EVERY

    def frame(self, origin: int, uuid: int, name: bytes,
              args: list) -> None:
        info = self.info
        if self.bulk and name in self._encoders and len(args) >= 1:
            key = args[0]
            if type(key) is not bytes:   # raw-scanned args skip this
                try:
                    key = self._as_bytes(key)
                except CstError:
                    info.skipped += 1
                    return
            recs = self._buf.setdefault(name, [])
            recs.append((key, origin, uuid,
                         (None, None, None, None, None, *args)))
            self._pending_keys.add(key)
            self._frames += 1
            # args over-counts rows (values/pairs ride along), so this
            # keeps the flushed batch under the round budget — an
            # over-budget batch would fall off the engines' vectorized
            # micro path (see _REPLAY_ROUND_ROWS)
            self._rows_bound += len(args)
            if self._frames >= _REPLAY_BULK_FRAMES or \
                    self._rows_bound >= _REPLAY_ROUND_ROWS:
                self.flush()
        else:
            if self.bulk and name not in self._state_free:
                # a KEY_SCOPED barrier reads/sweeps exactly its own
                # key: with no pending rows for it, it commutes with
                # buffer and round and applies in place (the live
                # coalescer's scoping — replica/coalesce.py barrier())
                scoped = name in self._key_scoped and len(args) >= 1
                if scoped:
                    try:
                        scoped = self._as_bytes(args[0]) \
                            not in self._pending_keys
                    except CstError:
                        scoped = False
                if not scoped:
                    # any other state-reading barrier must see every
                    # prior record landed: drain buffer AND round
                    if self._frames:
                        self.flush()
                    self._merge_round()
            self._apply_one(origin, uuid, name, args)
        self._observe(origin, uuid)

    def batch(self, origin: int, base: int, last: int, n: int,
              payload: bytes) -> None:
        from ..replica import wire
        self.flush()
        node = self.node
        try:
            wb = wire.decode_wire_batch(payload, node.ks, origin, base)
            if wb.n_frames != n:
                raise wire.WireFormatError("frame count mismatch")
        except wire.WireFormatError as e:
            # a crc-valid record with an undecodable payload: skip it
            # loudly, never replay garbage
            log.error("aof replay: undecodable batch record (%s); "
                      "skipping %d ops", e, n)
            self.info.skipped += n
            return
        if not self.bulk:
            node.merge_stream_batch(wb, n)
        else:
            # finalize()'s key-delete rule reads LIVE dt columns: land
            # the pending round first iff it raises a dt of one of
            # THIS batch's keys (disjoint key sets commute)
            if self._round_dt_keys and not \
                    self._round_dt_keys.isdisjoint(wb.batch.keys):
                self._merge_round()
            node.ensure_flushed_for(("env",))
            self._push_round(wb.finalize())
        self.info.batches += 1
        self.info.batch_frames += n
        self._observe(origin, last)

    def _apply_one(self, origin: int, uuid: int, name: bytes,
                   args: list) -> None:
        if args and type(args[0]) is bytes:
            # raw-scanned frame (scan_segment raw mode: every arg is
            # plain bytes, all-or-nothing): the reference apply path
            # takes RESP messages, so re-wrap — barriers and other
            # non-encodable frames only, the columnar encoders take
            # the bytes as-is
            bulk = self._bulk_cls
            args = [bulk(a) for a in args]
        try:
            self.node.apply_replicated(name, args, origin, uuid)
            self.info.frames += 1
        except CstError as e:
            log.warning("aof replay: op %d (%s) failed (%s); skipped",
                        uuid, name, e)
            self.info.skipped += 1

    def _observe(self, origin: int, uuid: int) -> None:
        info = self.info
        if uuid > info.replayed_max:
            info.replayed_max = uuid
        if origin == self.node.node_id and uuid > info.local_max:
            info.local_max = uuid
        self.node.hlc.observe(uuid)
        done = info.frames + info.batch_frames
        if done >= self._next_progress:
            self._next_progress += _REPLAY_PROGRESS_EVERY
            log.info("aof replay progress: %d ops replayed "
                     "(%d skipped, %d merge rounds)", done,
                     info.skipped, info.merge_rounds)

    # ------------------------------------------------- bulk merge rounds

    def _push_round(self, b) -> None:
        # close the round BEFORE it would cross the row budget: the
        # budget equals the engines' host micro-strategy ceiling, and
        # an over-budget round falls off the vectorized path
        if self._round and \
                self._round_rows + b.n_rows > _REPLAY_ROUND_ROWS:
            self._merge_round()
        self._round.append(b)
        self._round_rows += b.n_rows
        self._pending_keys.update(b.keys)
        if len(b.del_keys):
            self._round_dt_keys.update(b.del_keys)
            self._pending_keys.update(b.del_keys)
        if b.key_dt.any():
            self._round_dt_keys.update(
                k for k, dt in zip(b.keys, b.key_dt.tolist()) if dt)

    def _merge_round(self) -> None:
        rnd, self._round = self._round, []
        self._round_rows = 0
        self._round_dt_keys.clear()
        # the frame buffer is always empty here (every caller flushes
        # first), so pendency collapses with the round
        self._pending_keys.clear()
        if not rnd:
            return
        # land the round as ONE wide batch: concatenating first means
        # one key resolution + one vectorized pass per plane for the
        # whole round, where per-batch merges would pay the numpy
        # fixed costs once per few-hundred-row record
        from ..engine.base import concat_batches
        self.node.merge_batches([concat_batches(rnd)])
        self.info.merge_rounds += 1

    def drain(self) -> None:
        """End-of-stream drain: frame buffer, then the pending round."""
        self.flush()
        self._merge_round()

    def flush(self) -> None:
        from ..replica.coalesce import BatchBuilder
        from ..server.commands import COLUMNAR_ENCODERS, NotColumnar
        buf, self._buf = self._buf, {}
        frames, self._frames = self._frames, 0
        self._rows_bound = 0
        if not frames:
            return
        node = self.node
        bb = BatchBuilder(node.ks)
        enc_errors = (NotColumnar, CstError, IndexError, TypeError,
                      ValueError, KeyError)
        failures: list = []
        for name, recs in buf.items():
            enc = COLUMNAR_ENCODERS[name]
            try:
                enc(bb, recs)
            except enc_errors:
                for r in recs:
                    try:
                        enc(bb, [r])
                    except enc_errors:
                        failures.append((name, r))
        if not self.bulk:
            node.merge_stream_batch(bb, frames - len(failures))
        else:
            # same dt-rule discipline as batch(): checked element rows
            # may not finalize over a pending dt raise of their OWN
            # key — disjoint key sets commute and keep the round open
            rdk = self._round_dt_keys
            if rdk and any(r[0] in rdk
                           for recs in buf.values() for r in recs):
                self._merge_round()
            node.ensure_flushed_for(("env",))
            self._push_round(bb.finalize())
            if failures:
                # the per-op fallbacks below read live state
                self._merge_round()
        self.info.frames += frames - len(failures)
        if failures:
            failures.sort(key=lambda f: f[1][2])
            for name, r in failures:
                self._apply_one(r[1], r[2], name, list(r[3][5:]))


def _frame_ctx():
    """Per-stream decode context: the native parser entry plus the RESP
    message classes, resolved ONCE instead of per record — replay
    decodes millions of frame records and the per-record import
    machinery + `_ext()` lookups were a measurable slice of the scan."""
    from ..resp import codec as C
    from ..resp.message import NIL, Arr, Bulk, Err, Int, Simple
    return C._ext(), Arr, Bulk, Int, Simple, Err, NIL


def _decode_frame(payload: bytes, parser=None, ctx=None):
    """Decode one REC_FRAME payload: varint header + exactly one RESP
    array.  The hot path hands the array straight to the native C
    parser (one call per record, no parser object, no buffer copy) —
    replay decodes millions of frame records and the per-record python
    around RespParser was a top scan cost.  `parser`: a reusable
    pure-python fallback parser for builds without the extension
    (_decode_stream rebuilds it after any failure, so a malformed
    record can never desync the stream that follows it).  `ctx`: a
    `_frame_ctx()` tuple shared across a stream's records."""
    if ctx is None:
        ctx = _frame_ctx()
    ext, Arr, Bulk, Int, Simple, Err, NIL = ctx
    origin, pos = read_uvarint(payload, 0)
    uuid, pos = read_uvarint(payload, pos)
    if ext is not None:
        try:
            msgs, new_pos, fallback = ext.resp_parse(
                payload, pos, Arr, Bulk, Int, Simple, Err, NIL, 2,
                512 << 20)
        except TypeError:   # prebuilt ext predating the max_bulk param
            msgs, new_pos, fallback = ext.resp_parse(
                payload, pos, Arr, Bulk, Int, Simple, Err, NIL)
        if len(msgs) != 1 or new_pos != len(payload) or fallback:
            raise ValueError("malformed frame record")
        msg = msgs[0]
    else:
        if parser is None:
            from ..resp import codec as C
            parser = C.RespParser()
        parser.feed(payload[pos:])
        msg = parser.next_msg()
        # a frame record holds exactly ONE message: anything left
        # queued or buffered would desync every later frame fed to
        # this parser (state peek, not a second parse call)
        if parser._qpos < len(parser._q) or \
                parser._pos < len(parser._buf):
            raise ValueError("trailing bytes in frame record")
    if not isinstance(msg, Arr) or not msg.items or \
            not isinstance(msg.items[0], Bulk):
        raise ValueError("malformed frame record")
    return origin, uuid, msg.items[0].val, msg.items[1:]


def _decode_batch_head(payload: bytes):
    r = VarintReader(payload)
    return r.uvarint(), r.uvarint(), r.uvarint(), r.uvarint(), \
        payload[r.pos:]


def _decode_wmark(payload: bytes):
    from .snapshot import _decode_replicas
    r = VarintReader(payload)
    landed = r.uvarint()
    hlc_mark = r.uvarint()
    return landed, hlc_mark, _decode_replicas(payload[r.pos:])


def scan_generation(aof_dir: str, gen: int, info: RecoveryInfo,
                    classes: tuple = (), raw: bool = False) -> list:
    """All segment record streams of one generation, with torn tails
    repaired (truncated on disk, LOUDLY).  Returns a list of per-segment
    record lists in segment order.  `classes` (see scan_segment): lets
    the native scanner pre-decode REC_FRAME records at scan time."""
    streams = []
    s = 0
    while True:
        path = OpLog.seg_path(aof_dir, gen, s)
        if not os.path.exists(path):
            break
        try:
            records, valid, total = scan_segment(path, classes, raw)
        except OpLogError as e:
            # unreadable (bad header — not a torn tail): quarantine the
            # SEGMENT, keep recovering from the others, and void the
            # log's watermark records (they may claim frames this
            # segment held)
            qpath = path + ".corrupt"
            try:
                os.replace(path, qpath)
            except OSError:  # pragma: no cover - fs-dependent
                qpath = path
            log.error("aof segment %s is unreadable (%s); quarantined "
                      "to %s", path, e, qpath)
            info.quarantined += 1
            info.wmark_unsafe = True
            streams.append([])
            s += 1
            continue
        if valid < total:
            info.tail_truncated += 1
            info.truncated_bytes += total - valid
            log.error(
                "aof segment %s has a torn tail: truncating %d bytes "
                "after the last valid record boundary (offset %d)",
                path, total - valid, valid)
            with open(path, "r+b") as f:
                f.truncate(valid)
        streams.append(records)
        s += 1
    return streams


def _decode_stream(recs: list) -> list:
    """Decode one segment's raw records into `(sortkey, rtype, data)`
    items — sortkey is the max uuid seen so far in file order, the
    k-way merge key.  Records the native scanner already pre-decoded
    (REC_FRAME 5-tuples, see scan_segment) pass straight through;
    crc-valid but undecodable records are skipped, loudly."""
    from ..resp.codec import make_parser
    seq = []
    last = 0
    parser = make_parser()
    ctx = _frame_ctx()
    for item in recs:
        rtype = item[0]
        try:
            if rtype == REC_FRAME:
                if len(item) == 5:   # pre-decoded at scan time
                    _, origin, uuid, name, args = item
                else:
                    origin, uuid, name, args = _decode_frame(
                        item[1], parser, ctx)
                last = max(last, uuid)
                seq.append((last, rtype, (origin, uuid, name, args)))
            elif rtype == REC_BATCH:
                origin, base, lastu, n, body = \
                    _decode_batch_head(item[1])
                last = max(last, base + 1)
                seq.append((last, rtype, (origin, base, lastu, n,
                                          body)))
                last = max(last, lastu)
            else:
                seq.append((last, rtype, item[1]))
        except (ValueError, IndexError, OverflowError, CstError):
            log.error("aof replay: undecodable record skipped")
            parser = make_parser()   # a bad frame may leave stale bytes
    return seq


def _merge_decoded(decoded: list):
    """K-way merge of decoded per-segment streams by uuid, preserving
    FILE order within a segment (barrier frames read live state, so a
    segment's arrival order is its execution order; cross-segment
    records touch disjoint key shards and commute — the parallel
    replay path leans on exactly this).  WMARK records sort with the
    record before them."""
    live = [d for d in decoded if d]
    if len(live) == 1:
        # single populated segment (every unsharded log): file order IS
        # the merge order, skip the per-record k-way scan
        yield from live[0]
        return
    idx = [0] * len(decoded)
    while True:
        best = -1
        best_key = None
        for i, seq in enumerate(decoded):
            if idx[i] < len(seq):
                key = seq[idx[i]][0]
                if best < 0 or key < best_key:
                    best, best_key = i, key
        if best < 0:
            return
        yield decoded[best][idx[best]]
        idx[best] += 1


def _iter_single_stream(recs: list):
    """(rtype, data) items of ONE populated segment, decoded lazily in
    file order — the sortkey bookkeeping `_decode_stream` does for the
    k-way merge is pure overhead when there is nothing to merge with,
    and every unsharded log is this case."""
    from ..resp.codec import make_parser
    parser = make_parser()
    ctx = _frame_ctx()
    for item in recs:
        rtype = item[0]
        try:
            if rtype == REC_FRAME:
                if len(item) == 5:   # pre-decoded at scan time
                    yield rtype, item[1:]
                else:
                    yield rtype, _decode_frame(item[1], parser, ctx)
            elif rtype == REC_BATCH:
                yield rtype, _decode_batch_head(item[1])
            else:
                yield rtype, item[1]
        except (ValueError, IndexError, OverflowError, CstError):
            log.error("aof replay: undecodable record skipped")
            parser = make_parser()   # a bad frame may leave stale bytes


def _merge_streams(streams: list):
    """Decode + k-way merge (see _decode_stream / _merge_decoded);
    yields (rtype, data) pairs."""
    live = [r for r in streams if r]
    if len(live) == 1:
        yield from _iter_single_stream(live[0])
        return
    for item in _merge_decoded([_decode_stream(r) for r in streams]):
        yield item[1:]


def arm(app, info: RecoveryInfo, n_segments: int = 1) -> OpLog:
    """Post-recovery arming (server/io.py start_node): open the live
    OpLog (resuming the current generation's segments), install the
    emission floor, register the buffer bytes with the overload
    governor, fence the repl log at the recovered watermark, and
    surface the recovery gauges in INFO."""
    node = app.node
    lg = OpLog(app.aof_dir, n_segments=n_segments,
               fsync_policy=app.aof_fsync,
               rewrite_pct=app.aof_rewrite_pct,
               rewrite_min_bytes=app.aof_rewrite_min_mb << 20,
               checkpoint_secs=getattr(app, "checkpoint_secs", 0.0),
               checkpoint_min_bytes=int(
                   getattr(app, "checkpoint_min_mb", 1)) << 20,
               node=node)
    lg.tail_truncated = info.tail_truncated
    node.oplog = lg
    lg.install_floor()
    node.governor.register_source(lg.used_buffer_bytes)
    if info.restore_to:
        # point-in-time restore dropped acked records above the target:
        # surviving watermarks over-claim, and the tail still holds the
        # dropped records — void the wmark law for this generation and
        # force an immediate rewrite to cut a fresh base
        lg._wmark_ok = False
        lg._rewrite_asap = True
        try:
            _write_meta(lg.meta_path(lg.dir), lg._meta_fields())
        except OSError:  # pragma: no cover - fs-dependent
            pass
    if node.node_id:
        # persist the identity so a future recovery can distinguish
        # local-origin records even when no snapshot survives
        try:
            _write_meta(lg.meta_path(lg.dir),
                        lg._meta_fields(node_id=node.node_id))
        except OSError:  # pragma: no cover - fs-dependent
            pass
    if info.fence:
        rl = node.repl_log
        rl.last_uuid = max(rl.last_uuid, info.fence)
        rl.evicted_up_to = max(rl.evicted_up_to, info.fence)
        node.hlc.observe(info.fence)
    if info.hlc_mark:
        # the beacon promise survives the crash: every beacon a peer
        # ever saw was capped at a durable HLC mark <= this, so
        # observing it keeps every post-crash mint above them
        node.hlc.observe(info.hlc_mark)
        lg.beacon_cap = info.hlc_mark
    x = node.stats.extra
    x["aof_recovery_source"] = info.source
    x["aof_tail_truncated"] = info.tail_truncated
    x["aof_recovered_ops"] = info.frames + info.batch_frames
    x["aof_recovered_local_max"] = info.local_max
    # every surviving op of THIS node's origin is at or below this —
    # the chaos oracle prunes its journal obligation above it
    x["aof_recovered_fence"] = info.fence
    x["recovery_mode"] = info.mode
    x["recovery_shards"] = info.shards
    x["recovery_merge_rounds"] = info.merge_rounds
    if info.restore_to:
        x["recovery_restore_to"] = info.restore_to
        x["recovery_restore_skipped"] = info.restore_skipped
    if info.quarantined:
        x["aof_segments_quarantined"] = info.quarantined
    if info.skipped:
        x["aof_replay_skipped"] = info.skipped
    if info.frames or info.batches or info.tail_truncated:
        log.info(
            "aof recovery (%s): %d frame ops + %d batch ops replayed, "
            "%d skipped, %d torn tail(s) truncated (%d bytes), fence "
            "%d", info.source, info.frames, info.batch_frames,
            info.skipped, info.tail_truncated, info.truncated_bytes,
            info.fence)
    return lg


def rearm(app, n_segments: int = 1) -> OpLog:
    """Re-open a node's op log WITHOUT replay — for a server rebuild
    over a surviving Node (the chaos harness's warm restart): the state
    lost nothing, the previous close() group-committed the log, so the
    fresh OpLog just resumes appending to the current generation."""
    node = app.node
    old = node.oplog
    if old is not None:
        node.governor.unregister_source(old.used_buffer_bytes)
        old.close()
    lg = OpLog(app.aof_dir, n_segments=n_segments,
               fsync_policy=app.aof_fsync,
               rewrite_pct=app.aof_rewrite_pct,
               rewrite_min_bytes=app.aof_rewrite_min_mb << 20,
               checkpoint_secs=getattr(app, "checkpoint_secs", 0.0),
               checkpoint_min_bytes=int(
                   getattr(app, "checkpoint_min_mb", 1)) << 20,
               node=node)
    node.oplog = lg
    lg.install_floor()
    node.governor.register_source(lg.used_buffer_bytes)
    return lg


async def recover_into_plane(app, restore_to: int = 0) -> RecoveryInfo:
    """Sharded-node boot recovery: the serve workers ARE the store, so
    the chosen snapshot fans out through plane.ingest_batches and log
    frames route to the worker owning their key (the exact per-key
    apply path ShardApplier uses).  Runs as start()'s boot-restore hook
    — plane up, listener not yet accepting.

    Fast-restart structure: segment scan + decode runs in a worker
    thread OVERLAPPED with the snapshot section ingest (the apply side
    waits for the ingest — a failed ingest resets the workers, so
    nothing may land before the snapshot settles).  Per-segment streams
    then replay CONCURRENTLY (CONSTDB_RECOVER_SHARDS; 0 = one task per
    segment, 1 = the serial merged-stream reference) — legal because
    segment-crossing records touch disjoint key shards and CRDT merges
    commute; each task keeps its own buffers so within-segment order is
    preserved end to end, and barriers fall back to the merged serial
    path (a generation containing any is replayed serially)."""
    node = app.node
    plane = node.serve_plane
    info = RecoveryInfo()
    info.restore_to = restore_to
    aof_dir = app.aof_dir
    meta = _read_meta(OpLog.meta_path(aof_dir))
    start_gen = int(meta.get("gen", 0) or 0)
    info.fence = int(meta.get("fence", 0) or 0)
    boot_ok = meta.get("boot_snap_ok", "1") != "0"
    gens = [g for g in OpLog.list_generations(aof_dir) if g >= start_gen]

    from ..conf import env_flag, env_int
    bulk = env_flag("CONSTDB_RECOVER_BULK", True)
    shards_knob = env_int("CONSTDB_RECOVER_SHARDS", 0)

    from ..server.io import _SNAPSHOT_LOAD_ERRORS, _quarantine_snapshot
    from .snapshot import SectionDemux
    loop = asyncio.get_running_loop()

    # -- overlap: scan + torn-tail repair + decode in a worker thread
    # while the snapshot sections stream into the shard workers below
    def _scan_all():
        classes = _frame_ctx()[1:]
        return {g: [_decode_stream(r)
                    for r in scan_generation(aof_dir, g, info, classes)]
                for g in gens}

    scan_fut = loop.run_in_executor(None, _scan_all)

    snap_name = meta.get("snapshot", "")
    base = os.path.join(aof_dir, snap_name) if snap_name else ""
    snap_meta = None
    records = []
    base_failed = False
    for candidate, label in ((base, "aof-base"),
                             (app.snapshot_path if boot_ok else "",
                              "boot")):
        if not candidate or not os.path.exists(candidate) or base_failed:
            continue
        f = await loop.run_in_executor(None, open, candidate, "rb")
        demux = SectionDemux(f)
        try:
            await plane.ingest_batches(demux.batches())
        except _SNAPSHOT_LOAD_ERRORS as e:
            await plane.pool.call_all("reset")
            _quarantine_snapshot(node, candidate, e)
            if candidate == base:
                base_failed = True
                info.wmark_unsafe = True
            continue
        finally:
            f.close()
        snap_meta = demux.meta
        records = demux.replica_rows
        info.source = f"{label}-snapshot"
        break

    decoded = await scan_fut
    if restore_to and snap_meta is not None and \
            snap_meta.repl_last_uuid > restore_to:
        raise OpLogError(
            f"--restore-to {restore_to} predates the recovered snapshot "
            f"cut (uuid {snap_meta.repl_last_uuid}); restore from a "
            "copy of an older checkpoint")

    # -- log replay: frames route to the worker owning their shard (the
    # worker-side per-key apply path); unroutable frames apply on the
    # parent exactly as ShardApplier.aapply does.  BATCH records only
    # appear when a node previously ran unsharded on the same log —
    # decode and aggregate into merge rounds fanned out like snapshot
    # chunks (bulk) or ingest one at a time (serial reference).
    from ..replica import wire
    from ..resp.codec import encode_into
    from ..resp.message import Arr, Bulk, Int, as_bytes
    from ..server.commands import COMMANDS, shard_routable
    from ..store.sharded_keyspace import shard_of
    n_shards = plane.n_shards
    wmarks: list = []
    prog = [_REPLAY_PROGRESS_EVERY]

    class _SegReplay:
        """One record stream's router: per-key frames buffer toward the
        owning worker, barriers drain and apply on the parent, batch
        records aggregate into merge rounds.  One instance per
        concurrent segment task — buffers and futures are private, so
        within-segment order survives the concurrency."""

        def __init__(self):
            self.bufs = [bytearray() for _ in range(n_shards)]
            self.counts = [0] * n_shards
            self.pending = 0
            self.round: list = []
            self.round_rows = 0

        async def flush_routed(self):
            if not self.pending:
                return
            futs = []
            for s in range(n_shards):
                if self.counts[s]:
                    futs.append((s, plane.pool.submit(
                        s, ("apply", bytes(self.bufs[s]),
                            self.counts[s]))))
                    self.bufs[s] = bytearray()
                    self.counts[s] = 0
            self.pending = 0
            for s, fut in futs:
                entries, _deleted, _stats = await fut
                if entries:
                    plane.merged.segments[s].push_many(entries)

        async def flush_round(self):
            rnd, self.round = self.round, []
            self.round_rows = 0
            if rnd:
                # one wide batch per round: ingest_batches splits,
                # encodes and submits per batch, so concatenating
                # first pays those once per round (engine/base.py)
                from ..engine.base import concat_batches
                await plane.ingest_batches([concat_batches(rnd)])
                info.merge_rounds += 1

        def _observe(self, origin, uuid):
            info.replayed_max = max(info.replayed_max, uuid)
            if origin == node.node_id:
                info.local_max = max(info.local_max, uuid)
            node.hlc.observe(uuid)
            done = info.frames + info.batch_frames
            if done >= prog[0]:
                prog[0] += _REPLAY_PROGRESS_EVERY
                log.info("aof replay progress: %d ops replayed "
                         "(%d skipped, %d merge rounds)", done,
                         info.skipped, info.merge_rounds)

        async def run(self, items):
            for item in items:
                rtype = item[1]
                if rtype == REC_FRAME:
                    origin, uuid, name, args = item[2]
                    if restore_to and uuid > restore_to:
                        info.restore_skipped += 1
                        continue
                    cmd = COMMANDS.get(name) or \
                        COMMANDS.get(name.lower())
                    routable = cmd is not None and \
                        shard_routable(cmd) and len(args) >= 1
                    key = None
                    if routable:
                        try:
                            key = as_bytes(args[0])
                        except CstError:
                            key = None
                    if key is not None:
                        # a pending batch round must land before any
                        # later frame touches its keys in a worker
                        if self.round:
                            await self.flush_round()
                        s = shard_of(key, n_shards)
                        encode_into(self.bufs[s], Arr([
                            Bulk(b"replicate"), Int(origin), Int(0),
                            Int(uuid), Bulk(name), *args]))
                        self.counts[s] += 1
                        self.pending += 1
                        info.frames += 1
                        if self.pending >= 512:
                            await self.flush_routed()
                    else:
                        await self.flush_round()
                        await self.flush_routed()
                        try:
                            node.apply_replicated(name, args, origin,
                                                  uuid)
                            info.frames += 1
                        except CstError as e:
                            log.warning("aof replay: op %d (%s) failed "
                                        "(%s); skipped", uuid, name, e)
                            info.skipped += 1
                    self._observe(origin, uuid)
                elif rtype == REC_BATCH:
                    origin, bbase, lastu, n, body = item[2]
                    if restore_to and lastu > restore_to:
                        info.restore_skipped += n
                        continue
                    await self.flush_routed()
                    try:
                        wb = wire.decode_wire_batch(body, node.ks,
                                                    origin, bbase)
                        if wb.n_frames != n:
                            raise wire.WireFormatError(
                                "frame count mismatch")
                    except wire.WireFormatError as e:
                        log.error("aof replay: undecodable batch "
                                  "record (%s); skipping %d ops", e, n)
                        info.skipped += n
                        continue
                    if bulk:
                        b = wb.finalize()
                        # close before crossing the row budget (the
                        # host micro-strategy ceiling — see
                        # _REPLAY_ROUND_ROWS)
                        if self.round and self.round_rows + b.n_rows \
                                > _REPLAY_ROUND_ROWS:
                            await self.flush_round()
                        self.round.append(b)
                        self.round_rows += b.n_rows
                    else:
                        await plane.ingest_batches([wb.finalize()])
                    info.batches += 1
                    info.batch_frames += n
                    self._observe(origin, lastu)
                else:
                    try:
                        w = _decode_wmark(item[2])
                        info.wmarks += 1
                        info.hlc_mark = max(info.hlc_mark, w[1])
                        if not restore_to or w[0] <= restore_to:
                            wmarks.append(w)
                    except (ValueError, IndexError, OverflowError):
                        log.error("aof replay: undecodable wmark "
                                  "skipped")
            await self.flush_round()
            await self.flush_routed()

    for gen in gens:
        streams = decoded.get(gen, [])
        nonempty = [s for s in streams if s]
        parallel = shards_knob != 1 and len(nonempty) > 1 and not any(
            r[1] == REC_BATCH for s in nonempty for r in s)
        if parallel:
            conc = len(nonempty) if shards_knob <= 0 \
                else min(shards_knob, len(nonempty))
            info.shards = max(info.shards, conc)
            sem = asyncio.Semaphore(conc)

            async def _one(items):
                async with sem:
                    await _SegReplay().run(items)

            await asyncio.gather(*[_one(s) for s in nonempty])
        else:
            await _SegReplay().run(_merge_decoded(streams))

    info.mode = ("bulk" if bulk else "serial") + (
        f"+shards{info.shards}" if info.shards > 1 else "")
    if info.frames or info.batches:
        info.source = (info.source + "+log") if snap_meta is not None \
            else "log-only"
    elif snap_meta is None:
        info.source = "empty"

    if snap_meta is not None:
        node.hlc.observe(snap_meta.repl_last_uuid)
        info.fence = max(info.fence, snap_meta.repl_last_uuid)
    adopt = list(records)
    # the newest surviving WMARK wins: landed coverage is non-decreasing
    # in file order, and all WMARKs live in one segment's stream
    wmark = None
    for w in wmarks:
        if wmark is None or w[0] >= wmark[0]:
            wmark = w
    if wmark is not None and not info.wmark_unsafe:
        landed, _hlc, wrecords = wmark
        info.fence = max(info.fence, landed)
        adopt.extend(wrecords)
    if adopt:
        node.replicas.merge_records(adopt, my_addr=app.advertised_addr,
                                    adopt_watermarks=not info.wmark_unsafe)
    info.fence = max(info.fence, info.local_max, info.replayed_max
                     if info.wmark_unsafe else 0)
    arm(app, info, n_segments=n_shards + 1)
    return info


def prescan_node_id(aof_dir: str, boot_snapshot: str = "") -> int:
    """The node identity a recovery would restore, WITHOUT replaying
    anything — the sharded boot path needs it before the workers spawn
    (they stamp it into writes)."""
    meta = _read_meta(OpLog.meta_path(aof_dir))
    nid = int(meta.get("node_id", 0) or 0)
    if nid:
        return nid
    from .snapshot import SnapshotLoader
    snap_name = meta.get("snapshot", "")
    boot_ok = meta.get("boot_snap_ok", "1") != "0"
    for candidate in (os.path.join(aof_dir, snap_name) if snap_name
                      else "", boot_snapshot if boot_ok else ""):
        if not candidate or not os.path.exists(candidate):
            continue
        try:
            with open(candidate, "rb") as f:
                for kind, payload in SnapshotLoader(f):
                    if kind == "node":
                        if payload.node_id:
                            return payload.node_id
                        break
        except Exception:  # noqa: BLE001 - recovery quarantines later
            continue
    return 0


def recover(node, aof_dir: str, boot_snapshot: str = "",
            engine=None, bulk=None, restore_to: int = 0) -> RecoveryInfo:
    """Single-keyspace boot recovery: base/boot snapshot + oplog tail,
    replayed through the real merge path (module docstring).  The
    caller (server/io.py start_node) sets the repl-log fences and INFO
    gauges from the returned RecoveryInfo.  Blocking; runs before the
    listener opens.

    `bulk` selects the merge-round landing strategy (None reads
    CONSTDB_RECOVER_BULK; see _ReplayApplier).  `restore_to` caps the
    replay at a point-in-time uuid: records above it are skipped (batch
    records at record granularity — a batch whose last uuid exceeds the
    target is dropped whole), watermarks above it are not adopted, and
    the caller must re-base the log afterwards (arm() flags the log
    dirty so the next rewrite cuts a fresh generation)."""
    from ..conf import env_flag
    if bulk is None:
        bulk = env_flag("CONSTDB_RECOVER_BULK", True)
    info = RecoveryInfo()
    info.mode = "bulk" if bulk else "serial"
    info.restore_to = restore_to
    meta = _read_meta(OpLog.meta_path(aof_dir))
    start_gen = int(meta.get("gen", 0) or 0)
    info.fence = int(meta.get("fence", 0) or 0)
    boot_ok = meta.get("boot_snap_ok", "1") != "0"
    gens = [g for g in OpLog.list_generations(aof_dir) if g >= start_gen]

    # -- snapshot source: the AOF base (log-consistent cut) when one
    # exists, the boot snapshot otherwise (its state covers its
    # watermarks — a consistent cut too; replaying the whole log over
    # it is idempotent re-merge).  A wipe fence forbids the boot
    # snapshot (it holds pre-wipe state).
    snap_name = meta.get("snapshot", "")
    base = os.path.join(aof_dir, snap_name) if snap_name else ""
    snap_meta = None
    records = []
    from ..server.io import _SNAPSHOT_LOAD_ERRORS, _quarantine_snapshot
    from .snapshot import load_snapshot
    base_failed = False
    for candidate, label in ((base, "aof-base"),
                             (boot_snapshot if boot_ok else "", "boot")):
        if not candidate or not os.path.exists(candidate) or base_failed:
            continue
        try:
            snap_meta, records = load_snapshot(candidate, node.ks,
                                               engine=engine or node.engine)
            info.source = f"{label}-snapshot"
            break
        except _SNAPSHOT_LOAD_ERRORS as e:
            node.replace_keyspace()
            _quarantine_snapshot(node, candidate, e)
            if candidate == base:
                # the base covered every pre-rewrite frame the log's
                # WMARKs may claim; with it gone, adopting them (or the
                # OLDER boot snapshot) would skip redelivery of ops the
                # recovered state lacks — replay ops only, keep
                # watermarks at zero, and let the peers resync us
                base_failed = True
                info.wmark_unsafe = True

    if restore_to and snap_meta is not None and \
            snap_meta.repl_last_uuid > restore_to:
        raise OpLogError(
            f"--restore-to {restore_to} predates the recovered snapshot "
            f"cut (uuid {snap_meta.repl_last_uuid}); restore from a "
            "copy of an older checkpoint")

    # -- log replay through the real apply path
    applier = _ReplayApplier(node, info, bulk=bulk)
    wmark = None
    classes = _frame_ctx()[1:]
    for gen in gens:
        for item in _merge_streams(
                scan_generation(aof_dir, gen, info, classes, raw=bulk)):
            rtype = item[0]
            if rtype == REC_FRAME:
                if restore_to and item[1][1] > restore_to:
                    info.restore_skipped += 1
                    continue
                applier.frame(*item[1])
            elif rtype == REC_BATCH:
                if restore_to and item[1][2] > restore_to:
                    info.restore_skipped += item[1][3]
                    continue
                applier.batch(*item[1])
            else:
                try:
                    w = _decode_wmark(item[1])
                    info.wmarks += 1
                    info.hlc_mark = max(info.hlc_mark, w[1])
                    if not restore_to or w[0] <= restore_to:
                        wmark = w
                except (ValueError, IndexError, OverflowError):
                    log.error("aof replay: undecodable wmark skipped")
        # generation boundary = a rewrite cut: land everything before
        # the next generation's records (they may read barrier state)
        applier.drain()
    applier.drain()
    if info.frames or info.batches:
        info.source = (info.source + "+log") if snap_meta is not None \
            else "log-only"
    elif snap_meta is None:
        info.source = "empty"

    # -- watermarks: snapshot records first (state-backed), then the
    # newest surviving WMARK (log-cut-backed: every frame it claims is
    # in the valid prefix BEFORE it — the consistency-cut law)
    if snap_meta is not None:
        if snap_meta.node_id and not node.node_id:
            node.node_id = snap_meta.node_id
        node.hlc.observe(snap_meta.repl_last_uuid)
        info.fence = max(info.fence, snap_meta.repl_last_uuid)
    adopt = list(records)
    if wmark is not None and not info.wmark_unsafe:
        landed, _hlc, wrecords = wmark
        info.fence = max(info.fence, landed)
        adopt.extend(wrecords)
    if adopt and node.replicas is not None:
        # membership always merges (the mesh must re-form around us);
        # pull watermarks adopt only when the backing state survived
        # whole — adopt_watermarks=False keeps them at zero and the
        # peers resync us instead (merge_records' own coupling law)
        node.replicas.merge_records(adopt, my_addr=node.addr or "",
                                    adopt_watermarks=not info.wmark_unsafe)
    info.fence = max(info.fence, info.local_max, info.replayed_max
                     if info.wmark_unsafe else 0)
    return info
