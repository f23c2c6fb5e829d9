"""Encode-once run cache: N push loops, one wire encoding.

A master-master node with N peers runs N independent push loops that
each drain the SAME repl-log runs; before this cache every loop
re-encoded the run — per-frame RESP or a REPLBATCH columnar payload —
so steady-state replication CPU scaled O(N·ops) when the encode work is
O(ops).  The first loop to drain a run now publishes the finished wire
bytes here; the other loops at the same cursor splice them into their
own socket buffer, so their per-peer work drops to dup/window
bookkeeping plus the write itself.

Keying (the "caps-class" law, docs/INVARIANTS.md "Broadcast plane"):
an entry is (caps_class, cursor) -> (end_cursor, bytes, counters).
`caps_class` captures EVERYTHING that changes the bytes a peer may
legally receive — "b" (REPLBATCH plain), "bz" (REPLBATCH with
negotiated CAP_COMPRESS framing), "f" (the byte-exact per-frame
rendering legacy and demoted peers get — so one legacy peer does not
reintroduce O(N) encode for everyone sharing its cursor range).  Two
peers in different classes never share bytes; two peers in the same
class at the same cursor always may, because the encoding is a pure
function of (class, cursor, log tail) and node-level knobs the class
pins.

Coherence with ring eviction: entries are immutable copies of the run's
bytes, so they stay CORRECT even after the ring evicts the entries they
were built from — but no new reader can ever be at a cursor below
`evicted_up_to` (the push loop's `can_resume_from` forces a resync
first), so such entries are dead weight and are swept.

Bounding: byte-capped LRU (CONSTDB_ENCODE_CACHE_MB; 0 disables) plus
ref-counting — an entry is published with the number of OTHER live
links expected to read it and is dropped the moment the last expected
reader consumes it (or immediately not cached when there are none, so a
single-peer node pays zero overhead).  The resident bytes are a
registered `used_memory` source for the overload governor
(server/overload.py "accounting completeness").
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional


class CachedRun:
    """One published wire encoding of a drained run."""

    __slots__ = ("end", "payload", "batches", "batch_frames",
                 "comp_raw", "comp_wire", "refs", "frames")

    def __init__(self, end: int, payload: bytes, batches: int,
                 batch_frames: int, comp_raw: int,
                 comp_wire: int, refs: int, frames: int = 0):
        self.end = end                  # cursor after the run
        self.payload = payload          # finished wire bytes
        self.batches = batches          # REPLBATCH frames inside
        self.batch_frames = batch_frames  # ops they cover
        self.comp_raw = comp_raw        # compression accounting
        self.comp_wire = comp_wire
        self.refs = refs                # expected remaining readers
        self.frames = frames            # log entries the run holds


class RunEncodeCache:
    """Bounded, ref-counted (caps_class, cursor) -> CachedRun map."""

    def __init__(self, cap_bytes: int = 16 << 20):
        self.cap_bytes = cap_bytes
        self._map: OrderedDict[tuple, CachedRun] = OrderedDict()
        self.bytes = 0

    def configure(self, cap_bytes: int) -> None:
        self.cap_bytes = cap_bytes
        self._shrink()

    @property
    def enabled(self) -> bool:
        return self.cap_bytes > 0

    def used_bytes(self) -> int:
        """Governed residency (overload-governor source)."""
        return self.bytes

    def __len__(self) -> int:
        return len(self._map)

    # ---------------------------------------------------------------- ops

    def get(self, caps_class: str, cursor: int,
            below: Optional[int] = None) -> Optional[CachedRun]:
        """The published encoding starting exactly after `cursor`, or
        None (the caller encodes and `put`s).  Consuming the last
        expected reader's reference drops the entry.  (Hit/miss GAUGES
        live on NodeStats — repl_encode_cache_hits/misses, counted by
        the push loop per DRAINED run, not per empty poll.)

        `below`: the caller's emission floor (repl-log floor
        discipline) — an entry whose run reaches at/past it is NOT
        handed out (and its refs are untouched: the caller will be
        back once the floor clears).  Load-bearing for the durable op
        log's emit-only-durable law: the serve path publishes a run's
        encoding at flush time, BEFORE its group commit lands, and an
        ungated splice would emit ops a torn tail could still lose
        (persist/oplog.py; caught by the chaos everysec cell)."""
        e = self._map.get((caps_class, cursor))
        if e is None:
            return None
        if below is not None and e.end >= below:
            return None
        e.refs -= 1
        if e.refs <= 0:
            self._drop((caps_class, cursor))
        else:
            self._map.move_to_end((caps_class, cursor))
        return e

    def put(self, caps_class: str, cursor: int, end: int, payload: bytes,
            batches: int = 0, batch_frames: int = 0,
            comp_raw: int = 0, comp_wire: int = 0,
            readers: int = 0, frames: int = 0) -> None:
        """Publish a finished encoding.  `readers`: how many OTHER links
        are expected to drain this range — <= 0 skips caching entirely
        (nobody to share with)."""
        if not self.enabled or readers <= 0 or not payload:
            return
        key = (caps_class, cursor)
        if key in self._map:
            self._drop(key)
        self._map[key] = CachedRun(end, payload, batches, batch_frames,
                                   comp_raw, comp_wire, readers, frames)
        self.bytes += len(payload)
        self._shrink()

    def evict_below(self, evicted_up_to: int) -> None:
        """Ring-eviction sweep: entries whose start cursor fell below
        the resumable horizon can never be read again (no peer can
        legally sit at that cursor — it would resync instead)."""
        if not self._map:
            return
        dead = [k for k in self._map if k[1] < evicted_up_to]
        for k in dead:
            self._drop(k)

    def clear(self) -> None:
        self._map.clear()
        self.bytes = 0

    # ------------------------------------------------------------ internal

    def _drop(self, key: tuple) -> None:
        e = self._map.pop(key, None)
        if e is not None:
            self.bytes -= len(e.payload)

    def _shrink(self) -> None:
        while self.bytes > self.cap_bytes and self._map:
            key = next(iter(self._map))  # LRU head
            self._drop(key)
