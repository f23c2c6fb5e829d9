"""Versioned hot-key reply cache: finished RESP reply bytes by key.

Canonical cache traffic is read-dominated, and a CRDT read is a pure
function of converged state (PAPERS.md: Approaches to CRDTs — queries
never mutate merge state), which makes its finished reply *cacheable by
version*: the serve coalescer's read planner (server/serve.py) stores
the encoded reply bytes of a key-scoped read here under
`(command, key, args-digest)` and replays them verbatim while the key's
state is provably unchanged.

Two mechanisms keep a cached reply exact, and both must hold
(docs/INVARIANTS.md "Read coalescing laws"):

  * **invalidate-before-visible** — every mutation intake drops the
    written keys' entries BEFORE the mutation becomes readable: the
    client op path (`commands.execute`), the per-frame replication path
    (`commands.apply_replicated`), and every batched merge — serve
    coalescer flushes, coalesced replication apply, columnar wire
    batches, snapshot/delta ingest, oplog replay — via the one engine
    seam they all ride (`Node.merge_batch`/`merge_batches`).  Sharded
    nodes hold one cache per shard worker (the worker's Node owns it),
    so each worker invalidates exactly its own shard.  State wipes
    (full resync) clear the cache outright.
  * **envelope stamp** — each entry records the key's envelope
    `(ct, mt, dt, expire)` at fill time and is served only while the
    live envelope still matches (expiry-armed keys are never cached at
    all — their replies are time-dependent).  Member-scoped kinds
    (sismember/hget — reply reads ONE element) skip the ct/mt checks
    (stored as -1): EVERY element write advances both (updated_at's max
    rule) while touching only the members it names, and those members'
    entries are exactly what the member-scoped intake hooks drop
    (`invalidate_key_members`); dt/expire still verify, so key
    delete/expiry always invalidates structurally.  The stamp
    is defense in depth against an invalidation path the first law
    missed; it is NOT sufficient alone (an element write carrying an
    old uuid can change visible content without moving the envelope),
    which is why the intake hooks are the law and the stamp the belt.

GC and element-table compaction never invalidate: they preserve visible
state by construction, and entries hold finished bytes, not row ids.

Entries and the by-key index hold atoms only — tuples of ints and bytes,
which the collector stops tracking at the first collection that sees
them — so a warm cache adds nothing to what a later collection walks
(docs/INVARIANTS.md, "The frozen heap").

Bounded: LRU over payload bytes (`CONSTDB_READ_CACHE_MB`; 0 disables),
a single entry never exceeds 1/8 of the cap, and the resident bytes are
a `used_memory` source for the overload governor, whose hard-watermark
reclaim drops the whole cache (server/overload.py — it is exactly a
rebuildable warm cache).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import numpy as np

_I64 = np.int64

# per-entry bookkeeping overhead charged on top of the payload bytes
# (dict slots, the stamp tuple, the by-key index entry)
_ENTRY_OVERHEAD = 200

# a key's index stays a tuple up to this many entries (a tuple of atoms
# leaves the collector's lists at its first collection; a set never
# does), and turns into a set past it, where a tuple's copy per add or
# drop would cost more than the set's walk
_INDEX_TUPLE_MAX = 16


def _noop(*_a) -> None:
    return None


# member-scoped entry kinds: their reply depends on ONE element of the
# key (the args-digest member/field), so an element write invalidates
# only the touched members' entries (invalidate_key_members) — every
# other kind reads the whole key and always drops
_MEMBER_SCOPED = frozenset((b"sismember", b"hget"))


class ReadReplyCache:
    """Bounded (command, key, args) -> stamped reply-bytes map."""

    __slots__ = ("cap_bytes", "bytes", "hits", "misses", "invalidations",
                 "_map", "_by_key")

    def __init__(self, cap_bytes: int = 0) -> None:
        self.cap_bytes = cap_bytes
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.invalidations = 0
        # (name, key, extra) -> (kid, ct, mt, dt, payload)
        self._map: OrderedDict[tuple, tuple] = OrderedDict()
        # key -> its entries' (name, key, extra): a tuple while small, a
        # set past _INDEX_TUPLE_MAX (module docstring: atoms only)
        self._by_key: dict[bytes, tuple | set] = {}

    def configure(self, cap_bytes: int) -> None:
        self.cap_bytes = max(0, cap_bytes)
        if not self.cap_bytes:
            self.clear()
        else:
            self._shrink()

    @property
    def enabled(self) -> bool:
        return self.cap_bytes > 0

    def __len__(self) -> int:
        return len(self._map)

    def used_bytes(self) -> int:
        """Governed residency (overload-governor source)."""
        return self.bytes

    # ----------------------------------------------------------------- ops

    def get(self, name: bytes, key: bytes, extra: bytes,
            ks) -> Optional[bytes]:
        """The cached reply, iff the key's live envelope still matches
        the entry's fill-time stamp (a mismatch drops the entry — some
        write moved the envelope without passing an intake hook we
        instrument, e.g. a lazy expiry raced the EXPIRE invalidation).
        Absent-key entries (kid == -1) verify by the key still being
        absent — exact, since an absent key has one fixed reply per
        command.  Counts a hit or a miss either way.  Delegates to
        `get_batch` so the stamp-verify rule lives in one place."""
        return self.get_batch([(name, key, extra)], ks)[0]

    def get_batch(self, reqs: list, ks) -> list:
        """Batched probe for one planned read run: `reqs` is a list of
        `(name, key, extra)` tuples, the result a payload-or-None list.
        All present entries' stamps verify in ONE vectorized pass over
        the envelope columns (the per-entry scalar reads were the
        measured hot cost of the hit path); mismatched entries drop
        exactly like `get`'s."""
        m = self._map
        ents = [m.get(r) for r in reqs]
        hit_idx = [i for i, e in enumerate(ents) if e is not None]
        out: list = [None] * len(reqs)
        if not hit_idx:
            self.misses += len(reqs)
            return out
        keys = ks.keys
        under_pressure = self.bytes * 2 >= self.cap_bytes
        move = m.move_to_end if under_pressure else _noop
        if len(hit_idx) < 16:
            # below the vectorization floor the fancy-index setup costs
            # more than the scalar verifies it replaces
            hits = 0
            ct, mt, dt, exp = keys.ct, keys.mt, keys.dt, keys.expire
            lookup = ks.key_index.lookup
            for i in hit_idx:
                ent = ents[i]
                kid = ent[0]
                if kid < 0:
                    good = lookup(reqs[i][1]) < 0
                else:
                    good = dt[kid] == ent[3] and not exp[kid] and \
                        (ent[2] < 0 or (ct[kid] == ent[1] and
                                        mt[kid] == ent[2]))
                if good:
                    out[i] = ent[4]
                    move(reqs[i])
                    hits += 1
                else:
                    self._drop(reqs[i])
            self.hits += hits
            self.misses += len(reqs) - hits
            return out
        pos_idx = [i for i in hit_idx if ents[i][0] >= 0]
        neg_idx = [i for i in hit_idx if ents[i][0] < 0]
        ok_by_i: dict = {}
        if pos_idx:
            kid_arr = np.fromiter((ents[i][0] for i in pos_idx),
                                  dtype=_I64, count=len(pos_idx))
            mt_st = np.fromiter((ents[i][2] for i in pos_idx),
                                dtype=_I64, count=len(pos_idx))
            # member-scoped entries (stamp -1) skip the ct/mt checks
            ok = (mt_st < 0) | (
                (keys.ct[kid_arr] ==
                 np.fromiter((ents[i][1] for i in pos_idx), dtype=_I64,
                             count=len(pos_idx))) &
                (keys.mt[kid_arr] == mt_st))
            ok &= (keys.dt[kid_arr] ==
                   np.fromiter((ents[i][3] for i in pos_idx), dtype=_I64,
                               count=len(pos_idx)))
            ok &= keys.expire[kid_arr] == 0
            for x, i in enumerate(pos_idx):
                ok_by_i[i] = bool(ok[x])
        if neg_idx:
            # absent-key entries: one batched index probe proves every
            # key is STILL absent
            found = ks.key_index.lookup_batch(
                [reqs[i][1] for i in neg_idx])
            for x, i in enumerate(neg_idx):
                ok_by_i[i] = found[x] < 0
        hits = 0
        for i in hit_idx:
            if ok_by_i[i]:
                out[i] = ents[i][4]
                move(reqs[i])
                hits += 1
            else:
                self._drop(reqs[i])
        self.hits += hits
        self.misses += len(reqs) - hits
        return out

    def put(self, name: bytes, key: bytes, extra: bytes, kid: int,
            ks, payload: bytes, env=None) -> None:
        """Stamp + store one finished reply.  Expiry-armed keys are
        never cacheable (time-dependent visibility); ABSENT keys are
        (`kid < 0` — their reply is fixed per command until a creation,
        which every intake hook invalidates, and the hit-time verify
        re-proves absence); oversized replies (> cap/8) are skipped
        rather than evicting the whole working set.  `env`: the key's
        already-gathered `(ct, dt, expire)`-era stamp source as
        `(ct, dt)` with expire known 0 — the read planner passes it so
        the fill pays no column re-reads (mt is read here either way)."""
        if not self.enabled:
            return
        if len(payload) + _ENTRY_OVERHEAD > self.cap_bytes >> 3:
            return
        if kid >= 0:
            keys = ks.keys
            # member-scoped kinds (sismember/hget) read ONE element:
            # their stamp skips ct/mt (stored -1), because EVERY element
            # write advances both (updated_at's max rule) while touching
            # only the members it names — which the member-scoped intake
            # hooks already invalidate exactly.  dt/expire still verify:
            # key deletes bump dt (and fully invalidate at intake), and
            # expiry arming must always drop.
            if name in _MEMBER_SCOPED:
                if env is not None:
                    ent = (kid, -1, -1, env[1], payload)
                elif int(keys.expire[kid]) != 0:
                    return  # time-dependent visibility — never cached
                else:
                    ent = (kid, -1, -1, int(keys.dt[kid]), payload)
            elif env is not None:
                ent = (kid, env[0], int(keys.mt[kid]), env[1], payload)
            else:
                if int(keys.expire[kid]) != 0:
                    return  # time-dependent visibility — never cached
                ent = (kid, int(keys.ct[kid]), int(keys.mt[kid]),
                       int(keys.dt[kid]), payload)
        else:
            ent = (-1, 0, 0, 0, payload)
        k = (name, key, extra)
        if k in self._map:
            self._drop(k)
        self._map[k] = ent
        idx = self._by_key.get(key)
        if idx is None:
            self._by_key[key] = (k,)
        elif type(idx) is set:
            idx.add(k)
        elif len(idx) < _INDEX_TUPLE_MAX:
            self._by_key[key] = idx + (k,)
        else:
            self._by_key[key] = {*idx, k}
        self.bytes += len(payload) + _ENTRY_OVERHEAD
        self._shrink()

    # -------------------------------------------------------- invalidation

    def invalidate_key(self, key: bytes) -> None:
        """Drop every entry for `key` (one mutation intake observed it)."""
        ks = self._by_key.pop(key, None)
        if not ks:
            return
        self.invalidations += len(ks)
        for k in ks:
            ent = self._map.pop(k, None)
            if ent is not None:
                self.bytes -= len(ent[4]) + _ENTRY_OVERHEAD

    def invalidate_key_members(self, key: bytes, members) -> None:
        """Element-write intake (sadd/srem/hset/hdel): the write touches
        exactly `members` of `key`, so member-scoped entries (sismember/
        hget — their reply reads ONE element) survive unless their
        member was touched; every whole-key kind (scans, counts, get,
        envelope-dependent replies) drops.  This is what lets a hot
        key's probe working set survive writes to its other members.
        Falls back to the full drop when `members` is None (shape the
        caller could not scope)."""
        ks = self._by_key.get(key)
        if not ks:
            return
        if members is None:
            self.invalidate_key(key)
            return
        memberset = members if type(members) is set else set(members)
        dead = [k for k in ks
                if k[0] not in _MEMBER_SCOPED or k[2] in memberset]
        self.invalidations += len(dead)
        m = self._map
        for k in dead:
            ent = m.pop(k, None)
            if ent is not None:
                self.bytes -= len(ent[4]) + _ENTRY_OVERHEAD
        self._unindex(key, dead)

    def invalidate_keys(self, keys) -> None:
        """Bulk intake (a merged ColumnarBatch's key lists).  When the
        batch names more keys than the cache holds entries, clearing
        outright is cheaper than probing each key (snapshot ingest at
        north-star scale must not pay O(rows) dict probes into an
        empty cache)."""
        if not self._map:
            return
        try:
            n = len(keys)
        except TypeError:
            keys = list(keys)
            n = len(keys)
        if n >= len(self._map):
            self.invalidations += len(self._map)
            self._map.clear()
            self._by_key.clear()
            self.bytes = 0
            return
        by_key = self._by_key
        for key in keys:
            if key in by_key:
                self.invalidate_key(key)

    def clear(self) -> None:
        """State wipe / hard-watermark reclaim: drop everything (counted
        as invalidations — the gauges must explain a hit-rate cliff)."""
        self.invalidations += len(self._map)
        self._map.clear()
        self._by_key.clear()
        self.bytes = 0

    # ------------------------------------------------------------ internal

    def _drop(self, k: tuple) -> None:
        ent = self._map.pop(k, None)
        if ent is None:
            return
        self.bytes -= len(ent[4]) + _ENTRY_OVERHEAD
        self._unindex(k[1], (k,))

    def _unindex(self, key: bytes, gone) -> None:
        """Take the entries `gone` out of `key`'s index."""
        idx = self._by_key.get(key)
        if idx is None:
            return
        if type(idx) is set:
            idx.difference_update(gone)
        else:
            idx = tuple([k for k in idx if k not in gone])
        if idx:
            self._by_key[key] = idx
        else:
            del self._by_key[key]

    def _shrink(self) -> None:
        while self.bytes > self.cap_bytes and self._map:
            self._drop(next(iter(self._map)))
