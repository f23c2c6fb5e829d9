"""Ordered-sequence CRDT: dense position identifiers + element tombstones.

Capability completion for the reference's `Sequence`/`List` scaffold
(reference src/crdt/list.rs:4-43): there it is an ordered-insert linked
list keyed by u128 ids, wired to nothing (SURVEY.md §2.5).  This is a
WORKING replicated list: every element gets a position identifier drawn
between its neighbors' (LSEQ-style path of (digit, node) pairs, so
identifiers from concurrent inserts at the same spot order
deterministically by writer node), deletes tombstone by identifier, and
merge is a keyed LWW union — commutative, associative, idempotent.

Positions are drawn EDGE-AWARE (LSEQ's boundary allocation, Nédelec et
al. 2013): where one side of the gap has run out of digits at the level
the new digit goes, the new digit sits one slot from the other side
instead of in the middle of the gap.  A run of head pushes then walks down
one slot at a time and a run of tail pushes up, ~32,767 of them to a
level, where the midpoint would add a level every 16.  The byte form and
its order are unchanged, so positions drawn either way stay valid.

`Sorted` is the ordered container both the CRDT here and the store's list
index (store/keyspace.py ListIndex) keep their entries in: chunks of at
most 2 * LOAD keys, so an insert or a removal anywhere costs O(log n) plus
a chunk's memmove, and a read of the first k entries O(k).
"""

from __future__ import annotations

import bisect
from typing import Iterator, Optional

# each path digit is (slot, node); slot space per level
_BASE = 1 << 16


class Sorted:
    """Distinct keys in order, each with an item, in chunks."""

    LOAD = 512
    __slots__ = ("_keys", "_items", "_first", "_len")

    def __init__(self, pairs=()) -> None:
        """`pairs`: (key, item) in key order, keys distinct."""
        pairs = list(pairs)
        step = self.LOAD
        self._keys = [[k for k, _ in pairs[i:i + step]]
                      for i in range(0, len(pairs), step)]
        self._items = [[v for _, v in pairs[i:i + step]]
                       for i in range(0, len(pairs), step)]
        self._first = [ks[0] for ks in self._keys]
        self._len = len(pairs)

    def __len__(self) -> int:
        return self._len

    def _chunk(self, key) -> int:
        """The chunk `key` belongs in (its first key is <= key)."""
        return max(bisect.bisect_right(self._first, key) - 1, 0)

    def get(self, key, default=None):
        if not self._len:
            return default
        c = self._chunk(key)
        ks = self._keys[c]
        j = bisect.bisect_left(ks, key)
        return self._items[c][j] if j < len(ks) and ks[j] == key else default

    def insert(self, key, item) -> bool:
        """Add `key`; an existing key keeps its item.  -> whether added."""
        if not self._len:
            self._keys, self._items, self._first = [[key]], [[item]], [key]
            self._len = 1
            return True
        c = self._chunk(key)
        ks = self._keys[c]
        j = bisect.bisect_left(ks, key)
        if j < len(ks) and ks[j] == key:
            return False
        ks.insert(j, key)
        self._items[c].insert(j, item)
        self._first[c] = ks[0]
        self._len += 1
        if len(ks) > 2 * self.LOAD:
            half = len(ks) // 2
            its = self._items[c]
            self._keys[c + 1:c + 1] = [ks[half:]]
            self._items[c + 1:c + 1] = [its[half:]]
            del ks[half:], its[half:]
            self._first.insert(c + 1, self._keys[c + 1][0])
        return True

    def remove(self, key) -> bool:
        """Drop `key` if present.  -> whether it was."""
        if not self._len:
            return False
        c = self._chunk(key)
        ks = self._keys[c]
        j = bisect.bisect_left(ks, key)
        if j >= len(ks) or ks[j] != key:
            return False
        del ks[j], self._items[c][j]
        self._len -= 1
        if ks:
            self._first[c] = ks[0]
        else:
            del self._keys[c], self._items[c], self._first[c]
        return True

    def first(self):
        return self._keys[0][0] if self._len else None

    def last(self):
        return self._keys[-1][-1] if self._len else None

    def before(self, key):
        """The greatest key below `key`, or None."""
        if not self._len:
            return None
        c = self._chunk(key)
        j = bisect.bisect_left(self._keys[c], key)
        if j:
            return self._keys[c][j - 1]
        return self._keys[c - 1][-1] if c else None

    def chunks(self) -> Iterator[tuple]:
        """(keys, items) chunk by chunk, in order (read-only views)."""
        return zip(self._keys, self._items)

    def items(self) -> Iterator[tuple]:
        for ks, its in zip(self._keys, self._items):
            yield from zip(ks, its)


class Sequence:
    __slots__ = ("items", "n_live")

    def __init__(self) -> None:
        # by position id: pos -> [value, add_t, del_t]
        self.items = Sorted()
        self.n_live = 0

    # ----------------------------------------------------------- positions

    @staticmethod
    def _between(lo: Optional[tuple], hi: Optional[tuple], node: int) -> tuple:
        """A fresh position strictly between lo and hi, edge-aware (see the
        module docstring)."""
        lo = lo or ()
        hi = hi or ()
        path = []
        level = 0
        while True:
            lo_out = level >= len(lo)
            hi_out = level >= len(hi)
            lo_d = (0, 0) if lo_out else lo[level]
            hi_d = (_BASE, 0) if hi_out else hi[level]
            if hi_d[0] - lo_d[0] > 1:
                if lo_out and not hi_out:
                    slot = hi_d[0] - 1          # pack against hi
                elif hi_out and not lo_out:
                    slot = lo_d[0] + 1          # pack against lo
                else:
                    slot = (lo_d[0] + hi_d[0]) // 2
                path.append((slot, node))
                return tuple(path)
            path.append(lo_d)
            level += 1

    # ----------------------------------------------------------------- ops

    def _live_pos(self, index: int) -> tuple:
        """(position of live element `index` or None past the end, the
        position just before it in the whole sequence, live or not)."""
        seen = 0
        prev = None
        for pos, it in self.items.items():
            if it[1] >= it[2]:
                if seen == index:
                    return pos, prev
                seen += 1
            prev = pos
        return None, prev

    def insert(self, index: int, value: bytes, node: int, uuid: int) -> tuple:
        """Insert before live index `index`; returns the position id.
        The neighbours are taken in the whole sequence, tombstones
        included, so a fresh position never lands on a deleted one."""
        if index <= 0:
            lo, hi = None, self.items.first()
        elif index >= self.n_live:
            lo, hi = self.items.last(), None
        else:
            hi, lo = self._live_pos(index)
        pos = self._between(lo, hi, node)
        self.apply_insert(pos, value, uuid)
        return pos

    def apply_insert(self, pos: tuple, value: bytes, uuid: int) -> None:
        """Keyed add-side LWW write (replication entry point)."""
        it = self.items.get(pos)
        if it is None:
            self.items.insert(pos, [value, uuid, 0])
            self.n_live += 1
        elif uuid > it[1]:
            self.n_live += (uuid >= it[2]) - (it[1] >= it[2])
            it[0], it[1] = value, uuid

    def delete(self, index: int, uuid: int) -> Optional[tuple]:
        pos, _ = self._live_pos(index) if 0 <= index < self.n_live \
            else (None, None)
        if pos is None:
            return None
        self.apply_delete(pos, uuid)
        return pos

    def apply_delete(self, pos: tuple, uuid: int) -> None:
        it = self.items.get(pos)
        if it is None:
            # delete for a not-yet-seen insert: tombstone placeholder
            self.items.insert(pos, [None, 0, uuid])
        elif uuid > it[2]:
            self.n_live -= (it[1] >= it[2]) - (it[1] >= uuid)
            it[2] = uuid

    def read(self) -> list[bytes]:
        return [it[0] for _pos, it in self.items.items() if it[1] >= it[2]]

    # ---------------------------------------------------------------- merge

    def merge(self, other: "Sequence") -> None:
        for pos, (value, add_t, del_t) in list(other.items.items()):
            if add_t:
                self.apply_insert(pos, value, add_t)
            if del_t:
                self.apply_delete(pos, del_t)

    def state(self) -> frozenset:
        return frozenset((pos, it[0], it[1], it[2])
                         for pos, it in self.items.items())


# ------------------------------------------------- wire/member serialization
# A list entry is stored as an ELEMENT ROW whose member bytes are its
# position id serialized as fixed-width big-endian digits — byte-lex order
# of members IS position order, so the list's order is its members' order
# and element-plane merges (both engines, snapshots, GC) apply unchanged.

_DIGIT_BYTES = 2 + 8  # slot (16-bit) + writer node (64-bit)


def pos_to_bytes(pos: tuple) -> bytes:
    out = bytearray()
    for slot, node in pos:
        out += slot.to_bytes(2, "big") + node.to_bytes(8, "big")
    return bytes(out)


def pos_from_bytes(b: bytes) -> tuple:
    return tuple((int.from_bytes(b[i:i + 2], "big"),
                  int.from_bytes(b[i + 2:i + _DIGIT_BYTES], "big"))
                 for i in range(0, len(b), _DIGIT_BYTES))


def pos_between_bytes(lo: Optional[bytes], hi: Optional[bytes],
                      node: int) -> bytes:
    """A fresh serialized position strictly between two serialized ones."""
    return pos_to_bytes(Sequence._between(
        pos_from_bytes(lo) if lo else None,
        pos_from_bytes(hi) if hi else None, node))
