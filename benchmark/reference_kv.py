"""The plain reference of the key-value cells, and the comparison that
decides `correct` there.

`RefStore` is the cache with the store's semantics written plainly: a
dictionary of key -> value that starts from the seed
(datagen_kv.RegisterWorld.initial: every key present), `SET` overwrites and
answers OK, `GET` answers the value.  It imports nothing of the program;
fake_kv_node.py serves it in the program's place.

`check_served_kv` holds a window's answers to the rule reference.py states
for one field of a record (its `Writes` is used as it is: a key is a record
of one field): with X -> Y meaning "X's reply was parsed before Y was sent,
or X is earlier on the same connection", a `GET` R may answer the key's
initial value or a `SET` W to that key unless R -> W, or some `SET` W' has
W -> W' -> R (older than a write acknowledged before the read was sent).
Every `SET` carries a value of its own, so an answer names its write.

Keys are uniform over millions, so a seeded share of the reads would hold
almost none that met a write.  Compared instead: EVERY `GET` whose key any
connection `SET` at any time in the run (by the rule above, one by one),
and a seeded share (`check_share`) of all the others against the key's
initial value (in bulk); every `SET`'s acknowledgement; after the close a
read-back of written keys (each connection's last write among them) and
untouched ones, each of which must answer a write that no other write to
the key came after; and operations never answered.
Counts of answers that differ, each with the limit 0.
"""

from __future__ import annotations

import numpy as np

import traffic
from reference import Writes, _parse

LIMITS = {"reads_wrong": 0, "acks_wrong": 0, "readback_wrong": 0,
          "never_answered": 0}
OK = b"+OK\r\n"


class RefStore:
    def __init__(self, world):
        self.world = world
        self.written = {}       # key number -> value

    def set(self, k: int, value: bytes) -> bytes:
        self.written[k] = value
        return OK

    def get(self, k: int) -> bytes:
        v = self.written.get(k)
        return self.world.initial(k) if v is None else v


def bulk_of(raw: bytes):
    """A raw `$n\\r\\n<bytes>\\r\\n` reply's bytes; None where it is not one."""
    try:
        value, end = _parse(raw, 0)
    except (ValueError, IndexError):
        return None
    return value if raw[:1] == b"$" and end == len(raw) else None


def check_served_kv(world, mix: dict, seed: int, results: list,
                    ops_of: dict, readback) -> dict:
    """`results`: the workers' per-connection records (loadgen_kv.py);
    `ops_of[conn]`: that connection's operations (traffic.conn_ops).
    `readback(keys)` -> raw GET replies, read after the window closed.
    -> {"numbers": {name: count}, "compared": {...}, "first": str}"""
    writes = Writes(world, mix, results, ops_of)
    written = np.array(sorted(writes.records), dtype=np.int64)
    numbers = dict.fromkeys(LIMITS, 0)
    compared = {"reads": 0, "reads_crossing_writes": 0, "acks": 0,
                "readback": 0}
    first = ""

    def differ(name: str, what: str, count: int = 1) -> None:
        nonlocal first
        numbers[name] += count
        first = first or f"{name}: {what}"

    for res in results:
        conn, sent, done = res["conn"], res["sent"], res["done"]
        ops = ops_of[conn]
        if done < sent or res["failed"]:
            numbers["never_answered"] += max(1, sent - done)
            first = first or (f"never_answered: connection {conn}: "
                              f"{res['failed']}")
        kinds, keys = ops.kinds[:done], ops.records[:done]
        is_set = kinds == traffic.UPDATE
        compared["acks"] += int(is_set.sum())
        # a reply of another shape than `+OK` / a bulk of the value's
        # width is wrong whatever the operation was compared for
        for i, raw in res["odd"].items():
            if i >= done:
                continue
            if is_set[i]:
                differ("acks_wrong", f"conn {conn} op {i} SET "
                       f"{world.key(int(keys[i]))!r} answered {raw!r}")
            else:
                differ("reads_wrong", f"conn {conn} op {i} GET "
                       f"{world.key(int(keys[i]))!r} answered {raw[:48]!r}")
        odd = np.zeros(done, dtype=bool)
        odd[[i for i in res["odd"] if i < done]] = True
        reads = ~is_set & ~odd
        at = np.searchsorted(written, keys)
        crossing = reads & (at < len(written)) & \
            (written[np.minimum(at, len(written) - 1)] == keys) \
            if len(written) else np.zeros(done, dtype=bool)
        # the others: a seeded share, against the initial value, in bulk
        plain = np.flatnonzero(reads & ~crossing & ops.check[:done])
        compared["reads"] += len(plain)
        if len(plain):
            bad = plain[res["vals"][plain] != world.values_of(keys[plain])]
            if len(bad):
                i = int(bad[0])
                differ("reads_wrong", f"conn {conn} op {i} GET "
                       f"{world.key(int(keys[i]))!r} answered "
                       f"{bytes(res['vals'][i])!r}, the key was never "
                       "written and holds another value", len(bad))
        # the reads that met a write, one by one
        ts, td, vals = res["t_sent"], res["t_done"], res["vals"]
        for i in np.flatnonzero(crossing).tolist():
            k = int(keys[i])
            compared["reads"] += 1
            compared["reads_crossing_writes"] += 1
            if not writes.may_read(k, world.initial(k), bytes(vals[i]),
                                   conn, i, float(ts[i]), float(td[i])):
                differ("reads_wrong", f"conn {conn} op {i} GET "
                       f"{world.key(k)!r} answers what no acknowledged "
                       "or pending write left there")
    # read-back: written keys (the last write is there once the window
    # has closed) and never-touched ones (nothing else moved)
    # — each connection's LAST write among them: a store that defers a
    # write behind its acknowledgement shows it there
    rng = np.random.default_rng([int(seed), 0x72656164])
    n_back = int(mix["readback_records"])
    last = set()
    for res in results:
        ops = ops_of[res["conn"]]
        sets = np.flatnonzero(ops.kinds[:res["done"]] == traffic.UPDATE)
        if len(sets):
            last.add(int(ops.records[sets[-1]]))
    rest = written[~np.isin(written, list(last))] if last else written
    more = max(0, n_back - len(last))
    pick = rest if len(rest) <= more else \
        rng.choice(rest, more, replace=False)
    cold = rng.integers(0, world.n, n_back // 4)
    sample = sorted(last) + pick.tolist() + cold.tolist()
    for k, raw in zip(sample, readback(sample)):
        compared["readback"] += 1
        got = bulk_of(raw)
        if got is None or not writes.may_remain(k, world.initial(k), got):
            differ("readback_wrong", f"GET {world.key(k)!r} after the "
                   "window is not the key's last write")
    return {"numbers": numbers, "compared": compared, "first": first}
