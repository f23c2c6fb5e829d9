"""The plain reference of the served cells, and the comparison that decides
`correct`.

`RefTable` is the YCSB table with the store's semantics written plainly: a
record is a dict of fields, HSET overwrites one field and answers how many
fields it created, HGETALL answers the whole dict.  It starts from the
seed (datagen.HashWorld.initial) and imports nothing of the program.
fake_node.py serves it in the program's place.

`check_served` holds the answers of a window to what such a table may say
when several connections meet on a record.  Every write carries a value of
its own (datagen.ValuePool by serial), so a field a read answers names the
write it came from.  With X -> Y ("X was over before Y began": X's reply
parsed before Y's pipeline was sent, or X earlier than Y on one
connection), a read R may answer, for each field, the table's initial
value or a write W to that field unless
  * R -> W (the value comes from a write not yet sent), or
  * some write W' to the field has W -> W' -> R (the value is older than a
    write acknowledged before the read was sent: not read back at once).
After the window closed and every connection is quiet, a read-back must
answer, for each field, a write that no other write to it came after — the
one last write where the connections did not overlap there, the initial
value where none wrote.  Clocks err to the safe side: a pipeline's send
time is taken before the send, a reply's time after the parse.

The numbers it returns are counts of answers that differ; each has the
limit 0.
"""

from __future__ import annotations

import numpy as np

import traffic

LIMITS = {"reads_wrong": 0, "acks_wrong": 0, "readback_wrong": 0,
          "never_answered": 0}


class RefTable:
    def __init__(self, world):
        self.world = world
        self.written = {}       # record -> {field bytes: value bytes}

    def hset(self, record: int, field: bytes, value: bytes) -> int:
        rec = self.written.setdefault(record, {})
        created = 0 if (field in rec or field in self.world.fields) else 1
        rec[field] = value
        return created

    def hgetall(self, record: int) -> dict:
        out = self.world.initial(record)
        out.update(self.written.get(record, ()))
        return out


def _parse(raw: bytes, pos: int):
    """One RESP value at `pos` -> (value, next pos)."""
    end = raw.index(b"\r\n", pos)
    t, rest = raw[pos:pos + 1], raw[pos + 1:end]
    if t == b"$":
        n = int(rest)
        if n < 0:
            return None, end + 2
        return raw[end + 2:end + 2 + n], end + 2 + n + 2
    if t == b"*":
        out, p = [], end + 2
        for _ in range(max(int(rest), 0)):
            v, p = _parse(raw, p)
            out.append(v)
        return out, p
    if t == b":":
        return int(rest), end + 2
    return raw[pos:end], end + 2


def parse_hgetall(raw: bytes):
    """A raw HGETALL reply as {field: value} — the node answers an array
    of [field, value] pairs, Redis a flat array; None where it is
    neither."""
    try:
        items, _ = _parse(raw, 0)
    except (ValueError, IndexError):
        return None
    if not isinstance(items, list):
        return None
    if items and not isinstance(items[0], list):
        items = list(zip(items[0::2], items[1::2]))
    try:
        return {f: v for f, v in items}
    except (TypeError, ValueError):
        return None


class Writes:
    """Every update the connections sent, grouped by (record, field)."""

    def __init__(self, world, mix: dict, results: list, ops_of: dict):
        fc = world.fieldcount
        rows = world.n * fc
        cols = {k: [] for k in ("slot", "conn", "idx", "ts", "td", "serial")}
        for res in results:
            conn, sent, done = res["conn"], res["sent"], res["done"]
            ops = ops_of[conn]
            idx = np.flatnonzero(ops.kinds[:sent] == traffic.UPDATE)
            ts = np.repeat(res["t_sent"], res["depth"])[:sent]
            td = np.where(np.arange(sent) < done, res["t_done"], np.inf)
            cols["slot"].append(ops.records[idx] * fc + ops.fields[idx])
            cols["conn"].append(np.full(len(idx), conn, dtype=np.int64))
            cols["idx"].append(idx)
            cols["ts"].append(ts[idx])
            cols["td"].append(td[idx])
            cols["serial"].append(traffic.write_serial(rows, mix, conn, idx))
        cat = {k: np.concatenate(v) if v else np.zeros(0)
               for k, v in cols.items()}
        order = np.argsort(cat["slot"], kind="stable")
        self.slot, self.conn, self.idx, self.ts, self.td, self.serial = (
            cat[k][order] for k in ("slot", "conn", "idx", "ts", "td",
                                    "serial"))
        self.values = world.pool.values(self.serial.astype(np.int64))
        self.records = set((self.slot // fc).tolist())
        self._by_value = {}     # slot -> {value: [its writes]}

    def of(self, slot: int) -> slice:
        return slice(int(np.searchsorted(self.slot, slot, "left")),
                     int(np.searchsorted(self.slot, slot, "right")))

    def wrote(self, slot: int, g: slice, got: bytes) -> list:
        """The writes of group `g` that carried the value `got`."""
        by_value = self._by_value.get(slot)
        if by_value is None:
            by_value = self._by_value[slot] = {}
            for w in range(g.start, g.stop):
                by_value.setdefault(self.values[w], []).append(w)
        return by_value.get(got, ())

    def after(self, g: slice, w: int) -> np.ndarray:
        """Which writes of group `g` came after its write `w`."""
        return (self.ts[g] > self.td[w]) | \
            ((self.conn[g] == self.conn[w]) & (self.idx[g] > self.idx[w]))

    def may_read(self, slot: int, initial: bytes, got: bytes, conn: int,
                 i: int, ts: float, td: float) -> bool:
        """May a read (connection, index, sent at ts, answered at td)
        answer `got` for this (record, field)?"""
        g = self.of(slot)
        if g.start == g.stop:
            return got == initial
        before_r = (self.td[g] < ts) | \
            ((self.conn[g] == conn) & (self.idx[g] < i))
        if got == initial and not before_r.any():
            return True
        for w in self.wrote(slot, g, got):
            future = self.ts[w] > td or \
                (self.conn[w] == conn and self.idx[w] > i)
            if not future and not (before_r & self.after(g, w)).any():
                return True
        return False

    def may_remain(self, slot: int, initial: bytes, got: bytes) -> bool:
        """May a read after every connection went quiet answer `got`?"""
        g = self.of(slot)
        if g.start == g.stop:
            return got == initial
        return any(not self.after(g, w).any()
                   for w in self.wrote(slot, g, got))


def check_served(world, mix: dict, seed: int, results: list, ops_of: dict,
                 readback) -> dict:
    """`results`: the workers' per-connection records; `ops_of[conn]`: that
    connection's operations (traffic.conn_ops).  `readback(records)`
    -> raw HGETALL replies, read from the node after the window closed.
    -> {"numbers": {name: count}, "compared": {...}, "first": str}"""
    fc = world.fieldcount
    writes = Writes(world, mix, results, ops_of)
    numbers = dict.fromkeys(LIMITS, 0)
    compared = {"reads": 0, "reads_crossing_writes": 0, "acks": 0,
                "readback": 0}
    first = ""

    def differ(name: str, what: str) -> None:
        nonlocal first
        numbers[name] += 1
        first = first or f"{name}: {what}"

    for res in results:
        conn, sent, done = res["conn"], res["sent"], res["done"]
        ops = ops_of[conn]
        if done < sent or res["failed"]:
            numbers["never_answered"] += max(1, sent - done)
            first = first or (f"never_answered: connection {conn}: "
                              f"{res['failed']}")
        # every field the traffic writes exists: an HSET creates none
        compared["acks"] += len(res["acks"])
        for i, ack in res["acks"].items():
            if ack != b":0\r\n":
                differ("acks_wrong", f"conn {conn} op {i} HSET "
                       f"{world.key(int(ops.records[i]))!r} answered "
                       f"{ack!r}, expected b':0\\r\\n'")
        ts = np.repeat(res["t_sent"], res["depth"])[:sent]
        for i in np.flatnonzero(ops.check[:done]).tolist():
            rec = int(ops.records[i])
            compared["reads"] += 1
            got = parse_hgetall(res["reads"].get(i, b""))
            want = world.initial(rec)
            if rec not in writes.records:
                ok = got == want
            else:
                compared["reads_crossing_writes"] += 1
                ok = got is not None and got.keys() == want.keys() and all(
                    writes.may_read(rec * fc + j, want[f], got[f], conn, i,
                                    float(ts[i]), float(res["t_done"][i]))
                    for j, f in enumerate(world.fields))
            if not ok:
                differ("reads_wrong", f"conn {conn} op {i} HGETALL "
                       f"{world.key(rec)!r} answers what no acknowledged "
                       "or pending write left there")
    # read-back: written records (the last write is there once the window
    # has closed) and never-touched ones (nothing else moved)
    rng = np.random.default_rng([int(seed), 0x72656164])
    n_back = int(mix["readback_records"])
    written = np.array(sorted(writes.records), dtype=np.int64)
    pick = written if len(written) <= n_back else \
        rng.choice(written, n_back, replace=False)
    cold = rng.integers(0, world.n, n_back // 4)
    sample = pick.tolist() + cold.tolist()
    for rec, raw in zip(sample, readback(sample)):
        compared["readback"] += 1
        got = parse_hgetall(raw)
        want = world.initial(rec)
        if not (got is not None and got.keys() == want.keys() and all(
                writes.may_remain(rec * fc + j, want[f], got[f])
                for j, f in enumerate(world.fields))):
            differ("readback_wrong", f"HGETALL {world.key(rec)!r} after "
                   "the window is not the record's last writes")
    return {"numbers": numbers, "compared": compared, "first": first}
