"""The plain reference of YCSB workload D, and the comparison that decides
`correct` in cell `ycsb-d` (benchmark/README.d.md).

`RefTableD` is the table with the store's semantics written plainly: the
preloaded records never change (D updates nothing), an insert's `HSET`
of all fields creates the record and answers how many fields it created,
`HGETALL` answers the whole record or nothing.  It starts from the seed
(datagen.HashWorld) and imports nothing of the program; fake_d_node.py
serves it in the program's place.

The rules (`check_served_d`), each an exact count with the limit 0:

* a read of a preloaded record answers its initial fields
  (`reads_lost` where it answers nothing, `reads_partial` where some,
  `reads_wrong` where others);
* a read of inserted record X answers nothing or exactly the insert's
  values.  Nothing is wrong (`reads_lost`) where the insert was over
  before the read began — X -> R: the insert's reply parsed before the
  read's pipeline was sent, or the insert earlier on the read's own
  connection; the record whole is wrong (`reads_wrong`) where the read
  was over before the insert began, or the insert was never sent.  Some
  of the fields is always wrong (`reads_partial`);
* every insert's reply is `:fieldcount` (`acks_wrong`);
* after quiesce every acknowledged insert is read back whole, and a
  seeded sample of `readback_records` preloaded ones (`readback_wrong`);
* every operation sent is answered (`never_answered`).

Every read of an inserted record is compared, and a seeded `check_share`
of the others (traffic_d.py).  The load workers keep, of each compared
read, the number of fields it answered and a digest of them
(`reply_digest`), not the reply: a window reads ~1 kB a read, over a
million reads.  Clocks err to the safe side: a pipeline's send time is
taken before the send, a reply's time after the parse.
"""

from __future__ import annotations

import hashlib

import numpy as np

import traffic_d as T
from reference import parse_hgetall

LIMITS = {"reads_lost": 0, "reads_partial": 0, "reads_wrong": 0,
          "acks_wrong": 0, "readback_wrong": 0, "never_answered": 0}
READBACK_CHUNK = 4096


def record_digest(fields: dict) -> int:
    """A record's fields, in any order, as one 64-bit number."""
    return int.from_bytes(hashlib.blake2b(b"\0".join(
        [f + b"\1" + v for f, v in sorted(fields.items())]),
        digest_size=8).digest(), "little")


def _pairs(raw: bytes):
    """The fast read of an HGETALL reply whose values hold no CRLF (every
    value of this world): {field: value}, or None where the reply is not
    laid out as one (parse_hgetall reads it then)."""
    t = raw.split(b"\r\n")
    if t[-1] or raw[:1] != b"*":
        return None
    n = int(t[0][1:])
    if len(t) == 5 * n + 2:             # [[field, value], ...]
        return dict(zip(t[3:-1:5], t[5:-1:5]))
    if len(t) == 2 * n + 2 and n % 2 == 0:     # [field, value, ...]
        return dict(zip(t[2:-1:4], t[4:-1:4]))
    return None


def reply_digest(raw: bytes) -> tuple:
    """A raw HGETALL reply -> (fields it answered, their digest): (0, 0)
    for nothing (nil or an empty array), (-1, 0) where it is not a
    record at all."""
    if raw in (b"$-1\r\n", b"*0\r\n", b"*-1\r\n"):
        return 0, 0
    try:
        got = _pairs(raw)
    except ValueError:
        got = None
    if got is None:
        got = parse_hgetall(raw)
    if not got:
        return -1, 0
    return len(got), record_digest(got)


class RefTableD:
    def __init__(self, world):
        self.world = world
        self.inserted = {}      # record -> {field bytes: value bytes}

    def hset(self, record: int, pairs: list) -> int:
        rec = self.inserted.setdefault(record, {})
        created = 0
        for f, v in pairs:
            if record >= self.world.n or f not in self.world.fields:
                created += f not in rec
            rec[f] = v
        return created

    def hgetall(self, record: int) -> dict:
        out = self.world.initial(record) if record < self.world.n else {}
        out.update(self.inserted.get(record, ()))
        return out


class Inserts:
    """Every insert the connections sent, by global insert number
    `g = record - R0`: who sent it, when, and when its reply came."""

    def __init__(self, world, mix: dict, results: list, ops_of: dict):
        conns = int(mix["connections"])
        per = {}
        for res in results:
            ops = ops_of[res["conn"]]
            sent, done = res["sent"], res["done"]
            idx = np.flatnonzero(ops.kinds[:sent] == T.INSERT)
            ts = np.repeat(res["t_sent"], res["depth"])[:sent]
            td = np.where(np.arange(sent) < done, res["t_done"], np.inf)
            per[res["conn"]] = (idx, ts[idx], td[idx])
        most = max((len(v[0]) for v in per.values()), default=0)
        n = most * conns
        self.conn = np.arange(n, dtype=np.int64) % conns
        self.idx = np.full(n, -1, dtype=np.int64)
        self.ts = np.full(n, np.inf)
        self.td = np.full(n, np.inf)
        for c, (idx, ts, td) in per.items():
            g = np.arange(len(idx), dtype=np.int64) * conns + c
            self.idx[g], self.ts[g], self.td[g] = idx, ts, td
        self.n = n

    def acked(self) -> np.ndarray:
        return np.flatnonzero(np.isfinite(self.td))


def _digests(world, records) -> dict:
    return {r: record_digest(world.initial(r)) for r in records}


def check_served_d(world, mix: dict, seed: int, results: list, ops_of: dict,
                   readback) -> dict:
    """`results`: the workers' per-connection records (loadgen_d.py);
    `ops_of[conn]`: that connection's operations (traffic_d.conn_ops);
    `readback(records)` -> raw HGETALL replies, read from the node after
    the window closed and every connection went quiet.
    -> {"numbers": {name: count}, "compared": {...}, "first": str}"""
    r0, fc = world.n, world.fieldcount
    ins = Inserts(world, mix, results, ops_of)
    numbers = dict.fromkeys(LIMITS, 0)
    compared = {"reads": 0, "reads_of_inserts": 0, "reads_empty": 0,
                "acks": 0, "readback": 0}
    first = ""

    def differ(name: str, count: int, what: str) -> None:
        nonlocal first
        if count:
            numbers[name] += count
            first = first or f"{name}: {what}"

    want_ack = b":%d\r\n" % fc
    cols = {k: [] for k in ("rec", "n", "d", "conn", "i", "ts", "td")}
    for res in results:
        conn, sent, done = res["conn"], res["sent"], res["done"]
        ops = ops_of[conn]
        if done < sent or res["failed"]:
            differ("never_answered", max(1, sent - done),
                   f"connection {conn}: {res['failed']}")
        compared["acks"] += len(res["acks"])
        for i, ack in res["acks"].items():
            if ack != want_ack:
                differ("acks_wrong", 1, f"conn {conn} op {i} HSET "
                       f"{world.key(int(ops.records[i]))!r} answered "
                       f"{ack!r}, expected {want_ack!r}")
        i = np.flatnonzero(ops.check[:done])
        ts = np.repeat(res["t_sent"], res["depth"])[:sent]
        cols["rec"].append(ops.records[i])
        cols["n"].append(res["got_n"][i].astype(np.int64))
        cols["d"].append(res["got_d"][i])
        cols["conn"].append(np.full(len(i), conn, dtype=np.int64))
        cols["i"].append(i)
        cols["ts"].append(ts[i])
        cols["td"].append(res["t_done"][i])
    r = {k: np.concatenate(v) if v else np.zeros(0, dtype=np.int64)
         for k, v in cols.items()}
    rec, n = r["rec"], r["n"]
    compared["reads"] = len(rec)
    compared["reads_empty"] = int((n == 0).sum())
    whole = n == fc
    want = _digests(world, np.unique(rec[whole]).tolist())
    right = whole & np.array([want.get(a, -1) == int(b) for a, b in
                              zip(rec.tolist(), r["d"].tolist())],
                             dtype=bool)
    # inserted records: when their insert was sent and answered
    inserted = rec >= r0
    compared["reads_of_inserts"] = int(inserted.sum())
    g = np.where(inserted, rec - r0, 0)
    known = inserted & (g < ins.n)
    gk = np.where(known, g, 0)
    same = known & (ins.conn[gk] == r["conn"])
    over_before = known & ((ins.td[gk] < r["ts"])
                           | (same & (ins.idx[gk] >= 0)
                              & (ins.idx[gk] < r["i"])))
    future = inserted & (~known | (ins.ts[gk] > r["td"])
                         | (same & (ins.idx[gk] > r["i"])))
    lost = (n == 0) & (~inserted | over_before)
    partial = (n > 0) & (n < fc)
    wrong = (~right & ~partial & (n != 0)) | (right & future)

    def first_of(mask) -> str:
        j = int(np.flatnonzero(mask)[0])
        return (f"conn {int(r['conn'][j])} op {int(r['i'][j])} HGETALL "
                f"{world.key(int(rec[j]))!r} answered {int(n[j])} fields")

    for name, mask in (("reads_lost", lost), ("reads_partial", partial),
                       ("reads_wrong", wrong)):
        if mask.any():
            differ(name, int(mask.sum()), first_of(mask))
    # read-back: every acknowledged insert, and preloaded records
    rng = np.random.default_rng([int(seed), 0x72656164])
    sample = (ins.acked() + r0).tolist() + \
        rng.integers(0, r0, int(mix["readback_records"])).tolist()
    for c0 in range(0, len(sample), READBACK_CHUNK):
        part = sample[c0:c0 + READBACK_CHUNK]
        for x, raw in zip(part, readback(part)):
            compared["readback"] += 1
            got_n, got_d = reply_digest(raw)
            if got_n != fc or got_d != record_digest(world.initial(x)):
                differ("readback_wrong", 1, f"HGETALL {world.key(x)!r} "
                       f"after the window answered {got_n} fields, not "
                       "the record whole")
    return {"numbers": numbers, "compared": compared, "first": first}
