"""The plain reference of the redis-benchmark cell, and the comparison that
decides `correct` there.

`RefStore` is one node's five keys with Redis semantics written plainly: a
string, a counter, a list, a set and a hash, each a Python value, and the
commands the mix sends (plus the read-backs) as methods.  It imports
nothing of the program; fake_rb_node.py serves it in the program's place.

`check_served_rb` holds a window's answers to what such a store may say
when 50 connections meet on the same five keys.  With X -> Y meaning "X's
reply was parsed before Y was sent" (at one command in flight this covers
"earlier on the same connection"), and every write carrying a value of its
own (traffic_rb.py), exact counts of answers that differ, limit 0:

* `acks_wrong` — SET answers +OK; HSET :0 or :1, and :1 exactly once, to
  an HSET no other acknowledged one came before; INCR replies are distinct
  and none is smaller than one acknowledged before its INCR was sent;
  push replies (LPUSH and RPUSH alike: the list's new length) likewise;
  SADD :1 and SPOP's member move the one member's balance, which stays in
  {0, 1} in every order the replies allow.
* `reads_wrong` — a GET answers nil or a SET, by reference.py's rule for a
  field (not a SET sent after the reply; none older than a SET acknowledged
  before the GET was sent); an LRANGE is the first k of some state of the
  list: its values are pushes sent before its reply, LPUSHes (newest first)
  then RPUSHes (oldest first), ordered as X -> Y allows, and none missing
  that was acknowledged before the LRANGE was sent unless the reply is full
  and every listed push may have landed after it.  A seeded share of the
  LRANGEs (the mix's `check_share`) is read whole; every other is held to
  its length: min(k, pushes acknowledged before it was sent) .. min(k,
  pushes sent before its reply).
* `readback_wrong` — after the close: the counter equals the acknowledged
  INCRs; `LRANGE mylist 0 -1` holds exactly the acknowledged pushes in an
  order the rule above allows; GET and HGET answer a write no other came
  after; SMEMBERS matches the member's balance.
* `never_answered` — operations with no reply 60 s after the close.
"""

from __future__ import annotations

from collections import deque

import numpy as np

import traffic_rb as T

LIMITS = {"acks_wrong": 0, "reads_wrong": 0, "readback_wrong": 0,
          "never_answered": 0}
INF = float("inf")


class RefStore:
    """One node's keys: string, counter, list, set, hash (Redis
    semantics, one member per set and per hash as the mix uses them)."""

    def __init__(self) -> None:
        self.strings: dict = {}
        self.lists: dict = {}
        self.sets: dict = {}
        self.hashes: dict = {}

    def set(self, key: bytes, v: bytes) -> bytes:
        self.strings[key] = v
        return b"+OK\r\n"

    def get(self, key: bytes) -> bytes:
        v = self.strings.get(key)
        return b"$-1\r\n" if v is None else b"$%d\r\n%s\r\n" % (len(v), v)

    def incr(self, key: bytes) -> bytes:
        n = int(self.strings.get(key, b"0")) + 1
        self.strings[key] = b"%d" % n
        return b":%d\r\n" % n

    def push(self, key: bytes, v: bytes, head: bool) -> bytes:
        lst = self.lists.setdefault(key, deque())
        if head:
            lst.appendleft(v)
        else:
            lst.append(v)
        return b":%d\r\n" % len(lst)

    def lrange(self, key: bytes, start: int, stop: int) -> bytes:
        lst = list(self.lists.get(key, ()))
        n = len(lst)
        start = max(start + n if start < 0 else start, 0)
        stop = stop + n if stop < 0 else stop
        got = lst[start:stop + 1]
        return b"*%d\r\n" % len(got) + b"".join(
            b"$%d\r\n%s\r\n" % (len(v), v) for v in got)

    def sadd(self, key: bytes, m: bytes) -> bytes:
        s = self.sets.setdefault(key, set())
        added = m not in s
        s.add(m)
        return b":%d\r\n" % added

    def spop(self, key: bytes) -> bytes:
        s = self.sets.get(key)
        if not s:
            return b"$-1\r\n"
        m = s.pop()
        return b"$%d\r\n%s\r\n" % (len(m), m)

    def smembers(self, key: bytes) -> bytes:
        s = sorted(self.sets.get(key, ()))
        return b"*%d\r\n" % len(s) + b"".join(
            b"$%d\r\n%s\r\n" % (len(m), m) for m in s)

    def hset(self, key: bytes, f: bytes, v: bytes) -> bytes:
        h = self.hashes.setdefault(key, {})
        new = f not in h
        h[f] = v
        return b":%d\r\n" % new

    def hget(self, key: bytes, f: bytes) -> bytes:
        v = self.hashes.get(key, {}).get(f)
        return b"$-1\r\n" if v is None else b"$%d\r\n%s\r\n" % (len(v), v)


def parse(raw: bytes, pos: int = 0):
    """One RESP value -> (value, next pos); an error raises ValueError."""
    end = raw.index(b"\r\n", pos)
    t, rest = raw[pos:pos + 1], raw[pos + 1:end]
    if t == b"$":
        n = int(rest)
        if n < 0:
            return None, end + 2
        return raw[end + 2:end + 2 + n], end + 4 + n
    if t == b"*":
        out, p = [], end + 2
        for _ in range(max(int(rest), 0)):
            v, p = parse(raw, p)
            out.append(v)
        return out, p
    if t == b":":
        return int(rest), end + 2
    if t == b"+":
        return rest, end + 2
    raise ValueError(f"error reply {raw[pos:end]!r}")


def order_violations(vals: np.ndarray, ts: np.ndarray,
                     td: np.ndarray) -> int:
    """Operations B for which some A with a LARGER value was acknowledged
    before B was sent (A -> B: B came later, so its value may not be
    smaller; a repeated value is counted apart)."""
    if len(vals) < 2:
        return 0
    o = np.argsort(vals, kind="stable")
    v = vals[o]
    suf = np.append(np.minimum.accumulate(td[o][::-1])[::-1], INF)
    later = suf[np.searchsorted(v, v, side="right")]
    return int((later < ts[o]).sum())


class _Writes:
    """The acknowledged-or-pending writes of one key (one field): by
    serial, with when each was sent and answered — reference.py's `Writes`
    for one slot, with its rule's two tests as searches."""

    def __init__(self, serial, ts, td):
        o = np.argsort(serial)
        self.serial, self.ts, self.td = serial[o], ts[o], td[o]
        by_td = np.argsort(self.td, kind="stable")
        self.td_sorted = self.td[by_td]
        # max send time over the writes answered up to each point
        self.ts_max = np.maximum.accumulate(self.ts[by_td]) \
            if len(by_td) else np.zeros(0)
        self.last_sent = float(self.ts.max()) if len(self.ts) else -INF

    def find(self, serials: np.ndarray) -> np.ndarray:
        at = np.searchsorted(self.serial, serials)
        ok = (at < len(self.serial)) & \
            (self.serial[np.minimum(at, len(self.serial) - 1)] == serials) \
            if len(self.serial) else np.zeros(len(serials), dtype=bool)
        return np.where(ok, at, -1)

    def newest_acked_sent(self, t: np.ndarray) -> np.ndarray:
        """max ts over the writes answered before each t (-inf: none)."""
        k = np.searchsorted(self.td_sorted, t, side="left")
        return np.where(k > 0, self.ts_max[np.maximum(k - 1, 0)], -INF)

    def may_read(self, got: np.ndarray, ts: np.ndarray,
                 td: np.ndarray) -> np.ndarray:
        """Reads sent at ts, answered at td, answering write serial `got`
        (-1: nil) -> may each answer so?"""
        newest = self.newest_acked_sent(ts)
        w = self.find(np.maximum(got, 0))
        found = w >= 0
        wts = np.where(found, self.ts[np.maximum(w, 0)], INF)
        wtd = np.where(found, self.td[np.maximum(w, 0)], -INF)
        as_write = found & (wts <= td) & (newest <= wtd)
        return np.where(got < 0, newest == -INF, as_write)

    def may_remain(self, got: int) -> bool:
        if got < 0:
            return len(self.serial) == 0
        w = int(self.find(np.array([got]))[0])
        return w >= 0 and self.last_sent <= self.td[w]


class _Pushes:
    """Every push the connections sent: by serial, and per connection its
    LPUSHes and RPUSHes in order."""

    def __init__(self, cols: dict):
        o = np.argsort(cols["serial"])
        for k, v in cols.items():
            setattr(self, k, v[o])
        self.head = self.kind == T.LPUSH
        self.td_sorted = np.sort(self.td)
        self.ts_sorted = np.sort(self.ts)
        self.per_conn = {}
        for side in (True, False):
            m = self.head == side
            for c in np.unique(self.conn[m]).tolist():
                sel = np.flatnonzero(m & (self.conn == c))
                sel = sel[np.argsort(self.ts[sel])]
                self.per_conn[(side, c)] = (self.serial[sel], self.ts[sel],
                                            self.td[sel])

    def find(self, serials: np.ndarray) -> np.ndarray:
        at = np.searchsorted(self.serial, serials)
        if not len(self.serial):
            return np.full(len(serials), -1)
        ok = (at < len(self.serial)) & \
            (self.serial[np.minimum(at, len(self.serial) - 1)] == serials)
        return np.where(ok, at, -1)

    def state_wrong(self, got: np.ndarray, k: int, ts_r: float,
                    td_r: float) -> str:
        """Is `got` (serials, in the reply's order) the first k of a state
        of the list that a read sent at ts_r, answered at td_r, may see?
        -> why not ("" where it may)."""
        n = len(got)
        if n > k:
            return "longer than its range"
        at = self.find(got)
        if (at < 0).any():
            return "a value no push carried"
        if len(np.unique(at)) < n:
            return "a push listed twice"
        if (self.ts[at] > td_r).any():
            return "a push sent after the reply"
        head = self.head[at]
        nh = int(head.sum())
        if head[nh:].any() or not head[:nh].all():
            return "an RPUSH before an LPUSH"
        hts, htd = self.ts[at[:nh]], self.td[at[:nh]]
        if nh > 1 and (hts[1:] > np.minimum.accumulate(htd)[:-1]).any():
            return "LPUSHes out of their order"
        tts, ttd = self.ts[at[nh:]], self.td[at[nh:]]
        if n - nh > 1 and (ttd[1:] < np.maximum.accumulate(tts)[:-1]).any():
            return "RPUSHes out of their order"
        full = n == k
        listed = set(got.tolist())
        h_td_min = float(htd.min()) if nh else INF
        t_ts_max = float(tts.max()) if n > nh else -INF
        for (side, _c), (ser, p_ts, p_td) in self.per_conn.items():
            last = int(np.searchsorted(p_td, ts_r, side="left"))
            if side:
                j = last - 1       # the newest acknowledged one not listed
                while j >= 0 and ser[j] in listed:
                    j -= 1
                if j >= 0 and not (full and n == nh
                                   and h_td_min >= p_ts[j]):
                    return "an acknowledged LPUSH missing"
            elif n > nh or not full:
                j = 0              # the oldest acknowledged one not listed
                while j < last and ser[j] in listed:
                    j += 1
                if j < last and not (full and t_ts_max <= p_td[j]):
                    return "an acknowledged RPUSH missing"
        return ""


def check_served_rb(cfg: dict, mix: dict, seed: int, results: list,
                    readback) -> dict:
    """`results`: the workers' per-connection records (loadgen_rb.py).
    `readback(cmds)` -> raw replies, read after the window closed.
    -> {"numbers": {name: count}, "compared": {...}, "first": str}"""
    numbers = dict.fromkeys(LIMITS, 0)
    compared = {"acks": 0, "reads": 0, "lranges_whole": 0, "readback": 0}
    first = ""

    def differ(name: str, what: str, count: int = 1) -> None:
        nonlocal first
        if count:
            numbers[name] += count
            first = first or f"{name}: {what}"

    cols = {k: [] for k in ("conn", "i", "kind", "ts", "td", "num", "stop",
                            "check", "odd")}
    lr = {}
    for res in results:
        conn, sent, done = res["conn"], res["sent"], res["done"]
        ops = T.conn_ops(mix, seed, conn)
        if done < sent or res["failed"]:
            differ("never_answered", f"connection {conn}: {res['failed']}",
                   max(1, sent - done))
        odd = np.zeros(sent, dtype=bool)
        odd[list(res["odd"])] = True
        cols["conn"].append(np.full(sent, conn))
        cols["i"].append(np.arange(sent))
        cols["kind"].append(ops.kinds[:sent])
        cols["ts"].append(res["t_sent"])
        cols["td"].append(np.where(np.arange(sent) < done, res["t_done"],
                                   INF))
        cols["num"].append(res["num"])
        cols["stop"].append(ops.stop[:sent])
        cols["check"].append(ops.check[:sent])
        cols["odd"].append(odd)
        for i, raw in res["odd"].items():
            kind = int(ops.kinds[i])
            name = "reads_wrong" if kind in (T.GET, T.LRANGE) \
                else "acks_wrong"
            differ(name, f"conn {conn} op {i} (test code {kind}) answered "
                   f"{raw[:48]!r}")
        for i, v in res["lr"].items():
            lr[(conn, i)] = v
    op = {k: np.concatenate(v) if v else np.zeros(0) for k, v in cols.items()}
    kind, ts, td, num = op["kind"], op["ts"], op["td"], op["num"]
    answered = (td < INF) & ~op["odd"]
    ser = T.serial(mix, op["conn"], op["i"])

    def of(k):
        return answered & (kind == k)

    # ---- acknowledgements
    compared["acks"] = int((answered & np.isin(
        kind, (T.SET, T.INCR, T.LPUSH, T.RPUSH, T.SADD, T.HSET,
               T.SPOP))).sum())
    m = of(T.HSET)
    if m.any():
        ones = np.flatnonzero(m & (num == 1))
        differ("acks_wrong", f"{len(ones)} HSETs answered :1, not one",
               abs(len(ones) - 1))
        if len(ones) == 1 and (td[m] < ts[ones[0]]).any():
            differ("acks_wrong", "HSET :1 after another was acknowledged")
        differ("acks_wrong", "HSET answered neither :0 nor :1",
               int((m & (num != 0) & (num != 1)).sum()))
    for what, sel in (("INCR", of(T.INCR)),
                      ("push", of(T.LPUSH) | of(T.RPUSH))):
        vals = num[sel]
        sent_n = int(np.isin(kind, (T.INCR,) if what == "INCR"
                             else (T.LPUSH, T.RPUSH)).sum())
        differ("acks_wrong", f"{what} replies repeat",
               len(vals) - len(np.unique(vals)))
        differ("acks_wrong", f"{what} reply outside 1..{sent_n}",
               int(((vals < 1) | (vals > sent_n)).sum()))
        differ("acks_wrong", f"{what} reply smaller than one acknowledged "
               "before it was sent",
               order_violations(vals, ts[sel], td[sel]))
    sadds, spops = of(T.SADD), of(T.SPOP)
    differ("acks_wrong", "SADD answered neither :0 nor :1",
           int((sadds & (num != 0) & (num != 1)).sum()))
    ups, downs = sadds & (num == 1), spops & (num == 1)
    at = np.unique(np.concatenate([ts[ups | downs], td[ups | downs]]))
    at = at[at < INF]

    # just after each instant t: landed ups are at least those answered by
    # t, landed downs at most those sent by t — and the other way round
    # for the most the balance can be
    def by(col):
        return np.searchsorted(np.sort(col), at, side="right")
    low = by(td[ups]) - by(ts[downs])
    high = by(ts[ups]) - by(td[downs])
    differ("acks_wrong", "the set member's balance leaves {0, 1}",
           int(((low > 1) | (high < 0)).sum()))

    def over(f, ufunc, sel):
        """ufunc of f over the instants from each op's send to its reply
        (f is 0 before the first instant)."""
        f = np.concatenate([[0], f, [0]])
        a = np.searchsorted(at, ts[sel], side="right")
        b = np.searchsorted(at, td[sel], side="right") + 1
        return ufunc.reduceat(f, np.column_stack([a, b]).ravel())[::2]
    # the member is there for a SADD :0 and gone for a SPOP nil, at some
    # instant between the command's send and its reply
    differ("acks_wrong", "a SADD :0 while the member cannot be there",
           int((over(high, np.maximum, sadds & (num == 0)) < 1).sum()))
    differ("acks_wrong", "a SPOP nil while the member cannot be gone",
           int((over(low, np.minimum, spops & (num == 0)) > 0).sum()))
    balance = int(ups.sum()) - int(downs.sum())

    # ---- reads
    sets = kind == T.SET
    strings = _Writes(ser[sets], ts[sets], td[sets])
    gets = of(T.GET)
    compared["reads"] += int(gets.sum())
    ok = strings.may_read(num[gets], ts[gets], td[gets])
    differ("reads_wrong", "a GET answers what no acknowledged or pending "
           "SET left there", int((~ok).sum()))
    pm = np.isin(kind, (T.LPUSH, T.RPUSH))
    pushes = _Pushes({"serial": ser[pm], "kind": kind[pm],
                      "conn": op["conn"][pm], "ts": ts[pm], "td": td[pm]})
    lrs = np.flatnonzero(of(T.LRANGE))
    compared["reads"] += len(lrs)
    k = op["stop"][lrs].astype(np.int64) + 1
    lo = np.minimum(k, np.searchsorted(pushes.td_sorted, ts[lrs]))
    hi = np.minimum(k, np.searchsorted(pushes.ts_sorted, td[lrs],
                                       side="right"))
    n = num[lrs]
    whole = op["check"][lrs]
    differ("reads_wrong", "an LRANGE shorter than the pushes acknowledged "
           "before it, or longer than those sent",
           int((~whole & ((n < lo) | (n > hi))).sum()))
    for x in lrs[whole].tolist():
        got = T.serials_of(lr.get((int(op["conn"][x]), int(op["i"][x])), b""))
        compared["lranges_whole"] += 1
        why = pushes.state_wrong(got, int(op["stop"][x]) + 1, float(ts[x]),
                                 float(td[x]))
        differ("reads_wrong", f"LRANGE conn {int(op['conn'][x])} op "
               f"{int(op['i'][x])}: {why}", int(bool(why)))

    # ---- read-back after the close
    keys = cfg["keys"]
    member = cfg["member"].encode()
    cmds = [("GET", keys["string"]), ("GET", keys["counter"]),
            ("LRANGE", keys["list"], 0, -1), ("SMEMBERS", keys["set"]),
            ("HGET", keys["hash"], member)]
    got = []
    for raw in readback(cmds):
        try:
            got.append(parse(raw)[0])
        except (ValueError, IndexError):
            got.append(ValueError)
    compared["readback"] = len(cmds)
    s, c, lst, sm, h = got

    def serial_of(v) -> int:
        return int.from_bytes(v, "big") if isinstance(v, bytes) and \
            len(v) == T.WIDTH else (-1 if v is None else -2)

    if not strings.may_remain(serial_of(s)):
        differ("readback_wrong", f"GET {keys['string']} after the close is "
               "not its last SET")
    incrs_sent = int((kind == T.INCR).sum())
    n_incr = int(of(T.INCR).sum())
    c = int(c) if isinstance(c, (int, bytes)) and c != b"" else None
    if c is None or not n_incr <= c <= incrs_sent:
        differ("readback_wrong", f"the counter reads {c}, "
               f"{n_incr} INCRs were acknowledged")
    if not isinstance(lst, list) or any(
            not isinstance(v, bytes) or len(v) != T.WIDTH for v in lst):
        differ("readback_wrong", "LRANGE 0 -1 is not a list of values")
    else:
        got_s = T.serials_of(b"".join(lst))
        why = pushes.state_wrong(got_s, len(got_s) + 1, INF, INF)
        acked = set(pushes.serial[pushes.td < INF].tolist())
        if acked - set(got_s.tolist()):
            why = why or "an acknowledged push missing"
        differ("readback_wrong", f"LRANGE 0 -1 after the close: {why}",
               int(bool(why)))
    want = [member] if balance == 1 else []
    if sm != want:
        differ("readback_wrong", f"SMEMBERS {keys['set']} reads {sm!r}, the "
               f"balance is {balance}")
    hs = kind == T.HSET
    hashes = _Writes(ser[hs], ts[hs], td[hs])
    if not hashes.may_remain(serial_of(h)):
        differ("readback_wrong", f"HGET {keys['hash']} after the close is "
               "not its last HSET")
    return {"numbers": numbers, "compared": compared, "first": first}
