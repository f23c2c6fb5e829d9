"""The plain reference of the redis-benchmark cell
(benchmark/reference_rb.py), off the chip and small.

Pinned here, on histories written by hand (4 connections taking turns,
one operation in flight each, 60 operations a connection, every test of
the mix, every LRANGE read whole):
  * a sound history — the plain store's own answers — reads 0 in every
    number, and so does one whose pushes overlap in time and land in
    either order;
  * a GET that answers a SET an acknowledged one had replaced, an LRANGE
    with its newest push left out or two of its values swapped, a repeated
    INCR or push reply, a second HSET :1, two SADD :1 with no SPOP between,
    a SADD :0 while the member cannot be there, a SPOP nil while it cannot
    be gone, a counter, a list or a set member missing from the read-back,
    and an operation never answered are each counted, in the right number.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

CONFIG = {"keys": {"string": "key:__rand_int__",
                   "counter": "counter:__rand_int__", "list": "mylist",
                   "set": "myset", "hash": "myhash"},
          "member": "element:__rand_int__"}
MIX = {"tests": ["SET", "GET", "INCR", "LPUSH", "RPUSH", "SADD", "HSET",
                 "SPOP", "LPUSH", "LRANGE_100", "LRANGE_300", "LRANGE_500",
                 "LRANGE_600"],
       "max_ops_per_conn": 60, "check_share": 1.0}
SEED = 4200000042
CONNS = 4


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import fake_rb_node
        import reference_rb
        import traffic_rb
    finally:
        sys.path.remove(BENCH)

    class B:
        pass
    b = B()
    b.fake, b.ref, b.T = fake_rb_node, reference_rb, traffic_rb
    return b


def _args(raw: bytes) -> list:
    """A RESP command's bulks."""
    parts, pos = [], raw.index(b"\r\n") + 2
    while pos < len(raw):
        end = raw.index(b"\r\n", pos)
        n = int(raw[pos + 1:end])
        parts.append(raw[end + 2:end + 2 + n])
        pos = end + 4 + n
    return parts


def _record(bench, kind: int, reply: bytes, res: dict, i: int) -> None:
    """What a load worker keeps of one reply (loadgen_rb.py)."""
    T = bench.T
    v, _ = bench.ref.parse(reply)
    if kind == T.SET:
        res["num"][i] = 1 if v == b"OK" else 0
    elif kind == T.GET:
        res["num"][i] = -1 if v is None else int.from_bytes(v, "big")
    elif kind == T.SPOP:
        res["num"][i] = 0 if v is None else 1
    elif kind == T.LRANGE:
        res["num"][i] = len(v)
        res["lr"][i] = b"".join(v)
    else:
        res["num"][i] = v


def history(bench, overlap: bool = False, dropped: tuple = ()):
    """The four connections take turns against the plain store: ->
    (results, store).  Every operation is over before the next is sent —
    or, with `overlap`, each LPUSH's reply is parsed only after the next
    operation was sent (two pushes in flight at once, in either order).
    The tests in `dropped` are acknowledged as the stand-in's
    `drop-write` does, and never applied."""
    T = bench.T
    store = bench.ref.RefStore()
    results = []
    for c in range(CONNS):
        n = MIX["max_ops_per_conn"]
        results.append({"conn": c, "sent": n, "done": n,
                        "t_sent": np.zeros(n), "t_done": np.zeros(n),
                        "num": np.zeros(n, dtype=np.int64), "lr": {},
                        "odd": {}, "failed": None})
    cfg = dict(CONFIG, seed=SEED)
    ops = [T.conn_ops(MIX, SEED, c) for c in range(CONNS)]
    t = 1.0
    for i in range(MIX["max_ops_per_conn"]):
        for c in range(CONNS):
            raw = T.command(cfg, MIX, ops[c], c, i)
            kind = int(ops[c].kinds[i])
            reply = (bench.fake.ack if kind in dropped
                     else bench.fake.apply)(store, _args(raw))
            res = results[c]
            res["t_sent"][i] = t
            res["t_done"][i] = t + (1.5 if overlap and kind == T.LPUSH
                                    else 0.5)
            t += 1.0
            _record(bench, kind, reply, res, i)
    return results, store


def readback_of(bench, store):
    def readback(cmds):
        return [bench.fake.apply(store, [str(p).encode() if not
                                         isinstance(p, bytes) else p
                                         for p in cmd]) for cmd in cmds]
    return readback


def check(bench, results, store):
    out = bench.ref.check_served_rb(CONFIG, MIX, SEED, results,
                                    readback_of(bench, store))
    return out["numbers"], out


def ops_of(bench, results, kind):
    """(connection index, op) of every operation of `kind`, in send
    order."""
    T = bench.T
    got = []
    for r in results:
        k = T.conn_ops(MIX, SEED, r["conn"]).kinds
        got += [(float(r["t_sent"][i]), r["conn"], i)
                for i in np.flatnonzero(k == kind).tolist()]
    return [(c, i) for _t, c, i in sorted(got)]


def test_sound_history_reads_zero(bench):
    results, store = history(bench)
    numbers, out = check(bench, results, store)
    assert numbers == dict.fromkeys(bench.ref.LIMITS, 0), out["first"]
    assert out["compared"]["lranges_whole"] > 50
    assert out["compared"]["readback"] == 5


def test_overlapping_pushes_may_land_in_either_order(bench):
    results, store = history(bench, overlap=True)
    # swap two overlapping LPUSHes' values in the store's list: a history
    # where the later-sent one landed first
    lst = store.lists[b"mylist"]
    T = bench.T
    pushes = ops_of(bench, results, T.LPUSH)
    (c1, i1), (c2, i2) = pushes[-2], pushes[-1]
    v1 = T.value(T.serial(MIX, c1, i1))
    v2 = T.value(T.serial(MIX, c2, i2))
    if abs(results[c1]["t_sent"][i1] - results[c2]["t_sent"][i2]) < 1.5:
        a, b = lst.index(v1), lst.index(v2)
        lst[a], lst[b] = lst[b], lst[a]
    numbers, out = check(bench, results, store)
    assert numbers == dict.fromkeys(bench.ref.LIMITS, 0), out["first"]


def test_stale_get_is_a_wrong_read(bench):
    T = bench.T
    results, store = history(bench)
    sets = ops_of(bench, results, T.SET)
    gets = ops_of(bench, results, T.GET)
    # a GET sent after two SETs were acknowledged answers the older one
    c, i = next((c, i) for c, i in gets
                if sum(results[c2]["t_done"][i2] < results[c]["t_sent"][i]
                       for c2, i2 in sets) >= 2)
    older = [s for s in sets if results[s[0]]["t_done"][s[1]]
             < results[c]["t_sent"][i]][-2]
    results[c]["num"][i] = T.serial(MIX, *older)
    numbers, _ = check(bench, results, store)
    assert numbers["reads_wrong"] == 1
    assert sum(numbers.values()) == 1


def test_lrange_missing_or_swapped_is_a_wrong_read(bench):
    T = bench.T
    results, store = history(bench)
    lrs = [(c, i) for c, i in ops_of(bench, results, T.LRANGE)
           if len(results[c]["lr"][i]) >= 3 * T.WIDTH]
    c, i = lrs[-1]
    full = results[c]["lr"][i]
    results[c]["lr"][i] = full[T.WIDTH:]                 # the newest left out
    results[c]["num"][i] -= 1
    c, i = lrs[-2]
    v = results[c]["lr"][i]
    results[c]["lr"][i] = v[3:6] + v[:3] + v[6:]         # two swapped
    numbers, _ = check(bench, results, store)
    assert numbers["reads_wrong"] == 2
    assert sum(numbers.values()) == 2


@pytest.mark.parametrize("fault", ["incr-repeat", "push-repeat",
                                   "second-hset-1"])
def test_bad_acknowledgements_are_counted(bench, fault):
    T = bench.T
    results, store = history(bench)
    kinds = {"incr-repeat": (T.INCR,), "push-repeat": (T.LPUSH, T.RPUSH),
             "second-hset-1": (T.HSET,)}[fault]
    ops = sorted((results[c]["t_sent"][i], c, i) for k in kinds
                 for c, i in ops_of(bench, results, k))
    (_t, c0, i0), (_t, c, i) = ops[-2:]
    # the last one answers what the one before it did (an HSET: :1)
    results[c]["num"][i] = 1 if fault == "second-hset-1" \
        else results[c0]["num"][i0]
    numbers, out = check(bench, results, store)
    assert numbers["acks_wrong"] == 1, out["first"]
    assert sum(numbers.values()) == 1


def test_set_member_balance_out_of_bounds(bench):
    T = bench.T
    results, store = history(bench)
    spops = [(c, i) for c, i in ops_of(bench, results, T.SPOP)
             if results[c]["num"][i] == 1]
    c, i = spops[len(spops) // 2]
    results[c]["num"][i] = 0              # a pop that took nothing: the
    #                                       next SADD :1 adds a second
    numbers, _ = check(bench, results, store)
    assert numbers["acks_wrong"] > 0


def test_readback_and_never_answered(bench):
    T = bench.T
    results, store = history(bench)
    store.lists[b"mylist"].pop()           # a push lost after its ack
    store.strings[b"counter:__rand_int__"] = b"1"
    store.sets[b"myset"] = {b"other"}
    results[2]["done"] -= 1                # the last operation unanswered
    numbers, out = check(bench, results, store)
    assert numbers["readback_wrong"] == 3, out["first"]
    assert numbers["never_answered"] == 1


def test_sadd_and_spop_dropped_answering_nothing_moved(bench):
    """A program that never adds the member: every SADD answers :0 and
    every SPOP nil.  The balance stays 0 and SMEMBERS reads [] — only the
    replies themselves can show it, and each SADD :0 does."""
    T = bench.T
    results, store = history(bench)
    for k in (T.SADD, T.SPOP):
        for c, i in ops_of(bench, results, k):
            results[c]["num"][i] = 0
    store.sets.clear()
    numbers, out = check(bench, results, store)
    assert numbers["acks_wrong"] == len(ops_of(bench, results, T.SADD)), \
        out["first"]
    assert sum(numbers.values()) == numbers["acks_wrong"]


def test_spop_nil_while_the_member_is_there(bench):
    T = bench.T
    results, store = history(bench)
    # a SPOP that found the member answers nil, and the SPOP after it
    # takes the member instead: the balance holds, the nil cannot
    spops = ops_of(bench, results, T.SPOP)
    j = next(j for j in range(len(spops) - 1)
             if results[spops[j][0]]["num"][spops[j][1]] == 1
             and results[spops[j + 1][0]]["num"][spops[j + 1][1]] == 0
             and not any(results[c]["t_sent"][i] > results[spops[j][0]]
                         ["t_sent"][spops[j][1]] and results[c]["t_sent"][i]
                         < results[spops[j + 1][0]]["t_sent"][spops[j + 1][1]]
                         for c, i in ops_of(bench, results, T.SADD)))
    (c0, i0), (c1, i1) = spops[j], spops[j + 1]
    results[c0]["num"][i0], results[c1]["num"][i1] = 0, 1
    numbers, out = check(bench, results, store)
    assert numbers["acks_wrong"] == 1, out["first"]
    assert sum(numbers.values()) == 1


@pytest.mark.parametrize("tests", ["SADD", "SPOP"])
def test_stand_in_dropping_set_writes_is_counted(bench, tests):
    """The stand-in's drop-write on every SADD (acknowledged :1, never
    added) or every SPOP (answers the member, never takes it)."""
    results, store = history(bench, dropped=(bench.T.CODES[tests],))
    numbers, out = check(bench, results, store)
    assert numbers["acks_wrong"] > 0, out
