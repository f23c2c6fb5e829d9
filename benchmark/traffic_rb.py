"""redis-benchmark's default tests as one mix: every connection's commands
from the mix file and `--seed`, for the load workers (loadgen_rb.py), the
plain reference (reference_rb.py) and its stand-in (fake_rb_node.py).

redis-benchmark without `-r` names one key per test: `key:__rand_int__`
(SET / GET), `counter:__rand_int__` (INCR), `mylist` (LPUSH / RPUSH /
LRANGE_*), `myset` (SADD / SPOP) and `myhash` (HSET), and SADD / HSET name
the one member `element:__rand_int__` (the configuration's `keys` and
`member`).  Each connection draws its tests in equal shares (the mix's
`tests`, LPUSH twice: redis-benchmark's own LPUSH and its "LPUSH (needed
to benchmark LRANGE)"), in a seeded order of its own.

Every write carries a 3-byte value of its own — the big-endian bytes of
its serial `1 + conn * max_ops_per_conn + i` — so an answer names the
write it came from.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

# test -> code, in the order a mix lists them (the codes are what the
# workers and the reference store per operation)
SET, GET, INCR, LPUSH, RPUSH, SADD, HSET, SPOP, LRANGE = range(9)
CODES = {"SET": SET, "GET": GET, "INCR": INCR, "LPUSH": LPUSH,
         "RPUSH": RPUSH, "SADD": SADD, "HSET": HSET, "SPOP": SPOP,
         "LRANGE_100": LRANGE, "LRANGE_300": LRANGE, "LRANGE_500": LRANGE,
         "LRANGE_600": LRANGE}
# redis-benchmark.c's ranges: LRANGE mylist 0 <stop>
STOPS = {"LRANGE_100": 99, "LRANGE_300": 299, "LRANGE_500": 449,
         "LRANGE_600": 599}
WIDTH = 3


class ConnOps:
    """Connection `conn`'s operations 0 .. count-1: `kinds` (codes
    above), `stop` (an LRANGE's last index, else -1) and `check` (the
    LRANGEs whose whole reply the comparison reads)."""

    def __init__(self, kinds, stop, check):
        self.kinds, self.stop, self.check = kinds, stop, check

    def __len__(self) -> int:
        return len(self.kinds)


def conn_ops(mix: dict, seed: int, conn: int) -> ConnOps:
    count = int(mix["max_ops_per_conn"])
    tests = mix["tests"]
    rng = np.random.default_rng([int(seed), 0x72626f70, conn])
    order = np.resize(np.arange(len(tests)), count)
    rng.shuffle(order)
    kinds = np.array([CODES[t] for t in tests], dtype=np.int8)[order]
    stop = np.array([STOPS.get(t, -1) for t in tests], dtype=np.int32)[order]
    check = (kinds == LRANGE) & (rng.random(count)
                                 < float(mix["check_share"]))
    return ConnOps(kinds, stop, check)


def serial(mix: dict, conn: int, i):
    """The write serial of connection `conn`'s i-th operation (int or
    array): unique over the run, under 2^24."""
    return 1 + conn * int(mix["max_ops_per_conn"]) + i


def value(s: int) -> bytes:
    return int(s).to_bytes(WIDTH, "big")


def serials_of(values: bytes) -> np.ndarray:
    """Concatenated 3-byte values -> their serials."""
    b = np.frombuffer(values, dtype=np.uint8).reshape(-1, WIDTH)
    return (b[:, 0].astype(np.int64) << 16) | (b[:, 1].astype(np.int64) << 8) \
        | b[:, 2].astype(np.int64)


def command(cfg: dict, mix: dict, ops: ConnOps, conn: int, i: int) -> bytes:
    """Operation i of connection `conn` as RESP bytes."""
    keys = cfg["keys"]
    k = ops.kinds[i]
    if k == GET:
        return _resp(b"GET", keys["string"])
    if k == LRANGE:
        return _resp(b"LRANGE", keys["list"], b"0", b"%d" % ops.stop[i])
    if k == INCR:
        return _resp(b"INCR", keys["counter"])
    if k == SPOP:
        return _resp(b"SPOP", keys["set"])
    if k == SADD:
        return _resp(b"SADD", keys["set"], cfg["member"])
    v = value(serial(mix, conn, i))
    if k == SET:
        return _resp(b"SET", keys["string"], v)
    if k == HSET:
        return _resp(b"HSET", keys["hash"], cfg["member"], v)
    return _resp(b"LPUSH" if k == LPUSH else b"RPUSH", keys["list"], v)


def _resp(*parts) -> bytes:
    parts = [p.encode() if isinstance(p, str) else p for p in parts]
    return b"*%d\r\n" % len(parts) + b"".join(
        b"$%d\r\n%s\r\n" % (len(p), p) for p in parts)
