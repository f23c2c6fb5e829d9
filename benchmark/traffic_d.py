"""YCSB workload D's traffic: every connection's reads and inserts from the
mix file and `--seed`, for the load workers (loadgen_d.py), the plain
reference (reference_d.py) and its stand-in (fake_d_node.py).

One global insert sequence, as YCSB's client threads share one insert
counter: connection `c`'s `k`-th insert creates record `R0 + k * C + c`
(`R0` = recordcount, `C` = the mix's connections), a new key `user%012d`
named as datagen names the preloaded ones, written by ONE `HSET` of all
`fieldcount` fields.  Its values are the record's own serials in the value
pool (datagen.HashWorld.initial of the record number), so a read names
the insert it saw.

Reads follow `requestdistribution=latest` (YCSB's SkewedLatestGenerator:
`max - zipfian(max)` over the records inserted so far): a read by a
connection that has sent `k` inserts draws `r` from one zipfian of the
mix's constant over `N = R0 + max(0, k - lag) * C` records and reads
record `N - 1 - r`.  `lag` (the mix's) is how many rounds of every
connection's inserts the read assumes behind its own: with 1, a read may
name an insert still in its own pipeline, or one another connection has
not sent yet — which may then answer nothing.

`check` marks the reads the comparison reads back: every read of an
inserted record and a seeded `check_share` of the others.  Nothing here
imports the program.
"""

from __future__ import annotations

import functools

import numpy as np

READ, INSERT = 0, 1


class ConnOps:
    """Connection `conn`'s operations 0 .. count-1, as arrays."""

    def __init__(self, kinds, records, check):
        self.kinds, self.records, self.check = kinds, records, check

    def __len__(self) -> int:
        return len(self.kinds)


@functools.lru_cache(maxsize=2)
def zipfian_weights(n: int, theta: float) -> np.ndarray:
    """W[r] = sum over i <= r of 1/(i+1)^theta: the unnormalized cdf of
    YCSB's ZipfianGenerator, for every item count up to n at once."""
    return np.cumsum(1.0 / np.power(np.arange(1, n + 1, dtype=np.float64),
                                    theta))


def conn_ops(mix: dict, recordcount: int, seed: int, conn: int) -> ConnOps:
    shares = mix["operations"]
    unknown = set(shares) - {"read", "insert"}
    if unknown:
        raise ValueError(f"operations this generator cannot send: {unknown}")
    if mix["keys"]["kind"] != "latest":
        raise ValueError(f"key distribution {mix['keys']['kind']!r} is "
                         "not `latest`")
    count = int(mix["max_ops_per_conn"])
    conns = int(mix["connections"])
    lag = int(mix["lag"])
    rng = np.random.default_rng([int(seed), 0x79637364, conn])
    n_ins = int(round(count * float(shares.get("insert", 0.0))))
    kinds = np.zeros(count, dtype=np.int8)
    kinds[:n_ins] = INSERT
    rng.shuffle(kinds)
    ins = kinds == INSERT
    k = np.cumsum(ins) - ins            # inserts sent before each op
    n_of = recordcount + np.maximum(k - lag, 0) * conns
    w = zipfian_weights(recordcount + max(n_ins - lag, 0) * conns,
                        float(mix["keys"]["constant"]))
    r = np.searchsorted(w, rng.random(count) * w[n_of - 1], side="right")
    records = np.where(ins, recordcount + k * conns + conn,
                       n_of - 1 - np.minimum(r, n_of - 1)).astype(np.int64)
    check = ~ins & ((records >= recordcount)
                    | (rng.random(count) < float(mix["check_share"])))
    return ConnOps(kinds, records, check)


def insert_of(record: np.ndarray, recordcount: int, conns: int):
    """Inserted record -> (connection, its insert number there)."""
    g = record - recordcount
    return g % conns, g // conns
