"""The one general traffic generator: a mix file's parameters and `--seed`
in, every connection's operations out.  The load generator sends them and
the comparison (reference.py) reads them back — both call `conn_ops`, so
the seed alone fixes what each connection sends, in which order.

A mix (`mixes/<name>.json`) gives operation shares (`read`, `update`), the
key distribution (`zipfian` with its constant, or `uniform`), connections,
pipeline depth, warm-up seconds and `max_ops_per_conn`.

Every connection draws its ranks from ONE distribution over all
`recordcount` records, as YCSB's client threads do, and rank r stands for
record `perm[r]` of one seeded permutation that all connections share (the
hot records are scattered over the keyspace, and they are hot for every
connection at once).  So connections do meet on a record: a read may
cross another connection's write, and reference.py judges it by what a
store that reads its acknowledged writes back may answer.

Everything is drawn from `--seed`: each connection's sequence of reads and
updates, of ranks and of fields, which ranks stand for which records, and
every value.
"""

from __future__ import annotations

import functools

import numpy as np

READ, UPDATE = 0, 1


@functools.lru_cache(maxsize=2)
def zipfian_cdf(n: int, theta: float) -> np.ndarray:
    """P(rank <= r) for r = 0..n-1 with P(r) ~ 1/(r+1)^theta (YCSB's
    ZipfianGenerator; its constant is theta = 0.99)."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def draw_ranks(rng, dist: dict, n: int, count: int) -> np.ndarray:
    kind = dist["kind"]
    if kind == "zipfian":
        cdf = zipfian_cdf(n, float(dist["constant"]))
        return np.minimum(np.searchsorted(cdf, rng.random(count)), n - 1)
    if kind == "uniform":
        return rng.integers(0, n, count)
    raise ValueError(f"unknown key distribution {kind!r}")


class ConnOps:
    """Connection `conn`'s operations 0 .. count-1, as arrays."""

    def __init__(self, kinds, records, fields, check):
        self.kinds, self.records, self.fields = kinds, records, fields
        self.check = check     # reads whose reply the parent compares

    def __len__(self) -> int:
        return len(self.kinds)


@functools.lru_cache(maxsize=2)
def record_of_rank(seed: int, recordcount: int) -> np.ndarray:
    """The seed's permutation of the records: rank -> record, the same for
    every connection."""
    return np.random.default_rng([int(seed), 0x6B657973]).permutation(
        recordcount)


def conn_ops(mix: dict, recordcount: int, fieldcount: int, seed: int,
             conn: int) -> ConnOps:
    count = int(mix["max_ops_per_conn"])
    rng = np.random.default_rng([int(seed), 0x6F7073, conn])
    shares = mix["operations"]
    unknown = set(shares) - {"read", "update"}
    if unknown:
        raise ValueError(f"operations this generator cannot send: {unknown}")
    n_upd = int(round(count * float(shares.get("update", 0.0))))
    kinds = np.zeros(count, dtype=np.int8)
    kinds[:n_upd] = UPDATE
    rng.shuffle(kinds)
    ranks = draw_ranks(rng, mix["keys"], recordcount, count)
    fields = rng.integers(0, fieldcount, count)
    check = (kinds == READ) & (rng.random(count)
                               < float(mix["check_share"]))
    records = record_of_rank(int(seed), recordcount)[ranks]
    return ConnOps(kinds, records.astype(np.int64), fields, check)


def write_serial(table_rows: int, mix: dict, conn: int, i: int) -> int:
    """The value pool serial of connection `conn`'s i-th operation, past
    the table's own rows (datagen.ValuePool)."""
    return table_rows + conn * int(mix["max_ops_per_conn"]) + i
