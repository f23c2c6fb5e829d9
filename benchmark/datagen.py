"""Seeded data for the benchmark's deployments.

`HashWorld` is the YCSB table: `recordcount` records, each one LWW-Hash
of `fieldcount` fields x `fieldlength` bytes.  Every value is a window of one
seeded pool of random bytes, at an offset that is a function of (record,
field) alone — so the snapshot writer, the load generator and the plain
reference (reference.py) all derive the same bytes from `--seed` without
shipping the gigabyte between them.

The snapshot goes through the server's own writer
(persist/snapshot.py write_snapshot_file): loading data is set-up, not the
system under test.  Nothing here imports JAX.
"""

from __future__ import annotations

import numpy as np

SEQ_BITS = 22
BASE_MS = 1_700_000_000_000     # every generated stamp is long past
POOL_BYTES = 16 << 20
_MULT = 2654435761              # Knuth's multiplicative hash constant


class ValuePool:
    """Seeded pool of printable bytes; `value(i)` is a `width`-byte window
    at a scrambled offset of serial `i`.  Serial spaces: the table's rows
    take 0 .. rows-1, the traffic's writes take `write_serial(...)`."""

    def __init__(self, seed: int, width: int):
        rng = np.random.default_rng([int(seed), 0x706F6F6C])
        self.width = width
        self.span = POOL_BYTES - width
        self.buf = rng.integers(97, 123, POOL_BYTES, dtype=np.uint8).tobytes()
        self.salt = int(rng.integers(0, self.span))

    def offsets(self, serials: np.ndarray) -> np.ndarray:
        return (serials.astype(np.uint64) * np.uint64(_MULT)
                + np.uint64(self.salt)) % np.uint64(self.span)

    def value(self, serial: int) -> bytes:
        off = (serial * _MULT + self.salt) % self.span
        return self.buf[off:off + self.width]

    def values(self, serials: np.ndarray) -> list:
        buf, w = self.buf, self.width
        return [buf[o:o + w] for o in self.offsets(serials).tolist()]


class HashWorld:
    """The YCSB table of one node, from the seed."""

    def __init__(self, config: dict, seed: int):
        shape = config["record"]
        self.n = int(config["recordcount"])
        self.fieldcount = int(shape["fieldcount"])
        self.pool = ValuePool(seed, int(shape["fieldlength"]))
        self.fields = [b"field%d" % j for j in range(self.fieldcount)]
        self.seed = int(seed)

    @staticmethod
    def key(i: int) -> bytes:
        return b"user%012d" % i

    def initial(self, i: int) -> dict:
        """Record i as the snapshot holds it: field -> value."""
        base = i * self.fieldcount
        return {f: self.pool.value(base + j)
                for j, f in enumerate(self.fields)}

    def batches(self, chunk_keys: int = 1 << 16):
        """The table as ColumnarBatch chunks (bounded memory)."""
        from constdb_tpu.crdt import semantics as S
        from constdb_tpu.engine.base import ColumnarBatch
        fc = self.fieldcount
        rng = np.random.default_rng([self.seed, 0x7374616D])
        for k0 in range(0, self.n, chunk_keys):
            k1 = min(k0 + chunk_keys, self.n)
            nk = k1 - k0
            rows = nk * fc
            b = ColumnarBatch()
            b.rows_unique_per_slot = True
            b.keys = [b"user%012d" % i for i in range(k0, k1)]
            b.key_enc = np.full(nk, S.ENC_DICT, dtype=np.int8)
            ms = rng.integers(0, 300_000, rows, dtype=np.int64)
            add_t = ((BASE_MS + ms) << SEQ_BITS) | \
                rng.integers(0, 8, rows, dtype=np.int64)
            b.el_ki = np.repeat(np.arange(nk, dtype=np.int64), fc)
            b.el_member = self.fields * nk
            b.el_add_t = add_t
            b.el_add_node = np.ones(rows, dtype=np.int64)
            b.el_del_t = np.zeros(rows, dtype=np.int64)
            b.el_val = self.pool.values(
                np.arange(k0 * fc, k1 * fc, dtype=np.int64))
            ct = add_t.reshape(nk, fc).max(axis=1)
            b.key_ct = ct
            b.key_mt = ct.copy()
            b.key_dt = np.zeros(nk, dtype=np.int64)
            b.key_expire = np.zeros(nk, dtype=np.int64)
            b.reg_t = np.zeros(nk, dtype=np.int64)
            b.reg_node = np.zeros(nk, dtype=np.int64)
            b.reg_val = [None] * nk
            yield b

    max_stamp = ((BASE_MS + 300_000) << SEQ_BITS) | 7


WORLDS = {"ycsb-hash": HashWorld}


def build_world(config: dict, seed: int):
    kind = config["world"]
    if kind not in WORLDS:
        raise ValueError(f"unknown world {kind!r} (have {sorted(WORLDS)})")
    return WORLDS[kind](config, seed)


def write_snapshot(world, path: str, node_id: int, alias: str, addr: str,
                   compress_level: int) -> int:
    """One node's boot snapshot through the server's own writer."""
    from constdb_tpu.persist.snapshot import NodeMeta, write_snapshot_file
    meta = NodeMeta(node_id=node_id, alias=alias, addr=addr,
                    repl_last_uuid=world.max_stamp)
    return write_snapshot_file(path, meta, [], world.batches(),
                               compress_level=compress_level)
