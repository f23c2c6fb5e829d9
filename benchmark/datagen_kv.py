"""Seeded data for the key-value deployments (memtier_benchmark's shape).

`RegisterWorld` is a cache at steady state: `recordcount` keys
`<key_prefix><n>`, every one present, each one LWW-Register holding
`valuelength` bytes.  Every value is a window of datagen.ValuePool's seeded
pool at an offset that is a function of the key's number alone — so the
snapshot writer, the load generator and the plain reference
(reference_kv.py) derive the same bytes from `--seed`.  Serial spaces as
datagen's: the table's keys take 0 .. n-1, the traffic's writes take
`traffic.write_serial(n, ...)`.

The snapshot goes through the server's own writer (datagen.write_snapshot):
loading data is set-up, not the system under test.  Nothing here imports
JAX.
"""

from __future__ import annotations

import numpy as np

import datagen


class RegisterWorld:
    """The key-value table of one node, from the seed."""

    fieldcount = 1          # traffic.conn_ops draws a field; there is one

    def __init__(self, config: dict, seed: int):
        shape = config["record"]
        self.n = int(config["recordcount"])
        self.width = int(shape["valuelength"])
        self.pool = datagen.ValuePool(seed, self.width)
        self.prefix = shape["key_prefix"].encode()
        self.seed = int(seed)

    def key(self, i: int) -> bytes:
        return b"%s%d" % (self.prefix, i)

    def number(self, key: bytes) -> int:
        return int(key[len(self.prefix):])

    def initial(self, i: int) -> bytes:
        """Key i's value as the snapshot holds it."""
        return self.pool.value(i)

    def values_of(self, serials: np.ndarray) -> np.ndarray:
        """The pool's values of `serials` as an array of fixed-width
        byte strings (the comparison's vectorised side)."""
        windows = np.lib.stride_tricks.sliding_window_view(
            np.frombuffer(self.pool.buf, dtype=np.uint8), self.width)
        rows = np.ascontiguousarray(
            windows[self.pool.offsets(serials).astype(np.int64)])
        return rows.view(f"S{self.width}").ravel()

    def batches(self, chunk_keys: int = 1 << 17):
        """The table as ColumnarBatch chunks (bounded memory)."""
        from constdb_tpu.crdt import semantics as S
        from constdb_tpu.engine.base import ColumnarBatch
        rng = np.random.default_rng([self.seed, 0x7374616D])
        prefix = self.prefix
        for k0 in range(0, self.n, chunk_keys):
            k1 = min(k0 + chunk_keys, self.n)
            nk = k1 - k0
            b = ColumnarBatch()
            b.rows_unique_per_slot = True
            b.keys = [b"%s%d" % (prefix, i) for i in range(k0, k1)]
            b.key_enc = np.full(nk, S.ENC_BYTES, dtype=np.int8)
            ms = rng.integers(0, 300_000, nk, dtype=np.int64)
            t = ((datagen.BASE_MS + ms) << datagen.SEQ_BITS) | \
                rng.integers(0, 8, nk, dtype=np.int64)
            b.key_ct = t
            b.key_mt = t.copy()
            b.key_dt = np.zeros(nk, dtype=np.int64)
            b.key_expire = np.zeros(nk, dtype=np.int64)
            b.reg_t = t.copy()
            b.reg_node = np.ones(nk, dtype=np.int64)
            b.reg_val = self.pool.values(np.arange(k0, k1, dtype=np.int64))
            yield b

    max_stamp = datagen.HashWorld.max_stamp


# datagen's registry of worlds, one more by name (datagen.py itself knows
# the hash table alone): `build_world` is datagen's
WORLDS = datagen.WORLDS
WORLDS.setdefault("memtier-registers", RegisterWorld)
build_world = datagen.build_world
