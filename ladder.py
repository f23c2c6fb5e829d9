#!/usr/bin/env python
"""Config-ladder benchmark: BASELINE.json configs 1-4, one family per rung.

Each rung isolates one CRDT family's merge path, so a regression in one
family cannot hide inside the mixed 10M aggregate (bench.py):

  1. pncounter — 100k INCR PNCounter keys, 2 replicas (cnt val/uuid path)
  2. lwwreg    — 1M LWWRegister keys, 4 replicas, conflicting timestamps
                 (reg rv_t/rv_node + win-value path)
  3. orset     — 1M ORSet keys x 4 members, 8 replicas, add-win union +
                 ~10% tombstones (el sparse-del path)
  4. lwwhash   — 500k LWW-Hash keys x 32 fields, 8 replicas (el
                 value-heavy src path)

For each rung: CPU-engine rate (capped key count — the per-row engine is
scale-flat, bench.py README note), device-engine rate at FULL size, and
the same subsample oracle verification as bench.py (verified flag).

Writes LADDER_r05.json style output:
    python ladder.py [--out LADDER.json] [--cpu-keys 100000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import (_uuids, chunk_batches, require_device,  # noqa: E402
                   time_engine, verify_store)
from constdb_tpu.crdt import semantics as S  # noqa: E402
from constdb_tpu.engine.base import ColumnarBatch  # noqa: E402
from constdb_tpu.engine.cpu import CpuMergeEngine  # noqa: E402

_I64 = np.int64


def _key_plane(b: ColumnarBatch, keys, enc, rng):
    """`keys`/`enc` are SHARED across the replica batches — snapshots of
    one keyspace really do carry identical key planes, and sharing the
    objects lets the engine's shape memo resolve them once."""
    n = len(keys)
    b.rows_unique_per_slot = True
    b.keys = keys
    b.key_enc = enc
    b.key_ct = _uuids(rng, n)
    b.key_mt = b.key_ct.copy()
    b.key_dt = np.zeros(n, dtype=_I64)
    b.key_expire = np.zeros(n, dtype=_I64)
    b.reg_val = [None] * n
    b.reg_t = np.zeros(n, dtype=_I64)
    b.reg_node = np.zeros(n, dtype=_I64)
    return n


def gen_pncounter(n_keys, n_rep, seed=11):
    """Config 1: every replica carries its own (key, node) counter slot —
    the post-INCR snapshot state of a 100k-key PN-counter keyspace."""
    rng = np.random.default_rng(seed)
    keys = [b"cnt%08d" % i for i in range(n_keys)]
    enc = np.full(n_keys, S.ENC_COUNTER, dtype=np.int8)
    out = []
    for r in range(n_rep):
        b = ColumnarBatch()
        _key_plane(b, keys, enc, rng)
        b.cnt_ki = np.arange(n_keys, dtype=_I64)
        b.cnt_node = np.full(n_keys, r + 1, dtype=_I64)
        b.cnt_val = rng.integers(-10_000, 10_000, n_keys).astype(_I64)
        b.cnt_uuid = _uuids(rng, n_keys)
        b.cnt_base = np.zeros(n_keys, dtype=_I64)
        b.cnt_base_t = np.full(n_keys, S.NEUTRAL_T, dtype=_I64)
        out.append(b)
    return out


def gen_lwwreg(n_keys, n_rep, seed=12):
    """Config 2: same keys on every replica with CONFLICTING timestamps —
    every slot resolves through the lexicographic (t, node) LWW."""
    rng = np.random.default_rng(seed)
    keys = [b"reg%08d" % i for i in range(n_keys)]
    enc = np.full(n_keys, S.ENC_BYTES, dtype=np.int8)
    pool = [b"val-%05d" % i for i in range(2048)]
    out = []
    for r in range(n_rep):
        b = ColumnarBatch()
        _key_plane(b, keys, enc, rng)
        idx = rng.integers(0, len(pool), n_keys)
        b.reg_val = [pool[i] for i in idx]
        b.reg_t = _uuids(rng, n_keys)
        b.reg_node = np.full(n_keys, r + 1, dtype=_I64)
        out.append(b)
    return out


def gen_orset(n_keys, n_rep, seed=13, members_per_set=4):
    """Config 3: add-win union with ~10% tombstones (sparse del side)."""
    rng = np.random.default_rng(seed)
    keys = [b"set%08d" % i for i in range(n_keys)]
    member_pool = [b"m%04d" % i for i in range(4096)]
    ki = np.repeat(np.arange(n_keys, dtype=_I64), members_per_set)
    midx = rng.integers(0, len(member_pool), len(ki))
    combo = (ki << 32) | midx
    _, first = np.unique(combo, return_index=True)
    first.sort()
    ki, midx = ki[first], midx[first]
    members = [member_pool[i] for i in midx]
    vals = [None] * len(ki)
    enc = np.full(n_keys, S.ENC_SET, dtype=np.int8)
    out = []
    for r in range(n_rep):
        b = ColumnarBatch()
        _key_plane(b, keys, enc, rng)
        b.el_ki = ki
        b.el_member = members
        b.el_val = vals
        b.el_add_t = _uuids(rng, len(ki))
        b.el_add_node = np.full(len(ki), r + 1, dtype=_I64)
        b.el_del_t = np.where(rng.random(len(ki)) < 0.1,
                              _uuids(rng, len(ki)), 0).astype(_I64)
        out.append(b)
    return out


def gen_lwwhash(n_keys, n_rep, seed=14, fields=32):
    """Config 4: per-field LWW with VALUES — the el src/win-value path at
    32 fields per key."""
    rng = np.random.default_rng(seed)
    keys = [b"h%08d" % i for i in range(n_keys)]
    field_names = [b"f%02d" % i for i in range(fields)]
    val_pool = [b"hv-%05d" % i for i in range(4096)]
    ki = np.repeat(np.arange(n_keys, dtype=_I64), fields)
    members = field_names * n_keys
    enc = np.full(n_keys, S.ENC_DICT, dtype=np.int8)
    out = []
    for r in range(n_rep):
        b = ColumnarBatch()
        _key_plane(b, keys, enc, rng)
        b.el_ki = ki
        b.el_member = members
        vidx = rng.integers(0, len(val_pool), len(ki))
        b.el_val = [val_pool[i] for i in vidx]
        b.el_add_t = _uuids(rng, len(ki))
        b.el_add_node = np.full(len(ki), r + 1, dtype=_I64)
        b.el_del_t = np.zeros(len(ki), dtype=_I64)
        out.append(b)
    return out


CONFIGS = [
    ("pncounter", gen_pncounter, 100_000, 2),
    ("lwwreg", gen_lwwreg, 1_000_000, 4),
    ("orset", gen_orset, 1_000_000, 8),
    ("lwwhash", gen_lwwhash, 500_000, 8),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-keys", type=int, default=100_000,
                    help="key cap for the pure-Python baseline run")
    ap.add_argument("--chunk", type=int, default=1 << 17)
    ns = ap.parse_args()

    # every rung is a device leg: no accelerator, no ladder
    _, device = require_device()
    from constdb_tpu.engine.tpu import TpuMergeEngine

    results = []
    for name, gen, n_keys, n_rep in CONFIGS:
        t0 = time.perf_counter()
        n_cpu = min(n_keys, ns.cpu_keys)
        cpu_chunks = chunk_batches(gen(n_cpu, n_rep), ns.chunk)
        cpu_t, _ = time_engine(CpuMergeEngine, cpu_chunks, repeats=1)
        cpu_rate = n_cpu / cpu_t

        batches = gen(n_keys, n_rep)
        chunks = chunk_batches(batches, ns.chunk)
        group = 4 * n_rep
        dev_t, store = time_engine(
            lambda: TpuMergeEngine(resident=True), chunks,
            repeats=1 if n_keys >= 500_000 else 2, group=group)
        dev_rate = n_keys / dev_t
        ok, n_checked, n_diff = verify_store(store, batches, n_keys,
                                             target=50_000)
        row = {"config": name, "keys": n_keys, "replicas": n_rep,
               "cpu_keys": n_cpu, "cpu_keys_per_sec": round(cpu_rate, 1),
               "device_keys_per_sec": round(dev_rate, 1),
               "device_wall_s": round(dev_t, 2),
               "speedup": round(dev_rate / cpu_rate, 2),
               "verified": ok, "verify_keys": n_checked,
               "device": device}
        results.append(row)
        print(f"[ladder] {name}: cpu {cpu_rate:,.0f} k/s (at {n_cpu}), "
              f"device {dev_rate:,.0f} k/s ({dev_t:.2f}s), "
              f"verify={'OK' if ok else f'{n_diff} DIFFS'} "
              f"(total {time.perf_counter() - t0:.1f}s)", file=sys.stderr)
        if not ok:
            print(json.dumps({"error": f"{name} verification failed",
                              "results": results}))
            sys.exit(1)

    out = {"metric": "family_ladder_keys_per_sec", "device": device,
           "results": results}
    print(json.dumps(out))
    if ns.out:
        with open(ns.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"[ladder] wrote {ns.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
