"""Hash-sharded keyspace (store/sharded_keyspace.py, parallel/host_pool.py,
engine/tpu.py ShardDispatcher).

The differential contract this pins:
  * CONSTDB_SHARDS=1 IS today's single-keyspace path — byte-identical
    store state, by construction and by test;
  * N>1 produces per-shard stores byte-identical to running the same
    engine over the same hash-split sub-batches, and the UNION of the
    shards is canonically identical to the unsplit single-path merge on a
    randomized multi-family workload (counters + registers + sets with
    tombstones and key-level deletes).
"""

import numpy as np
import pytest

import bench
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.engine.tpu import ShardDispatcher, TpuMergeEngine
from constdb_tpu.store.keyspace import KeySpace
from constdb_tpu.store.sharded_keyspace import (MAX_SHARDS, ShardedKeySpace,
                                                default_shards,
                                                extract_shard,
                                                keyspace_state_bytes,
                                                shard_ids, shard_of)

_I64 = np.int64


def _workload(n_keys=420, n_rep=3, chunk=120, seed=13):
    """Randomized multi-family chunk stream + key-level delete tombstones
    (make_workload alone never exercises del_keys)."""
    batches = bench.make_workload(n_keys, n_rep, seed=seed)
    chunks = bench.chunk_batches(batches, chunk)
    dels = [b"k%010d" % i for i in range(0, n_keys, 37)]
    c0 = chunks[0]
    c0.del_keys = dels
    c0.del_t = np.arange(1, len(dels) + 1, dtype=_I64) + (1 << 30)
    return chunks


def _split(chunks, n_shards):
    """Parent-side reference split — the same function the workers run."""
    out = [[] for _ in range(n_shards)]
    for c in chunks:
        sids = shard_ids(c.keys, n_shards)
        dsids = shard_ids(c.del_keys, n_shards) if c.del_keys else None
        for s in range(n_shards):
            sub = extract_shard(c, sids, dsids, s)
            if sub.n_rows or sub.del_keys:
                out[s].append(sub)
    return out


def _cpu_reference(chunks):
    ks = KeySpace()
    cpu = CpuMergeEngine()
    for c in chunks:
        cpu.merge(ks, c)
    return ks


# ------------------------------------------------------------------ split


def test_shard_hash_deterministic_and_bounded():
    keys = [b"k%06d" % i for i in range(500)] + [b"", b"\xff" * 40]
    sids = shard_ids(keys, 5)
    assert sids.dtype == np.uint8
    assert int(sids.max()) < 5
    for i, k in enumerate(keys):
        assert sids[i] == shard_of(k, 5)
    # every shard gets a reasonable share (crc32 spreads)
    counts = np.bincount(sids, minlength=5)
    assert (counts > 0).all()


def test_extract_shard_covers_and_remaps():
    chunks = _workload(n_keys=300, n_rep=2, chunk=300)  # one chunk/replica
    c = chunks[0]
    n = 3
    sids = shard_ids(c.keys, n)
    dsids = shard_ids(c.del_keys, n)
    subs = [extract_shard(c, sids, dsids, s) for s in range(n)]
    assert sum(s.n_keys for s in subs) == c.n_keys
    assert sum(len(s.cnt_ki) for s in subs) == len(c.cnt_ki)
    assert sum(len(s.el_ki) for s in subs) == len(c.el_ki)
    assert sum(len(s.del_keys) for s in subs) == len(c.del_keys)
    for s, sub in enumerate(subs):
        assert all(shard_of(k, n) == s for k in sub.keys)
        assert all(shard_of(k, n) == s for k in sub.del_keys)
        # counter/element rows re-point at shard-local key positions
        kid = np.asarray(sub.cnt_ki)
        assert (kid >= 0).all() and (kid < sub.n_keys).all()
        ekid = np.asarray(sub.el_ki)
        assert (ekid >= 0).all() and (ekid < sub.n_keys).all()
        # spot-check a few element rows carry the right member bytes
        for j in range(0, len(ekid), max(1, len(ekid) // 7)):
            orig = np.nonzero(sids[np.asarray(c.el_ki)] == s)[0][j]
            assert sub.el_member[j] == c.el_member[orig]
            assert sub.el_add_t[j] == c.el_add_t[orig]


def test_extract_requires_del_sids():
    chunks = _workload(n_keys=100, n_rep=1, chunk=100)
    c = chunks[0]
    with pytest.raises(ValueError, match="del_keys"):
        extract_shard(c, shard_ids(c.keys, 2), None, 0)


def test_default_shards(monkeypatch):
    monkeypatch.setenv("CONSTDB_SHARDS", "3")
    assert default_shards() == 3
    monkeypatch.setenv("CONSTDB_SHARDS", "9999")
    assert default_shards() == MAX_SHARDS
    monkeypatch.delenv("CONSTDB_SHARDS")
    monkeypatch.setattr("os.cpu_count", lambda: 2)
    assert default_shards() == 1  # <= 2 cores: today's exact path
    monkeypatch.setattr("os.cpu_count", lambda: 8)
    assert default_shards() == 8


# ----------------------------------------------- degenerate single shard


def test_shards1_byte_identical_to_plain_engine():
    """The n_shards=1 facade IS the single-keyspace path: byte-identical
    store state for the same group cadence."""
    chunks = _workload()
    group = 4
    sks = ShardedKeySpace(n_shards=1, engine_spec="tpu", group=group)
    for c in chunks:
        sks.submit(c)
    sks.flush()

    eng = TpuMergeEngine(resident=True)
    ref = KeySpace()
    for i in range(0, len(chunks), group):
        eng.merge_many(ref, chunks[i:i + group])
    eng.flush(ref)

    got = sks.state_bytes_per_shard()
    assert len(got) == 1
    assert got[0] == keyspace_state_bytes(ref)
    sks.close()
    eng.close()


# -------------------------------------------------- local (in-process) N>1


def test_sharded_local_byte_identical_and_union_matches():
    """N=3 in-process shards (ShardDispatcher, real TPU-path engines):
    every shard's store is byte-identical to the same engine run over the
    same split sub-batches, and the union equals the unsplit single-path
    merge canonically."""
    chunks = _workload()
    n, group = 3, 4
    sks = ShardedKeySpace(n_shards=n, mode="local", group=group)
    for c in chunks:
        sks.submit(c)
    sks.flush()

    # per-shard byte-level reference: same engine, same split, same cadence
    split = [[] for _ in range(n)]
    for i in range(0, len(chunks), group):
        for s, subs in enumerate(_split(chunks[i:i + group], n)):
            split[s].append(subs)
    for s in range(n):
        ref = KeySpace()
        eng = TpuMergeEngine(resident=True)
        for subs in split[s]:
            if subs:
                eng.merge_many(ref, subs)
        eng.flush(ref)
        assert keyspace_state_bytes(sks.stores[s]) == \
            keyspace_state_bytes(ref), f"shard {s} diverged"
        eng.close()

    # union vs the unsplit single path
    single = KeySpace()
    eng = TpuMergeEngine(resident=True)
    for i in range(0, len(chunks), group):
        eng.merge_many(single, chunks[i:i + group])
    eng.flush(single)
    assert sks.canonical() == single.canonical()
    eng.close()
    sks.close()


# ------------------------------------------------- process-parallel N>1


def test_sharded_process_cpu_byte_identical():
    """N=2 worker processes (shared-memory transport, CPU engines): each
    worker's store is byte-identical to the reference engine over the
    same split, and the union matches the unsplit reference."""
    chunks = _workload()
    n = 2
    sks = ShardedKeySpace(n_shards=n, mode="process", engine_spec="cpu",
                          group=4)
    for c in chunks:
        sks.submit(c)
    sks.flush()
    got = sks.state_bytes_per_shard()

    split = _split(chunks, n)
    for s in range(n):
        ref = KeySpace()
        cpu = CpuMergeEngine()
        for sub in split[s]:
            cpu.merge(ref, sub)
        assert got[s] == keyspace_state_bytes(ref), f"shard {s} diverged"

    assert sks.canonical() == _cpu_reference(chunks).canonical()
    # the facade routes key subsets by hash too
    some = [b"k%010d" % i for i in range(0, 420, 11)]
    want = {k: v for k, v in _cpu_reference(chunks).canonical().items()
            if k in set(some)}
    assert sks.canonical(keys=some) == want
    sks.close()


def test_process_mode_refuses_device_engines():
    """One process per chip: process-mode workers build the CPU engine
    only, and asking for a device engine there is an error at
    construction — never a child that hangs on a chip its parent
    holds.  (A device engine shards in-process: mode="local", above.)"""
    with pytest.raises(ValueError, match="CPU engine only"):
        ShardedKeySpace(n_shards=2, mode="process", engine_spec="tpu")
    with pytest.raises(ValueError, match="CPU engine only"):
        ShardedKeySpace(n_shards=2, mode="process", engine_spec="cpu",
                        engine_factory=lambda: TpuMergeEngine(resident=True))


def test_consolidate_into_single_keyspace():
    """Shard exports merge back into one serving keyspace (the replica
    catch-up consolidation step) with nothing lost — tombstones
    included."""
    chunks = _workload()
    sks = ShardedKeySpace(n_shards=2, mode="process", engine_spec="cpu",
                          group=4)
    for c in chunks:
        sks.submit(c)
    sks.flush()
    target = KeySpace()
    sks.consolidate_into(target, CpuMergeEngine())
    ref = _cpu_reference(chunks)
    assert target.canonical() == ref.canonical()
    assert target.key_deletes == ref.key_deletes
    sks.close()


def test_load_snapshot_into_sharded_store(tmp_path):
    """load_snapshot fans raw chunk payloads into a sharded store (the
    workers decode AND hash in parallel — the submit_raw path)."""
    from constdb_tpu.persist.snapshot import NodeMeta, dump_keyspace, \
        load_snapshot
    from test_merge_properties import gen_store

    src = gen_store(seed=31, node=5)
    path = str(tmp_path / "src.snapshot")
    dump_keyspace(path, src, NodeMeta(node_id=5), chunk_keys=64)
    sks = ShardedKeySpace(n_shards=2, mode="process", engine_spec="cpu",
                          group=3)
    meta, _records = load_snapshot(path, sks)
    assert meta.node_id == 5
    assert sks.canonical() == src.canonical()
    sks.close()


def test_pool_worker_error_propagates():
    """A worker failure surfaces as a parent-side RuntimeError with the
    worker traceback, not a hang."""
    from constdb_tpu.parallel.host_pool import HostShardPool

    pool = HostShardPool(1)
    try:
        with pytest.raises(RuntimeError, match="shard worker 0"):
            pool.submit_group([], [(b"garbage-not-a-batch",
                                    None, None, None, -1, -1)])
            pool.barrier()
    finally:
        pool.close()
