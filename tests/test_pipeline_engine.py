"""Pipelined merge dispatch (engine/tpu.py stage/dispatch split).

The double-buffered pipeline overlaps host STAGING of family k+1 with
DISPATCH of family k.  Everything here pins the contract that makes the
overlap safe: byte-identical results vs the serial path, the
flush-before-touch invariant still failing loudly, and the win-pool id
ceiling flushing at a round boundary instead of raising mid-round.
"""

import numpy as np
import pytest

import bench
from constdb_tpu.engine.base import batch_from_keyspace
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.store.keyspace import KeySpace


def _run_rounds(engine, chunks, group):
    """Two-plus deterministic merge_many rounds into a fresh store."""
    st = KeySpace()
    for i in range(0, len(chunks), group):
        engine.merge_many(st, chunks[i:i + group])
    if engine.needs_flush:
        engine.flush(st)
    return st


def _store_bytes(ks: KeySpace):
    """Exact store state: every numeric column byte plus the object
    planes — stricter than canonical(), which normalizes."""
    n, c, e = ks.keys.n, ks.cnt.n, ks.el.n
    return (
        {name: ks.keys.col(name)[:n].tobytes()
         for name in ("enc", "ct", "mt", "dt", "expire", "rv_t", "rv_node",
                      "cnt_sum")},
        {name: ks.cnt.col(name)[:c].tobytes()
         for name in ("kid", "node", "val", "uuid", "base", "base_t")},
        {name: ks.el.col(name)[:e].tobytes()
         for name in ("kid", "add_t", "add_node", "del_t")},
        list(ks.key_bytes), list(ks.reg_val), list(ks.el_member),
        list(ks.el_val), dict(ks.key_deletes), sorted(ks.garbage),
    )


@pytest.mark.parametrize("group", [4, 8])
def test_pipeline_matches_serial_byte_identical(group):
    """The deterministic two-round merge_many produces BYTE-identical
    store state with the pipeline on and off (the serial path stays
    selectable via the ctor knob / CONSTDB_PIPELINE)."""
    batches = bench.make_workload(600, 4, seed=11)
    chunks = bench.chunk_batches(batches, 150)  # several rounds per run
    st_pipe = _run_rounds(
        TpuMergeEngine(resident=True, pipeline=True), chunks, group)
    st_serial = _run_rounds(
        TpuMergeEngine(resident=True, pipeline=False), chunks, group)
    a, b = _store_bytes(st_pipe), _store_bytes(st_serial)
    for got, want in zip(a, b):
        assert got == want
    # and both match the CPU reference
    ref = KeySpace()
    cpu = CpuMergeEngine()
    for c in chunks:
        cpu.merge(ref, c)
    assert st_pipe.canonical() == ref.canonical()


def test_pipeline_env_knob(monkeypatch):
    monkeypatch.setenv("CONSTDB_PIPELINE", "0")
    assert TpuMergeEngine().pipeline is False
    monkeypatch.delenv("CONSTDB_PIPELINE")
    assert TpuMergeEngine().pipeline is True
    assert TpuMergeEngine(pipeline=False).pipeline is False


def test_flush_before_touch_still_raises_under_pipeline():
    """An op-path write to a plane holding unflushed merged columns must
    still fail loudly when the next (pipelined) merge finds the stale
    mirror — overlapped staging must not swallow the invariant."""
    batches = bench.make_workload(200, 2, seed=3)
    eng = TpuMergeEngine(resident=True, pipeline=True)
    st = KeySpace()
    eng.merge_many(st, batches)
    assert eng.needs_flush
    # simulate a buggy caller: host write WITHOUT Node.ensure_flushed
    st.touch("el")
    with pytest.raises(RuntimeError, match="flush-before-touch"):
        eng.merge_many(st, bench.make_workload(200, 2, seed=4))


def test_pool_ceiling_flushes_at_round_boundary():
    """A round that would cross the int32 src-plane id ceiling triggers a
    flush FIRST (the documented remedy) instead of raising mid-round."""
    batches = bench.make_workload(300, 2, seed=9)
    eng = TpuMergeEngine(resident=True, pipeline=True)
    st = KeySpace()
    eng.merge_many(st, batches)
    assert eng._pool_size > 0
    # next round's rows would cross a ceiling barely above the current
    # pool: merge_many must flush, then succeed
    eng.POOL_ID_CEILING = eng._pool_size + 1
    more = bench.make_workload(300, 2, seed=10)
    eng.merge_many(st, more)
    eng.flush(st)
    ref = KeySpace()
    cpu = CpuMergeEngine()
    for b in batches + more:
        cpu.merge(ref, b)
    assert st.canonical() == ref.canonical()


def test_pool_single_round_overflow_raises_before_mutation():
    """A single round too large for the id space raises BEFORE mutating
    pool state (the old check appended first, corrupting the pool)."""
    eng = TpuMergeEngine(resident=True)
    eng.POOL_ID_CEILING = 1  # no round fits
    with pytest.raises(RuntimeError, match="single"):
        eng._pool_add(None, col=np.arange(8, dtype=np.int64))
    assert eng._pool_size == 0 and not eng._val_pool


def test_sparse_rank_falls_back_to_hash():
    """A rank touching few kids across a wide range converts to hash mode
    instead of paying an O(kid range) dense window (round-5 advisor)."""
    ks = KeySpace()
    wide = 5_000_000
    kids = np.array([0, wide], dtype=np.int64)
    rows = ks.cnt.append_block(2, kid=kids, node=7, val=0,
                               uuid=ks.NEUTRAL_T, base=0,
                               base_t=ks.NEUTRAL_T)
    rank = ks.rank_of(7)
    ks.cnt_rows_assign(rank, kids, rows)
    assert rank in ks.cnt_rank_hash and rank not in ks.cnt_rank_rows
    got = ks.cnt_rows_lookup(rank, kids)
    assert got.tolist() == rows.tolist()
    # op path agrees and keeps extending the hash
    assert ks._cnt_row(0, node=7) == rows[0]
    assert ks._cnt_row(wide, node=7) == rows[1]
    r3 = ks._cnt_row(wide // 2, node=7)
    assert ks.cnt_rows_lookup(rank, np.array([wide // 2]))[0] == r3
    # memory: nothing dense was ever allocated for this rank
    assert ks.memory_report()["numeric_bytes"] < (1 << 22)


def test_clustered_rank_stays_dense():
    """Clustered kids keep the vectorized dense window (the fast path)."""
    ks = KeySpace()
    kids = np.arange(500, dtype=np.int64)
    rows = ks.cnt.append_block(500, kid=kids, node=3, val=0,
                               uuid=ks.NEUTRAL_T, base=0,
                               base_t=ks.NEUTRAL_T)
    rank = ks.rank_of(3)
    ks.cnt_rows_assign(rank, kids, rows)
    assert rank in ks.cnt_rank_rows and rank not in ks.cnt_rank_hash
    assert ks.cnt_rows_lookup(rank, kids).tolist() == rows.tolist()


def test_build_engine_starts_no_process(monkeypatch):
    """JAX initializes in the server process, once: build_engine never
    probes the backend from a second process (a child that touches the
    chip before the server does would hold it — one process per
    chip)."""
    import subprocess

    import constdb_tpu.conf as conf

    def boom(*a, **kw):
        raise AssertionError("build_engine spawned a process")

    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(subprocess, "Popen", boom)
    for kind in ("auto", "cpu"):
        assert conf.build_engine(kind).name == "cpu"


def test_bench_smoke_pipelined_end_to_end():
    """Fast tier-1 bench smoke: the pipelined engine runs the real
    chunked snapshot-merge cadence end-to-end WITH oracle verification,
    so dispatch-path regressions fail tests instead of waiting for the
    next bench round."""
    n_keys, n_rep = 50_000, 4
    batches = bench.make_workload(n_keys, n_rep, seed=7)
    chunks = bench.chunk_batches(batches, 1 << 14)
    eng = TpuMergeEngine(resident=True, dense_fold="auto", pipeline=True)
    st = KeySpace()
    group = 2 * n_rep
    for i in range(0, len(chunks), group):
        eng.merge_many(st, chunks[i:i + group])
    eng.flush(st)
    assert eng.folds > 0
    ok, n_checked, n_diff = bench.verify_store(st, batches, n_keys,
                                               target=1_500)
    assert ok, f"{n_diff} diffs on {n_checked} sampled keys"


def test_snapshot_roundtrip_through_pipeline():
    """A full keyspace dump re-merged through the pipelined engine equals
    the source (idempotent state merge)."""
    from test_merge_properties import gen_store

    src = gen_store(seed=21, node=4)
    b = batch_from_keyspace(src)
    eng = TpuMergeEngine(resident=True, pipeline=True)
    st = KeySpace()
    eng.merge_many(st, [b])
    eng.flush(st)
    assert st.canonical() == src.canonical()
