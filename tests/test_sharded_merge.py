"""Sharded SPMD merge over a virtual 8-device mesh must equal the
single-device dense kernels bit-for-bit."""

import subprocess
import sys

import jax
import numpy as np
import pytest

from constdb_tpu.ops import dense as D
from constdb_tpu.ops.segment import NEUTRAL_T
from constdb_tpu.parallel import make_mesh, shard_batch_arrays, sharded_merge_step

_HAVE_MESH = len(jax.devices()) >= 8

needs_mesh = pytest.mark.skipif(
    not _HAVE_MESH, reason="needs 8 devices (re-run via subprocess below)")


def test_reruns_on_virtual_cpu_mesh_if_needed():
    """When this interpreter runs on the chip (CONSTDB_TEST_TPU=1: one
    device), the mesh tests above are skipped — re-run this module in a
    subprocess on the virtual 8-device CPU platform so they always
    execute somewhere."""
    if _HAVE_MESH:
        return  # ran inline
    import os

    if os.environ.get("CONSTDB_MESH_RERUN"):
        pytest.fail("virtual CPU mesh unavailable even in the clean-env "
                    "subprocess — not recursing further")
    from conftest import cpu_mesh_subprocess_env

    r = subprocess.run(
        [sys.executable, "-m", "pytest", __file__, "-q", "--no-header"],
        env=cpu_mesh_subprocess_env(), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert " passed" in r.stdout and "failed" not in r.stdout, r.stdout


def _random_inputs(rng, R, S):
    # single source of truth for this input shape lives in __graft_entry__
    from __graft_entry__ import _example_arrays
    return _example_arrays(R, S, seed=int(rng.integers(0, 1 << 31)))


@needs_mesh
@pytest.mark.parametrize("rep,seed", [(1, 0), (2, 1), (4, 2), (8, 3)])
def test_matches_single_device(rep, seed):
    R, S = 8, 256
    rng = np.random.default_rng(seed)
    vals, ts, at, an, dt, env = _random_inputs(rng, R, S)

    mesh = make_mesh(8, rep=rep)
    step = sharded_merge_step(mesh)
    d_in = shard_batch_arrays(mesh, vals, ts, at, an, dt, env)
    V, T, AT, AN, DT, WIN, ENV, touched = jax.device_get(step(*d_in))

    v1, t1 = jax.device_get(D.dense_merge_counters(vals, ts))
    a1, n1, d1, w1 = jax.device_get(D.dense_merge_elems(at, an, dt))
    e1 = jax.device_get(D.dense_max(env))

    np.testing.assert_array_equal(V, v1)
    np.testing.assert_array_equal(T, t1)
    np.testing.assert_array_equal(AT, a1)
    np.testing.assert_array_equal(AN, n1)
    np.testing.assert_array_equal(DT, d1)
    np.testing.assert_array_equal(ENV, e1)
    # winner indices must agree wherever a real winner exists
    np.testing.assert_array_equal(WIN, w1)
    assert touched == np.sum(t1 > NEUTRAL_T)


slow = pytest.mark.skipif(
    not __import__("os").environ.get("CONSTDB_SLOW"),
    reason="set CONSTDB_SLOW=1 for the 100k-key mesh soak")


@needs_mesh
@slow
def test_kv_sharded_engine_at_scale():
    """The PRODUCTION kv-sharded merge path (TpuMergeEngine(mesh=...)) at
    real scale: ≥100k keys streamed as non-pow2 chunks, so per-shard state
    spans many tiles, the pow2+multiple-of-kv padding rule exercises both
    branches, and chunk boundaries straddle range-partition edges.  Must
    stay canonical()-identical to the CPU engine (VERDICT r4 item 6 —
    shard-boundary bugs hide at toy sizes where every slot fits one tile).
    """
    import bench
    from constdb_tpu.engine.cpu import CpuMergeEngine
    from constdb_tpu.engine.tpu import TpuMergeEngine
    from constdb_tpu.parallel import engine_mesh
    from constdb_tpu.persist.snapshot import batch_chunks
    from constdb_tpu.store.keyspace import KeySpace

    n_keys, n_rep = 120_000, 4
    batches = bench.make_workload(n_keys, n_rep, seed=23)
    # 13_331 is deliberately non-pow2 and coprime with 8: every chunk ends
    # inside a shard's slot range, never on a partition edge
    chunks = bench.chunk_batches(batches, 13_331)

    eng = TpuMergeEngine(resident=True, mesh=engine_mesh(8))
    st = KeySpace()
    group = 2 * n_rep
    for i in range(0, len(chunks), group):
        eng.merge_many(st, chunks[i:i + group])
    eng.flush(st)

    oracle = KeySpace()
    cpu = CpuMergeEngine()
    for b in batches:
        cpu.merge(oracle, b)
    got, want = st.canonical(), oracle.canonical()
    assert len(got) == n_keys
    diff = [k for k in want if got.get(k) != want[k]]
    assert not diff, f"{len(diff)} keys diverge, e.g. {diff[:3]}"
    assert got == want


@needs_mesh
def test_kv_sharded_engine_device_iota_idx():
    """The device-derived (iota) idx must carry the replicated mesh
    sharding — mixing a default-device idx with kv-sharded state would
    crash or silently degrade the bulk kernels.  Forced on (threshold 1)
    at small scale, canonical()-checked against the CPU engine."""
    import bench
    from constdb_tpu.engine.cpu import CpuMergeEngine
    from constdb_tpu.engine.tpu import TpuMergeEngine
    from constdb_tpu.parallel import engine_mesh
    from constdb_tpu.store.keyspace import KeySpace

    batches = bench.make_workload(3000, 4, seed=41)
    eng = TpuMergeEngine(resident=True, mesh=engine_mesh(8))
    eng.IDX_IOTA_MIN = 1
    st = KeySpace()
    eng.merge_many(st, batches)
    eng.flush(st)
    oracle = KeySpace()
    cpu = CpuMergeEngine()
    for b in batches:
        cpu.merge(oracle, b)
    assert st.canonical() == oracle.canonical()


@needs_mesh
def test_row0_wins_ties_across_rep_shards():
    """The local-state row (global row 0) must win exact (t, node) ties even
    when the tying replica row lives on another rep shard."""
    R, S = 8, 128
    at = np.full((R, S), NEUTRAL_T, np.int64)
    an = np.zeros((R, S), np.int64)
    dt = np.zeros((R, S), np.int64)
    at[0], an[0] = 5 << 22, 3   # local state
    at[7], an[7] = 5 << 22, 3   # identical write from a replica on shard 3
    vals = np.zeros((R, S), np.int64)
    ts = np.full((R, S), NEUTRAL_T, np.int64)
    env = np.zeros((R, S, 4), np.int64)

    mesh = make_mesh(8, rep=4)
    step = sharded_merge_step(mesh)
    out = jax.device_get(step(*shard_batch_arrays(mesh, vals, ts, at, an, dt, env)))
    WIN = out[5]
    assert (WIN == 0).all()
