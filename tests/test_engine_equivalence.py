"""Differential tests: the batched JAX engine must be bit-identical to the
CPU reference engine on random multi-node CRDT states (SURVEY.md §7 "Exact
tie semantics ... must be bit-identical between CPU and TPU engines or
replicas diverge").
"""

import pytest

from constdb_tpu.crdt import ENC_COUNTER, ENC_DICT, ENC_SET
from constdb_tpu.engine import CpuMergeEngine, batch_from_keyspace
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.store import KeySpace

from test_merge_properties import gen_store


@pytest.fixture(scope="module", params=["bulk", "scatter"])
def engines(request):
    tpu = TpuMergeEngine()
    # force the chooser: both device strategies must match the CPU engine
    # (bulk needs rows-unique batches; those tests fall back to scatter)
    tpu.BULK_FRACTION = 10**18 if request.param == "bulk" else 0
    return CpuMergeEngine(), tpu


def both_sums(ks):
    return {k: ks.counter_sum(kid) for kid, k in enumerate(ks.key_bytes)
            if ks.enc_of(kid) == ENC_COUNTER}


@pytest.mark.parametrize("seed", range(10))
def test_merge_into_empty_matches_cpu(engines, seed):
    cpu, tpu = engines
    src = gen_store(seed, node=1)
    a, b = KeySpace(), KeySpace()
    s1 = cpu.merge(a, batch_from_keyspace(src))
    s2 = tpu.merge(b, batch_from_keyspace(src))
    assert a.canonical() == b.canonical()
    assert both_sums(a) == both_sums(b)
    assert (s1.keys_seen, s1.keys_created) == (s2.keys_seen, s2.keys_created)


@pytest.mark.parametrize("seed", range(10))
def test_merge_overlapping_states_matches_cpu(engines, seed):
    cpu, tpu = engines
    x = gen_store(seed, node=1)
    y = gen_store(seed + 1000, node=2)
    bx, by = batch_from_keyspace(x), batch_from_keyspace(y)

    a = KeySpace()
    cpu.merge(a, bx)
    cpu.merge(a, by)
    b = KeySpace()
    tpu.merge(b, bx)
    tpu.merge(b, by)
    assert a.canonical() == b.canonical()
    assert both_sums(a) == both_sums(b)


@pytest.mark.parametrize("seed", [0, 3, 7])
def test_three_way_and_idempotent(engines, seed):
    cpu, tpu = engines
    batches = [batch_from_keyspace(gen_store(seed + i * 77, node=i + 1)) for i in range(3)]
    a, b = KeySpace(), KeySpace()
    for bt in batches + [batches[0]]:  # re-merge first batch: idempotence
        cpu.merge(a, bt)
        tpu.merge(b, bt)
    assert a.canonical() == b.canonical()


@pytest.mark.parametrize("seed", [2, 5])
def test_gc_after_tpu_merge_matches_cpu(engines, seed):
    cpu, tpu = engines
    x = gen_store(seed, node=1)
    y = gen_store(seed + 500, node=2)
    a, b = KeySpace(), KeySpace()
    for eng, ks in ((cpu, a), (tpu, b)):
        eng.merge(ks, batch_from_keyspace(x))
        eng.merge(ks, batch_from_keyspace(y))
        ks.gc(40 << 22)  # horizon past every uuid in gen_store
    assert a.canonical() == b.canonical()
    # all dead elements must have been collected identically
    for ks in (a, b):
        for kid, key in enumerate(ks.key_bytes):
            if ks.enc_of(kid) in (ENC_SET, ENC_DICT):
                for m, at, an, dt, v in ks.elem_all(kid):
                    assert at >= dt, (key, m)


def test_type_conflict_skipped_tpu():
    tpu = TpuMergeEngine()
    a, b = KeySpace(), KeySpace()
    ka, _ = a.get_or_create(b"k", ENC_COUNTER, 5 << 22)
    a.counter_change(ka, 1, 1, 5 << 22)
    kb, _ = b.get_or_create(b"k", ENC_SET, 6 << 22)
    b.elem_add(kb, b"m", None, 6 << 22, 2)
    st = tpu.merge(a, batch_from_keyspace(b))
    assert st.type_conflicts == 1
    assert a.counter_sum(a.lookup(b"k")) == 1


def test_empty_batch():
    tpu = TpuMergeEngine()
    ks = KeySpace()
    st = tpu.merge(ks, batch_from_keyspace(KeySpace()))
    assert st.keys_seen == 0


def test_duplicate_slot_rows_in_one_batch():
    """A batch built from a raw op stream can carry several rows for the same
    (key, node) slot; the engine must LWW-reduce them, not keep the last
    placement (regression: the dense path used to silently drop all but the
    final row)."""
    import numpy as np

    from constdb_tpu.engine.base import ColumnarBatch

    b = ColumnarBatch()
    b.keys = [b"k"]
    b.key_enc = np.array([0], np.int8)  # counter
    b.key_ct = np.array([1 << 22], np.int64)
    b.key_mt = np.array([0], np.int64)
    b.key_dt = np.array([0], np.int64)
    b.key_expire = np.array([0], np.int64)
    b.reg_val = [None]
    b.reg_t = np.zeros(1, np.int64)
    b.reg_node = np.zeros(1, np.int64)
    # newer write listed FIRST: last-placement would keep the stale value
    b.cnt_ki = np.array([0, 0], np.int64)
    b.cnt_node = np.array([7, 7], np.int64)
    b.cnt_val = np.array([50, 3], np.int64)
    b.cnt_uuid = np.array([9 << 22, 2 << 22], np.int64)
    b.cnt_base = np.zeros(2, np.int64)
    b.cnt_base_t = np.full(2, KeySpace.NEUTRAL_T, np.int64)
    assert not b.rows_unique_per_slot

    for eng in (CpuMergeEngine(), TpuMergeEngine()):
        ks = KeySpace()
        eng.merge(ks, b)
        assert ks.counter_sum(ks.lookup(b"k")) == 50, eng.name


def test_duplicate_keys_in_one_batch():
    """A raw op-stream batch may list the same key twice; the engine must
    resolve both to one store row (regression: bulk-create used to make two
    rows and orphan one)."""
    import numpy as np

    from constdb_tpu.engine.base import ColumnarBatch

    b = ColumnarBatch()
    b.keys = [b"k", b"k"]
    b.key_enc = np.array([0, 0], np.int8)
    b.key_ct = np.array([1 << 22, 1 << 22], np.int64)
    b.key_mt = np.zeros(2, np.int64)
    b.key_dt = np.zeros(2, np.int64)
    b.key_expire = np.zeros(2, np.int64)
    b.reg_val = [None, None]
    b.reg_t = np.zeros(2, np.int64)
    b.reg_node = np.zeros(2, np.int64)
    b.cnt_ki = np.array([0, 1], np.int64)
    b.cnt_node = np.array([1, 2], np.int64)
    b.cnt_val = np.array([5, 10], np.int64)
    b.cnt_uuid = np.array([2 << 22, 3 << 22], np.int64)
    b.cnt_base = np.zeros(2, np.int64)
    b.cnt_base_t = np.full(2, KeySpace.NEUTRAL_T, np.int64)

    for eng in (CpuMergeEngine(), TpuMergeEngine()):
        ks = KeySpace()
        eng.merge(ks, b)
        assert ks.n_keys() == 1, eng.name
        assert ks.counter_sum(ks.lookup(b"k")) == 15, eng.name


# ------------------------------------------------- multi-device (kv mesh)

@pytest.fixture(scope="module")
def kv_mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the 8-device virtual CPU platform (conftest)")
    from constdb_tpu.parallel import engine_mesh
    return engine_mesh()


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_mesh_engine_matches_cpu(kv_mesh, resident, seed):
    """The kv-sharded engine (state range-partitioned over the device
    mesh) must stay bit-identical to the CPU engine on streamed chunked
    catch-up — the production replica-link access pattern."""
    from constdb_tpu.persist.snapshot import batch_chunks

    srcs = [gen_store(seed * 10 + i, node=i + 1) for i in range(3)]
    chunks = [c for src in srcs
              for c in batch_chunks(batch_from_keyspace(src), 37)]

    a = KeySpace()
    cpu = CpuMergeEngine()
    for c in chunks:
        cpu.merge(a, c)

    b = KeySpace()
    eng = TpuMergeEngine(resident=resident, mesh=kv_mesh)
    for c in chunks:
        eng.merge(b, c)
    if eng.needs_flush:
        eng.flush(b)
    assert a.canonical() == b.canonical()
    assert both_sums(a) == both_sums(b)


def test_mesh_engine_state_is_sharded(kv_mesh):
    """The resident mirrors really are range-partitioned over "kv" (not
    silently replicated)."""
    src = gen_store(2, node=1, n_ops=400)
    b = KeySpace()
    eng = TpuMergeEngine(resident=True, mesh=kv_mesh)
    eng.merge(b, batch_from_keyspace(src))
    assert eng._res, "resident state missing"
    from jax.sharding import PartitionSpec
    for fam, res in eng._res.items():
        for name, plane in res["cols"].items():
            for arr in plane:                    # (hi, lo): each half
                spec = arr.sharding.spec
                assert spec and spec[0] == "kv", \
                    f"{fam}.{name} not kv-sharded: {arr.sharding}"
    eng.flush(b)


# ---------------------------------------------- aligned multi-batch fold

@pytest.fixture(scope="module")
def aligned_batches():
    import bench

    return bench.make_workload(600, 4, seed=11)


@pytest.mark.parametrize("mode", ["xla", "pallas-interpret"])
def test_aligned_fold_matches_cpu(aligned_batches, mode):
    """R aligned replica snapshots reduce on-device in one fused pass
    (Pallas on TPU / XLA dense elsewhere) then scatter once; the result
    must stay bit-identical to the CPU engine folding them one by one."""
    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for b in aligned_batches:
        cpu.merge(cpu_store, b)

    eng = TpuMergeEngine(dense_fold=mode)
    st = KeySpace()
    eng.merge_many(st, aligned_batches)
    assert eng.folds > 0, "aligned fold did not trigger"
    assert st.canonical() == cpu_store.canonical()
    assert both_sums(st) == both_sums(cpu_store)


@pytest.mark.parametrize("mode", ["xla", "pallas-interpret"])
def test_aligned_fold_onto_existing_state(aligned_batches, mode):
    """Folding onto a non-empty store: the single scatter must still merge
    correctly against resident prior state."""
    first, rest = aligned_batches[0], aligned_batches[1:]

    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for b in aligned_batches:
        cpu.merge(cpu_store, b)

    eng = TpuMergeEngine(resident=True, dense_fold=mode)
    st = KeySpace()
    eng.merge(st, first)
    eng.merge_many(st, rest)
    assert eng.folds > 0
    eng.flush(st)
    assert st.canonical() == cpu_store.canonical()


def test_fold_off_still_matches(aligned_batches):
    eng = TpuMergeEngine(dense_fold="off")
    st = KeySpace()
    eng.merge_many(st, aligned_batches)
    assert eng.folds == 0
    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for b in aligned_batches:
        cpu.merge(cpu_store, b)
    assert st.canonical() == cpu_store.canonical()


@pytest.mark.parametrize("mode", ["xla", "pallas-interpret"])
def test_aligned_counter_fold_matches_cpu(mode):
    """Aligned counter rows (same (key, node) slots in every batch —
    repeated syncs from one origin) fold via the fused pair kernel."""
    import bench

    batches = bench.make_workload(400, 1, seed=3)
    # same origin twice, second sync with advanced uuids/values
    b2 = bench.make_workload(400, 1, seed=4)[0]
    b2.cnt_node = batches[0].cnt_node
    many = [batches[0], b2]

    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for b in many:
        cpu.merge(cpu_store, b)

    eng = TpuMergeEngine(dense_fold=mode)
    st = KeySpace()
    eng.merge_many(st, many)
    assert eng.folds > 0
    assert st.canonical() == cpu_store.canonical()
    assert both_sums(st) == both_sums(cpu_store)


def _dict_none_batches():
    """Two aligned batches over one dict key: the lexicographic winner for
    member m carries value None (review regression: the winning None must
    CLEAR the stored value, exactly as the CPU engine does)."""
    import numpy as np

    def mk(add_t, val):
        b = batch_from_keyspace(KeySpace())  # empty scaffold
        b.rows_unique_per_slot = True
        b.keys = [b"d1"]
        b.key_enc = np.array([ENC_DICT], dtype=np.int8)
        b.key_ct = np.array([1 << 22], dtype=np.int64)
        b.key_mt = np.array([add_t], dtype=np.int64)
        b.key_dt = np.zeros(1, dtype=np.int64)
        b.key_expire = np.zeros(1, dtype=np.int64)
        b.reg_val = [None]
        b.reg_t = np.zeros(1, dtype=np.int64)
        b.reg_node = np.zeros(1, dtype=np.int64)
        b.el_ki = np.zeros(1, dtype=np.int64)
        b.el_member = [b"m"]
        b.el_val = [val]
        b.el_add_t = np.array([add_t], dtype=np.int64)
        b.el_add_node = np.array([1], dtype=np.int64)
        b.el_del_t = np.zeros(1, dtype=np.int64)
        return b

    lo = mk(100 << 22, b"y")
    hi = mk(200 << 22, None)   # the winner — and it carries None
    return lo, hi


@pytest.mark.parametrize("mode", ["off", "xla", "pallas-interpret"])
def test_winning_none_value_clears_dict_field(mode):
    lo, hi = _dict_none_batches()
    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    cpu.merge(cpu_store, lo)
    cpu.merge(cpu_store, hi)

    st = KeySpace()
    TpuMergeEngine(dense_fold=mode).merge_many(st, [lo, hi])
    assert st.canonical() == cpu_store.canonical()
    kid = st.lookup(b"d1")
    row = st.el_row(kid, b"m")
    assert st.el_val[row] is None


def test_non_pow2_mesh_engine():
    """State padding must round up to the kv axis size, not just pow2
    (review regression: a 6-device mesh crashed on the first merge)."""
    import jax

    if len(jax.devices()) < 6:
        pytest.skip("needs >= 6 virtual devices")
    from constdb_tpu.parallel import engine_mesh

    src = gen_store(5, node=1)
    st = KeySpace()
    eng = TpuMergeEngine(resident=True, mesh=engine_mesh(6))
    eng.merge(st, batch_from_keyspace(src))
    eng.flush(st)
    cpu_store = KeySpace()
    CpuMergeEngine().merge(cpu_store, batch_from_keyspace(src))
    assert st.canonical() == cpu_store.canonical()
