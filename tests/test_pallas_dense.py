"""Pallas fused dense merge kernels vs the XLA reference (ops/dense.py),
and the XLA programs of the resident micro path vs the host reference.

The Pallas kernels run through the interpreter on the CPU platform (same
kernel code path as TPU, minus the Mosaic compile), over adversarial
int64 data: NEUTRAL_T sentinels, negative values, 63-bit uuids, exact
ties.  On the chip (CONSTDB_TEST_TPU=1) Mosaic compiles them here as it
does for the engine.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from constdb_tpu.crdt.semantics import NEUTRAL_T
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.ops import dense as D
from constdb_tpu.ops import pallas_dense as PD


_INTERPRET = jax.default_backend() != "tpu"


def _cols(rng, R, S, ties=True):
    t = rng.integers(0, 1 << 62, (R, S)).astype(np.int64)
    t[rng.random((R, S)) < 0.25] = NEUTRAL_T
    if ties:
        # force exact ties between rows on a third of the slots
        cols = rng.random(S) < 0.33
        t[:, cols] = t[0, cols]
    return t


@pytest.mark.parametrize("seed,R,S", [(0, 2, 64), (1, 8, 512),
                                      (2, 9, 1000), (3, 16, 4096)])
def test_merge_elems_matches_xla(seed, R, S):
    rng = np.random.default_rng(seed)
    at = _cols(rng, R, S)
    an = rng.integers(0, 1 << 31, (R, S)).astype(np.int64)
    an[rng.random((R, S)) < 0.2] = NEUTRAL_T
    dt = np.where(rng.random((R, S)) < 0.5,
                  rng.integers(0, 1 << 62, (R, S)), 0).astype(np.int64)

    a1, n1, d1, w1 = (np.asarray(x) for x in D.dense_merge_elems(at, an, dt))
    a2, n2, d2, w2 = (np.asarray(x) for x in
                      PD.merge_elems(at, an, dt, interpret=_INTERPRET))
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(n1, n2)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(w1, w2)


@pytest.mark.parametrize("seed,R,S", [(0, 2, 64), (1, 8, 512), (2, 16, 3000)])
def test_merge_counters_matches_xla(seed, R, S):
    rng = np.random.default_rng(seed)
    ts = _cols(rng, R, S)
    vals = rng.integers(-(1 << 40), 1 << 40, (R, S)).astype(np.int64)
    # exact-uuid ties must resolve by max value on both paths
    v1, t1 = (np.asarray(x) for x in D.dense_merge_counters(vals, ts))
    v2, t2 = (np.asarray(x) for x in
              PD.merge_counters(vals, ts, interpret=_INTERPRET))
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(v1, v2)


def test_negative_and_extreme_values():
    """Full-range int64 round-trips through the hi/lo split correctly."""
    at = np.array([[NEUTRAL_T, -1, (1 << 62) - 1, 0],
                   [0, -2, (1 << 62) - 2, NEUTRAL_T]], dtype=np.int64)
    an = np.array([[1, 5, 2, NEUTRAL_T],
                   [2, 4, 3, NEUTRAL_T]], dtype=np.int64)
    dt = np.array([[0, 3, 0, 0], [5, 0, 0, 0]], dtype=np.int64)
    a1, n1, d1, w1 = (np.asarray(x) for x in D.dense_merge_elems(at, an, dt))
    a2, n2, d2, w2 = (np.asarray(x) for x in
                      PD.merge_elems(at, an, dt, interpret=_INTERPRET))
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(n1, n2)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(w1, w2)


@pytest.mark.parametrize("n", [2, 3, 8])
@pytest.mark.parametrize("strat", ["sum", "maxmag", "trimmed-mean"])
def test_tensor_reduce_matches_xla(strat, n):
    """The tensor strategy kernel vs its XLA twin on the SAME backend
    (tests/test_tensor_family.py pins both to the host chain in
    interpret mode; this is the leg Mosaic compiles on the chip)."""
    from constdb_tpu.crdt import tensor as T
    sid = T.STRATEGY_IDS[strat]
    rng = np.random.default_rng(sid * 10 + n)
    G, Kp = 5, 2 * PD.TENSOR_BLOCK
    mat = jnp.asarray((rng.standard_normal((G, n, Kp)) * 9)
                      .astype(np.float32))
    cnts = jnp.ones((G, n), jnp.float32)
    div = np.float32(n if n <= 2 else n - 2)
    xla = np.asarray(D.tensor_reduce(mat, cnts, div, strat=sid, n=n))
    pal = np.asarray(PD.tensor_reduce(
        mat, cnts, div, strat=sid, n=n, interpret=_INTERPRET))
    assert np.array_equal(xla.view(np.uint32), pal.view(np.uint32))


# ------------------------------------------------ resident micro programs
# The steady-state micro path's device programs — gather-compare-scatter
# over one LWW pair (ops/bulk.py bulk_lww_src) and the segment-sum counter
# re-derivation (ops/dense.py segment_sum) — vs the host reference, over
# the engine's exact padding protocol (pads land at >= sp and are dropped).

from constdb_tpu.engine.base import ColumnarBatch
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.ops import bulk as B
from constdb_tpu.ops.segment import next_pow2
from constdb_tpu.store import KeySpace


def _pad1(arr, n, fill):
    out = np.full(n, fill, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def _plane(a):
    """A host int64 column as the device holds it (ops/bulk.py Plane)."""
    return B.plane_split(jnp.array(np.asarray(a, np.int64)))


def _host(x):
    """A device plane (Plane or the int32 src array) back as numpy."""
    if isinstance(x, B.Plane):
        return np.asarray(B.plane_rows(x, n=x.shape[0]))
    return np.asarray(x)


def _scatter_xla(p, s, src, idx, bp, bs, base):
    """bulk_lww_src over a pow2-padded batch, as the engine pads it
    (`_batch_idx`): pad rows carry NEUTRAL values and target rows >= sp.
    Takes and returns the device planes (two Planes and the int32 src
    array, donated); the batch columns stay int64."""
    sp, n = p.shape[0], len(idx)
    np2 = next_pow2(n)
    idx_x = np.concatenate([idx, (sp + np.arange(np2 - n)).astype(np.int32)])
    return B.bulk_lww_src(
        p, s, src, jnp.array(idx_x), jnp.array(_pad1(bp, np2, NEUTRAL_T)),
        jnp.array(_pad1(bs, np2, NEUTRAL_T)), base)


def _host_scatter_ref(p, s, src, idx, bp, bs, base):
    """Per-row host reference: lexicographic (primary, secondary) win —
    exactly crdt/semantics.py lww_wins / hostbatch's fold rule."""
    p, s, src = p.copy(), s.copy(), src.copy()
    for j, r in enumerate(idx.tolist()):
        win = (bp[j] > p[r]) or (bp[j] == p[r] and bs[j] > s[r])
        if win:
            p[r], s[r], src[r] = bp[j], bs[j], base + j
    return p, s, src


def _scatter_case(rng, sp):
    n = int(rng.integers(1, sp + 1))
    idx = np.sort(rng.choice(sp, n, replace=False)).astype(np.int32)
    p = rng.integers(-9, 9, sp).astype(np.int64)
    s = rng.integers(-9, 9, sp).astype(np.int64)
    p[rng.random(sp) < 0.2] = NEUTRAL_T
    src = np.where(rng.random(sp) < 0.5, -1,
                   rng.integers(0, 50, sp)).astype(np.int32)
    bp = rng.integers(-9, 9, n).astype(np.int64)
    bs = rng.integers(-9, 9, n).astype(np.int64)
    # equal-stamp ties (local must keep) and full-pair ties
    for j in range(n):
        if rng.random() < 0.3:
            bp[j] = p[idx[j]]
        if rng.random() < 0.3:
            bs[j] = s[idx[j]]
    return p, s, src, idx, bp, bs, int(rng.integers(0, 1000))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scatter_pair_xla_twin_matches_host(seed):
    """The resident scatter (ops/bulk.py bulk_lww_src) vs the per-row
    host reference, randomized over plane sizes, batch sizes and ties."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        sp = int(2 ** rng.integers(0, 7))
        p, s, src, idx, bp, bs, base = _scatter_case(rng, sp)
        got = _scatter_xla(_plane(p), _plane(s), jnp.array(src),
                           idx, bp, bs, base)
        want = _host_scatter_ref(p, s, src, idx, bp, bs, base)
        for g, w, name in zip(got, want, ("primary", "secondary", "src")):
            np.testing.assert_array_equal(_host(g), w, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_scatter_chained_rounds(seed):
    """Several rounds over the SAME donated planes, full-range int64
    magnitudes — bit-identical to the per-round host reference."""
    rng = np.random.default_rng(seed)
    sp = 32
    want = (rng.integers(-(1 << 60), 1 << 60, sp).astype(np.int64),
            rng.integers(-(1 << 40), 1 << 40, sp).astype(np.int64),
            np.full(sp, -1, np.int32))
    got = (_plane(want[0]), _plane(want[1]), jnp.array(want[2]))
    base = 0
    for _ in range(5):
        n = int(rng.integers(1, sp))
        idx = np.sort(rng.choice(sp, n, replace=False)).astype(np.int32)
        bp = rng.integers(-(1 << 60), 1 << 60, n).astype(np.int64)
        bs = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
        got = _scatter_xla(*got, idx, bp, bs, base)
        want = _host_scatter_ref(*want, idx, bp, bs, base)
        base += next_pow2(n)
    for g, w, name in zip(got, want, ("primary", "secondary", "src")):
        np.testing.assert_array_equal(_host(g), w, err_msg=name)


def _reg_batch(keys, u0):
    b = ColumnarBatch()
    n = len(keys)
    b.keys = list(keys)
    uu = (np.arange(n, dtype=np.int64) + u0) << 22
    b.key_enc = np.full(n, 3, np.int8)  # ENC_BYTES
    b.key_ct = uu.copy()
    b.key_mt = uu.copy()
    b.key_dt = np.zeros(n, np.int64)
    b.key_expire = np.zeros(n, np.int64)
    b.reg_val = [b"v%d" % (u0 + i) for i in range(n)]
    b.reg_t = uu
    b.reg_node = np.full(n, 1, np.int64)
    b.rows_unique_per_slot = False
    return b


@pytest.mark.parametrize("n_keys, n_touch, np2", [
    (5, 3, TpuMergeEngine.MICRO_SCATTER_PAD),    # nw < the pad floor
    (300, 300, 512),                             # nw > it: pow2(nw)
    (4, 4, 4)])                                  # nw == sp: pads to itself
def test_padded_micro_batch_equals_host(monkeypatch, n_keys, n_touch, np2):
    """The engine's padded scatter batch in each regime of its length
    rule: pads target rows >= sp, the scatter drops them, and the merged
    planes equal the host engine's."""
    seen = []
    real = B.bulk_lww_win

    def spy(t, n, blk):
        seen.append((t.shape[0], np.asarray(blk)[0]))
        return real(t, n, blk)

    monkeypatch.setattr(B, "bulk_lww_win", spy)
    keys = [b"r%03d" % i for i in range(n_keys)]
    ref, dev = KeySpace(), KeySpace()
    cpu = CpuMergeEngine()
    eng = TpuMergeEngine(resident=True, steady=True, warmup=0)
    for ks, e in ((ref, cpu), (dev, eng)):
        e.merge_many(ks, [_reg_batch(keys, 100)])
        e.merge_many(ks, [_reg_batch(keys[:n_touch], 900)])
    sp, idx = seen[-1]
    assert sp == next_pow2(n_keys) and len(idx) == np2
    assert (idx[:n_touch] < sp).all() and (idx[n_touch:] >= sp).all()
    eng.flush(dev)
    assert dev.canonical() == ref.canonical()
    eng.close()


@pytest.mark.parametrize("seed,n,n_seg", [(0, 1, 1), (1, 33, 7),
                                          (2, 257, 64), (3, 1000, 100)])
def test_segment_sum_matches_host(seed, n, n_seg):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_seg, n).astype(np.int32)
    # full-range magnitudes force carries out of the low 32-bit word
    # (the chip emulates int64 as a 32-bit pair)
    vals = rng.integers(-(1 << 61), 1 << 61, n).astype(np.int64)
    got = np.asarray(D.segment_sum(jnp.array(ids), jnp.array(vals),
                                   n_seg=n_seg))
    want = np.zeros(n_seg, dtype=np.int64)
    np.add.at(want, ids, vals)
    np.testing.assert_array_equal(got, want)


def test_segment_sum_carry_boundary():
    """Sums crossing the uint32 boundary carry into the high word."""
    ids = np.zeros(8, dtype=np.int32)
    vals = np.full(8, (1 << 32) - 1, dtype=np.int64)
    got = np.asarray(D.segment_sum(jnp.array(ids), jnp.array(vals),
                                   n_seg=3))
    assert got.tolist() == [8 * ((1 << 32) - 1), 0, 0]
    # negative totals keep their sign across the word boundary
    vals = np.array([-(1 << 40), 1, -(1 << 33), 5], dtype=np.int64)
    ids = np.array([0, 1, 0, 1], dtype=np.int32)
    got = np.asarray(D.segment_sum(jnp.array(ids), jnp.array(vals),
                                   n_seg=2))
    assert got.tolist() == [-(1 << 40) - (1 << 33), 6]


def _cnt_batch(totals, u0, unique):
    b = ColumnarBatch()
    n = len(totals)
    b.keys = [b"c%02d" % i for i in range(n)]
    uu = (np.arange(n, dtype=np.int64) + u0) << 22
    b.key_enc = np.zeros(n, np.int8)  # ENC_COUNTER
    b.key_ct = uu.copy()
    b.key_mt = uu.copy()
    b.key_dt = np.zeros(n, np.int64)
    b.key_expire = np.zeros(n, np.int64)
    b.reg_val = [None] * n
    b.reg_t = np.zeros(n, np.int64)
    b.reg_node = np.zeros(n, np.int64)
    b.cnt_ki = np.arange(n, dtype=np.int64)
    b.cnt_node = np.full(n, 7, np.int64)
    b.cnt_val = np.asarray(totals, dtype=np.int64)
    b.cnt_uuid = uu
    b.cnt_base = np.zeros(n, np.int64)
    b.cnt_base_t = np.full(n, NEUTRAL_T, np.int64)
    b.rows_unique_per_slot = unique
    return b


def test_recompute_sums_on_device_reads_the_micro_rounds(monkeypatch):
    """A bulk counter catch-up (whole-plane cnt mirror, dirty=None)
    followed by steady micro rounds: the flush-time segment-sum on the
    device (the rule for every backend but CPU, so the backend is named
    here) re-derives cnt_sum from the planes the micro rounds wrote."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = []
    real = D.segment_sum
    monkeypatch.setattr(
        D, "segment_sum",
        lambda *a, **kw: calls.append(kw["n_seg"]) or real(*a, **kw))
    ref, dev = KeySpace(), KeySpace()
    cpu = CpuMergeEngine()
    eng = TpuMergeEngine(resident=True, steady=True, warmup=0,
                         dense_fold="xla")
    # bulk catch-up: whole-plane cnt mirror (dirty=None)
    b1, b2 = (_cnt_batch([100, 101, 102, 103], 10, True) for _ in range(2))
    cpu.merge_many(ref, [b1])
    eng.merge_many(dev, [b2])
    for r in range(3):
        t = [200 + 10 * r + i for i in range(4)]
        m1, m2 = (_cnt_batch(t, 50 + 10 * r, False) for _ in range(2))
        cpu.merge_many(ref, [m1])
        eng.merge_many(dev, [m2])
    eng.flush(dev)
    assert calls == [4]
    np.testing.assert_array_equal(dev.keys.cnt_sum[:4], ref.keys.cnt_sum[:4])
    assert dev.canonical() == ref.canonical()
    eng.close()


# ------------------------------------------------------- the kernel choice


@pytest.mark.parametrize("dense_fold, backend, mesh, want", [
    ("auto", "cpu", False, "xla"),
    ("auto", "tpu", False, "pallas"),
    ("auto", "tpu", True, "xla"),       # pallas_call inside GSPMD: not yet
    ("auto", "gpu", False, "xla"),
    ("off", "tpu", False, "xla"),
    ("xla", "tpu", False, "xla"),
    ("pallas", "cpu", False, "pallas"),                  # forced: as given
    ("pallas-interpret", "tpu", True, "pallas-interpret")])
def test_kernel_backend_table(monkeypatch, dense_fold, backend, mesh, want):
    """What the fold and tensor-reduce call sites run, by what the engine
    observes: a forced dense_fold as given; otherwise Mosaic on a TPU
    backend without a mesh, XLA everywhere else."""
    eng = TpuMergeEngine(resident=True, dense_fold=dense_fold)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if mesh:
        eng._mesh = object()
    assert eng._kernel_backend() == want
    ran = eng._pallas_or_xla(lambda interp: ("pallas", interp),
                             lambda: ("xla", None))
    assert ran == {"xla": ("xla", None), "pallas": ("pallas", False),
                   "pallas-interpret": ("pallas", True)}[want]
