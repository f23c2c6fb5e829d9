"""Pallas fused dense merge kernels vs the XLA reference (ops/dense.py).

Runs through the Pallas interpreter on the CPU platform (same kernel code
path as TPU, minus the Mosaic compile), over adversarial int64 data:
NEUTRAL_T sentinels, negative values, 63-bit uuids, exact ties.  On the
chip (CONSTDB_TEST_TPU=1) every kernel the engine runs compiled there
(TpuMergeEngine.AUTO_TPU_KERNELS == "pallas") is compiled by Mosaic here
too; the kernels whose XLA twin the engine selects stay interpreted.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from constdb_tpu.crdt.semantics import NEUTRAL_T
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.ops import dense as D
from constdb_tpu.ops import pallas_dense as PD


def _interpret(kernel: str) -> bool:
    return jax.default_backend() != "tpu" or \
        TpuMergeEngine.AUTO_TPU_KERNELS[kernel] != "pallas"


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
                      PD.merge_elems(at, an, dt,
                                     interpret=_interpret("merge_elems")))
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
              PD.merge_counters(vals, ts,
                                interpret=_interpret("merge_counters")))
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
                      PD.merge_elems(at, an, dt,
                                     interpret=_interpret("merge_elems")))
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
        mat, cnts, div, strat=sid, n=n,
        interpret=_interpret("tensor_reduce")))
    assert np.array_equal(xla.view(np.uint32), pal.view(np.uint32))


# -------------------------------------------------- resident scatter kernels
# The steady-state micro-path kernels (gather-compare-scatter over one LWW
# pair + the segment-sum counter re-derivation) vs their XLA twins
# (ops/bulk.py bulk_lww_src / ops/dense.py segment_sum) and the host
# reference, over the engine's exact padding protocol.

from constdb_tpu.ops import bulk as B


def _pad1(arr, n, fill):
    out = np.full(n, fill, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def _scatter_both(p, s, src, idx, bp, bs, base):
    """Run the Pallas scatter (engine padding protocol: pads target a
    free row with NEUTRAL values) and the XLA twin (pads out of range)
    on copies; -> ((p, s, src) pallas, (p, s, src) xla)."""
    sp, n = len(p), len(idx)
    np2 = PD._pow2(max(n, 1))
    pad_row = TpuMergeEngine._scatter_pad_row(idx.astype(np.int64), n, sp) \
        if np2 > n else 0
    pl_out = PD.scatter_pair_src(
        jnp.array(p), jnp.array(s), jnp.array(src),
        jnp.array(_pad1(idx, np2, pad_row)),
        jnp.array(_pad1(bp, np2, NEUTRAL_T)),
        jnp.array(_pad1(bs, np2, NEUTRAL_T)),
        np.int32(base), interpret=_interpret("scatter_pair_src_split"))
    idx_x = np.concatenate([idx, (sp + np.arange(np2 - n)).astype(np.int32)])
    xla_out = B.bulk_lww_src(
        jnp.array(p), jnp.array(s), jnp.array(src), jnp.array(idx_x),
        jnp.array(_pad1(bp, np2, NEUTRAL_T)),
        jnp.array(_pad1(bs, np2, NEUTRAL_T)), base)
    return tuple(np.asarray(x) for x in pl_out), \
        tuple(np.asarray(x) for x in xla_out)


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
    """The XLA resident-scatter twin (ops/bulk.py bulk_lww_src) vs the
    per-row host reference, randomized — cheap enough for tier-1 at full
    shape coverage (XLA traces are ~ms; the Pallas interpreter pays ~1s
    PER SHAPE to trace, so its randomized twin runs in the slow suite
    and tier-1 keeps the small fixed-shape Pallas cases below)."""
    from constdb_tpu.ops import bulk as B
    rng = np.random.default_rng(seed)
    for _ in range(25):
        sp = int(2 ** rng.integers(0, 7))
        p, s, src, idx, bp, bs, base = _scatter_case(rng, sp)
        n = len(idx)
        np2 = PD._pow2(n)
        idx_x = np.concatenate([idx,
                                (sp + np.arange(np2 - n)).astype(np.int32)])
        got = tuple(np.asarray(x) for x in B.bulk_lww_src(
            jnp.array(p), jnp.array(s), jnp.array(src), jnp.array(idx_x),
            jnp.array(_pad1(bp, np2, NEUTRAL_T)),
            jnp.array(_pad1(bs, np2, NEUTRAL_T)), base))
        want = _host_scatter_ref(p, s, src, idx, bp, bs, base)
        for g, w, name in zip(got, want, ("primary", "secondary", "src")):
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scatter_pair_src_matches_xla_and_host(seed):
    rng = np.random.default_rng(seed)
    for _ in range(16):
        sp = int(2 ** rng.integers(0, 7))
        p, s, src, idx, bp, bs, base = _scatter_case(rng, sp)
        got_pl, got_xla = _scatter_both(p, s, src, idx, bp, bs, base)
        want = _host_scatter_ref(p, s, src, idx, bp, bs, base)
        for g, x, w, name in zip(got_pl, got_xla, want,
                                 ("primary", "secondary", "src")):
            np.testing.assert_array_equal(x, w, err_msg=f"xla {name}")
            np.testing.assert_array_equal(g, w, err_msg=f"pallas {name}")


def test_scatter_pad_collision_would_revert():
    """The pad-targeting contract (ops/pallas_dense.py): a pad aliased
    onto a REAL row's target reads pre-merge state and reverts the
    merge.  _scatter_pad_row must therefore pick a row outside the
    batch — pinned both ways."""
    sp = 8
    p = np.zeros(sp, dtype=np.int64)
    s = np.zeros(sp, dtype=np.int64)
    src = np.full(sp, -1, np.int32)
    idx = np.array([0], dtype=np.int32)       # one real row, wins slot 0
    bp = np.array([5], dtype=np.int64)
    bs = np.array([1], dtype=np.int64)
    # engine helper picks a free row — result must match the reference
    assert TpuMergeEngine._scatter_pad_row(idx.astype(np.int64), 1, sp) == 1
    got_pl, got_xla = _scatter_both(p, s, src, idx, bp, bs, 7)
    want = _host_scatter_ref(p, s, src, idx, bp, bs, 7)
    for g, x, w in zip(got_pl, got_xla, want):
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(x, w)


def test_scatter_pad_row_finds_interior_gap():
    rows = np.array([0, 1, 3, 4, 6, 7], dtype=np.int64)  # 2 and 5 absent
    assert TpuMergeEngine._scatter_pad_row(rows, len(rows), 8) == 2
    rows = np.array([1, 2, 3], dtype=np.int64)
    assert TpuMergeEngine._scatter_pad_row(rows, len(rows), 4) == 0
    rows = np.array([0, 1, 2], dtype=np.int64)
    assert TpuMergeEngine._scatter_pad_row(rows, len(rows), 8) == 3


@pytest.mark.parametrize("seed,n,n_seg", [(0, 1, 1), (1, 33, 7),
                                          (2, 257, 64), (3, 1000, 100)])
def test_segment_sum_matches_xla_and_host(seed, n, n_seg):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_seg, n).astype(np.int32)
    # full-range magnitudes force the unsigned lo-word carry chains
    vals = rng.integers(-(1 << 61), 1 << 61, n).astype(np.int64)
    got = np.asarray(PD.segment_sum(jnp.array(ids), jnp.array(vals),
                                    n_seg=n_seg,
                                    interpret=_interpret("segment_sum")))
    xla = np.asarray(D.segment_sum(jnp.array(ids), jnp.array(vals),
                                   n_seg=n_seg))
    want = np.zeros(n_seg, dtype=np.int64)
    np.add.at(want, ids, vals)
    np.testing.assert_array_equal(xla, want)
    np.testing.assert_array_equal(got, want)


def test_segment_sum_carry_boundary():
    """Sums crossing the uint32 boundary exercise the explicit carry."""
    ids = np.zeros(8, dtype=np.int32)
    vals = np.full(8, (1 << 32) - 1, dtype=np.int64)
    got = np.asarray(PD.segment_sum(jnp.array(ids), jnp.array(vals),
                                    n_seg=3,
                                    interpret=_interpret("segment_sum")))
    assert got.tolist() == [8 * ((1 << 32) - 1), 0, 0]
    # negative totals round-trip the split sign correctly
    vals = np.array([-(1 << 40), 1, -(1 << 33), 5], dtype=np.int64)
    ids = np.array([0, 1, 0, 1], dtype=np.int32)
    got = np.asarray(PD.segment_sum(jnp.array(ids), jnp.array(vals),
                                    n_seg=2,
                                    interpret=_interpret("segment_sum")))
    assert got.tolist() == [-(1 << 40) - (1 << 33), 6]


def test_segment_sum_scratch_cap():
    with pytest.raises(ValueError):
        PD.segment_sum(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int64),
                       n_seg=PD.SEGMENT_SUM_MAX_SEG + 1,
                       interpret=_interpret("segment_sum"))

# ---------------------------------------------------- pre-split planes
# The retired PR 8 follow-up: LWW pair planes live PRE-SPLIT as hi/lo
# 32-bit pairs between micro rounds (scatter_pair_src_split), so the
# steady path pays no O(plane) int64<->hi/lo pass per call.  The int64
# wrapper (scatter_pair_src) — which every test above still drives —
# splits/joins around the SAME kernel, so the pad-collision and
# randomized differentials pin the split kernel too; the cases below
# additionally pin the CHAINED form (state stays split across rounds)
# and the engine's split-cache lifecycle.


@pytest.mark.parametrize("seed", [0, 1])
def test_scatter_split_chained_rounds(seed):
    """Several rounds over the SAME planes with the state kept in split
    form throughout (joined only at the end) — bit-identical to the
    per-round host reference and to the int64 XLA twin chain."""
    rng = np.random.default_rng(seed)
    sp = 32
    p = rng.integers(-(1 << 60), 1 << 60, sp).astype(np.int64)
    s = rng.integers(-(1 << 40), 1 << 40, sp).astype(np.int64)
    src = np.full(sp, -1, np.int32)
    p_hi, p_lo = PD.split_plane(jnp.array(p))
    s_hi, s_lo = PD.split_plane(jnp.array(s))
    src_d = jnp.array(src)
    want_p, want_s, want_src = p.copy(), s.copy(), src.copy()
    base = 0
    for _ in range(5):
        n = int(rng.integers(1, sp))
        idx = np.sort(rng.choice(sp, n, replace=False)).astype(np.int32)
        bp = rng.integers(-(1 << 60), 1 << 60, n).astype(np.int64)
        bs = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
        np2 = PD._pow2(n)
        pad = TpuMergeEngine._scatter_pad_row(idx.astype(np.int64), n, sp) \
            if np2 > n else 0
        p_hi, p_lo, s_hi, s_lo, src_d = PD.scatter_pair_src_split(
            p_hi, p_lo, s_hi, s_lo, src_d,
            jnp.array(_pad1(idx, np2, pad)),
            jnp.array(_pad1(bp, np2, NEUTRAL_T)),
            jnp.array(_pad1(bs, np2, NEUTRAL_T)),
            np.int32(base), interpret=True)
        want_p, want_s, want_src = _host_scatter_ref(
            want_p, want_s, want_src, idx, bp, bs, base)
        base += np2
    np.testing.assert_array_equal(
        np.asarray(PD.join_plane(p_hi, p_lo)), want_p)
    np.testing.assert_array_equal(
        np.asarray(PD.join_plane(s_hi, s_lo)), want_s)
    np.testing.assert_array_equal(np.asarray(src_d), want_src)


def test_engine_split_cache_steady_state():
    """The engine keeps pair planes split BETWEEN micro rounds under a
    Pallas backend (res['split'] populated, int64 cols stale-by-design)
    and still flushes/reads exactly the host-engine results."""
    from constdb_tpu.engine.base import ColumnarBatch
    from constdb_tpu.engine.cpu import CpuMergeEngine
    from constdb_tpu.store import KeySpace

    rng = np.random.default_rng(5)

    def batch(u0):
        b = ColumnarBatch()
        n = 12
        b.keys = [b"r%02d" % rng.integers(6) for _ in range(n)]
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

    ref = KeySpace()
    cpu = CpuMergeEngine()
    dev = KeySpace()
    eng = TpuMergeEngine(resident=True, steady=True, warmup=0,
                         dense_fold="pallas-interpret")
    for r in range(4):
        b1, b2 = batch(100 + 20 * r), batch(100 + 20 * r)
        b2.keys = list(b1.keys)
        b2.reg_val = list(b1.reg_val)
        cpu.merge_many(ref, [b1])
        eng.merge_many(dev, [b2])
        if r:
            res = eng._res.get("reg")
            assert res is not None and res.get("split"), \
                "pair planes not kept split between micro rounds"
    eng.flush(dev)
    assert dev.canonical() == ref.canonical()
    eng.close()


def test_recompute_sums_joins_split_cache():
    """A bulk counter catch-up (whole-plane cnt mirror, dirty=None)
    followed by steady micro rounds leaves the val/uuid truth in the
    split cache; the flush-time device segment-sum must JOIN it before
    re-deriving cnt_sum, or counters serve pre-merge totals (found by
    review: canonical() matched while cnt_sum was stale)."""
    from constdb_tpu.engine.base import ColumnarBatch
    from constdb_tpu.engine.cpu import CpuMergeEngine
    from constdb_tpu.store import KeySpace

    def cnt_batch(totals, u0, unique):
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

    ref = KeySpace()
    cpu = CpuMergeEngine()
    dev = KeySpace()
    # the production shape is dense_fold="auto" RESOLVING to pallas (a
    # real TPU backend): host-combine staging stays on (env rides host
    # mode — no env mirror, so nothing flushes between the bulk round
    # and the micro rounds) while the scatter kernels run Pallas.  On
    # the CPU backend auto resolves to xla, so pin the resolution.
    eng = TpuMergeEngine(resident=True, steady=True, warmup=0,
                         dense_fold="auto")
    eng._kernel_backend = lambda kernel: "pallas-interpret"
    # bulk catch-up: whole-plane cnt mirror (dirty=None)
    b1, b2 = (cnt_batch([100, 101, 102, 103], 10, True) for _ in range(2))
    cpu.merge_many(ref, [b1])
    eng.merge_many(dev, [b2])
    # steady micro rounds: winners land in the split pair cache
    for r in range(3):
        t = [200 + 10 * r + i for i in range(4)]
        m1, m2 = (cnt_batch(t, 50 + 10 * r, False) for _ in range(2))
        cpu.merge_many(ref, [m1])
        eng.merge_many(dev, [m2])
    eng.flush(dev)
    np.testing.assert_array_equal(dev.keys.cnt_sum[:4], ref.keys.cnt_sum[:4])
    assert dev.canonical() == ref.canonical()
    eng.close()
