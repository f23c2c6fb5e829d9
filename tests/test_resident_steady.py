"""Device-resident steady-state merges (engine/tpu.py micro path).

The load-bearing claims of the round-12 routing inversion, each pinned:
  * op-stream micro-batches merged IN PLACE against resident device
    planes are byte-identical to the host engines — canonical export
    differentials for the coalesced replication stream and for mixed
    snapshot-ingest + stream traffic, and a fixed-HLC lockstep serving
    differential (reply streams, canonical export, repl_log) — on BOTH
    kernel backends (XLA twins and pallas-interpret);
  * flushes are PARTIAL: `flush_rows_downloaded` stays strictly below
    the whole-plane equivalent while `dev_rounds_resident` > 0;
  * consecutive coalescable stream batches merge with NO flush between
    them (env stays host-authoritative; `Node.ensure_flushed_for`
    narrows the finalize barrier);
  * the warm-streak gate routes cold planes to the host fallback and
    engages after `CONSTDB_RESIDENT_WARMUP` stable rounds;
  * `CONSTDB_RESIDENT=0` (and steady=False) pin the pre-round-12 host
    micro routing exactly;
  * `host_stale` reports exactly the families holding unflushed device
    state — unapplied win vectors included;
  * a micro round crosses the link once each way (one block up, its win
    vector down) and its flush launches nothing: rounds on the same rows
    before one flush, either order of bulk and micro rounds, and the
    stage-clock protocol test.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")  # noqa: F841

from constdb_tpu.crdt import semantics as S
from constdb_tpu.engine.base import ColumnarBatch
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.server.node import Node
from constdb_tpu.store.keyspace import KeySpace
from constdb_tpu.utils.hlc import SEQ_BITS

from test_coalesce_apply import drive, frame, mixed_stream, u

BACKENDS = ("auto", "pallas-interpret")


def steady_engine(fold="auto", warmup=0, **kw):
    # steady FORCED: the auto default engages only over a real
    # accelerator backend, and these differentials run on CPU builders
    kw.setdefault("steady", True)
    return TpuMergeEngine(resident=True, dense_fold=fold, warmup=warmup,
                          **kw)


def coalescable_stream(n, seed=21, keys=60):
    """Encodable-only frames (no barriers): the regime where the steady
    path should ride with zero flushes between batches."""
    import random
    rng = random.Random(seed)
    frames = []
    prev = 0
    for i in range(1, n + 1):
        r = rng.random()
        k = b"k%03d" % rng.randrange(keys)
        if r < 0.3:
            f = (b"set", b"r" + k, b"v%d" % i)
        elif r < 0.55:
            f = (b"cntset", b"c" + k, rng.randrange(-50, 50))
        elif r < 0.75:
            f = (b"sadd", b"s" + k, b"m%d" % rng.randrange(10))
        elif r < 0.9:
            f = (b"hset", b"h" + k, b"f%d" % rng.randrange(6), b"v%d" % i)
        else:
            f = (b"srem", b"s" + k, b"m%d" % rng.randrange(10))
        frames.append(frame(prev, u(i), *f))
        prev = u(i)
    return frames, prev


# ---------------------------------------------------------- differentials


def _stream_differential(fold, n_frames, keys, max_frames):
    """Coalesced replication apply on the resident micro path equals the
    per-frame CPU reference byte for byte — including tombstones,
    counter deletes, and the GC queue — with resident rounds proven and
    downloads proven partial."""
    frames, last = mixed_stream(n_frames, seed=5, keys=keys)
    eng = steady_engine(fold)
    n1 = Node(node_id=1, engine=eng)
    n2 = Node(node_id=2)
    drive(n1, frames, max_frames=max_frames)
    drive(n2, frames, max_frames=1)
    n1.ensure_flushed()
    assert n1.canonical() == n2.canonical()
    assert eng.dev_rounds_resident > 0
    # partial, not whole-plane, downloads (the acceptance criterion)
    assert 0 < eng.flush_rows_downloaded < eng.flush_rows_full_equiv
    # GC parity under the same horizon
    horizon = last + (1 << SEQ_BITS)
    assert n1.ks.gc(horizon) == n2.ks.gc(horizon)
    assert n1.canonical() == n2.canonical()


def test_stream_differential_compact():
    """Tier-1 variant: small mixed stream, XLA backend — every barrier
    class still present, so flush-after-every-DEL interleavings stay
    covered (the wide both-backend run is the slow twin; the barrier
    flushes dominate its wall through per-shape jit traces)."""
    _stream_differential("auto", 250, 40, 64)


@pytest.mark.slow
@pytest.mark.parametrize("fold", BACKENDS)
def test_stream_differential_wide(fold):
    _stream_differential(fold, 1500, 80, 64)


@pytest.mark.parametrize(
    "fold", ("auto",
             # interpret-mode tracing rides the tier-1 budget line on the
             # burstable builder; the slow suite + the ci.sh resident
             # smoke keep the pallas-interpret leg covered
             pytest.param("pallas-interpret", marks=pytest.mark.slow)))
def test_snapshot_ingest_then_stream(fold):
    """Bulk catch-up (unique batches, whole-plane dirty) followed by
    steady-state micro rounds on the SAME engine: the dirty=None planes
    flush wholesale, later micro rounds flush their dirty rows, and the
    result equals the CPU reference — including counter sums re-derived
    through the segment-sum path under pallas-interpret."""
    from constdb_tpu.engine.base import ColumnarBatch

    n_keys = 400
    b = ColumnarBatch()
    b.keys = [b"c%05d" % i for i in range(n_keys)]
    from constdb_tpu.crdt import semantics as S
    b.key_enc = np.full(n_keys, S.ENC_COUNTER, dtype=np.int8)
    b.key_ct = np.full(n_keys, u(1), dtype=np.int64)
    b.key_mt = np.full(n_keys, u(1), dtype=np.int64)
    b.key_dt = np.zeros(n_keys, dtype=np.int64)
    b.key_expire = np.zeros(n_keys, dtype=np.int64)
    b.reg_val = [None] * n_keys
    b.reg_t = np.zeros(n_keys, dtype=np.int64)
    b.reg_node = np.zeros(n_keys, dtype=np.int64)
    b.cnt_ki = np.arange(n_keys, dtype=np.int64)
    b.cnt_node = np.full(n_keys, 9, dtype=np.int64)
    b.cnt_val = np.arange(n_keys, dtype=np.int64) - 50
    b.cnt_uuid = np.full(n_keys, u(1), dtype=np.int64)
    b.cnt_base = np.zeros(n_keys, dtype=np.int64)
    b.cnt_base_t = np.full(n_keys, S.NEUTRAL_T, dtype=np.int64)
    b.rows_unique_per_slot = True

    frames, _ = coalescable_stream(600, seed=8)
    eng = steady_engine(fold)
    n1 = Node(node_id=1, engine=eng)
    n2 = Node(node_id=2)
    for n in (n1, n2):
        n.merge_batch(b)
        drive(n, frames, max_frames=48)
        n.ensure_flushed()
    assert n1.canonical() == n2.canonical()
    assert eng.dev_rounds_resident > 0


@pytest.mark.parametrize("fold", BACKENDS)
def test_serve_lockstep_differential(tmp_path, fold):
    """Fixed-HLC lockstep serving: a coalescing node on the resident
    micro path produces byte-identical reply streams, canonical export,
    and repl_log vs the CPU-engine coalescing node."""
    from test_serve_coalesce import drive_node, mixed_workload

    work = mixed_workload(n_conns=2, rounds=10)
    eng = steady_engine(fold)

    async def main():
        got = await drive_node(tmp_path / "dev", 64, work, engine=eng)
        want = await drive_node(tmp_path / "cpu", 64, work)
        return got, want

    (g_raw, g_canon, g_repl, g_st), (w_raw, w_canon, w_repl, w_st) = \
        asyncio.run(main())
    for ci, (g, w) in enumerate(zip(g_raw, w_raw)):
        assert g == w, f"conn {ci} reply stream diverged"
    assert g_canon == w_canon
    assert g_repl == w_repl
    assert g_st.serve_msgs_coalesced == w_st.serve_msgs_coalesced
    assert eng.dev_rounds_resident > 0
    assert eng.flush_rows_downloaded < eng.flush_rows_full_equiv


# ------------------------------------------------------- routing behavior


def test_no_flush_between_coalescable_batches():
    """Pure-coalescable stream: batches merge in place round after round
    with exactly ONE flush at the end (the explicit ensure_flushed) —
    the narrowed finalize barrier never forces a round-trip."""
    frames, _ = coalescable_stream(800)
    eng = steady_engine()
    flushes = []
    real_flush = eng.flush

    def counting_flush(store):
        if eng.needs_flush:
            flushes.append(True)
        real_flush(store)

    eng.flush = counting_flush
    n1 = Node(node_id=1, engine=eng)
    drive(n1, frames, max_frames=64)
    assert eng.dev_rounds_resident >= 10
    assert not flushes  # nothing flushed during the whole stream
    n1.ensure_flushed()
    assert len(flushes) == 1
    n2 = Node(node_id=2)
    drive(n2, frames, max_frames=1)
    assert n1.canonical() == n2.canonical()


def test_warmup_gate_engages_after_stable_rounds():
    frames, _ = coalescable_stream(600)
    eng = steady_engine(warmup=2)
    n1 = Node(node_id=1, engine=eng)
    drive(n1, frames, max_frames=32)
    # the first `warmup` rounds route to the host fallback, the rest ride
    assert eng.host_micro_rounds == 2
    assert eng.dev_rounds_resident > 0
    n2 = Node(node_id=2)
    drive(n2, frames, max_frames=1)
    n1.ensure_flushed()
    assert n1.canonical() == n2.canonical()


def test_resident_env_pin(monkeypatch):
    """CONSTDB_RESIDENT=0 pins the exact pre-round-12 host micro routing
    (steady=False equivalently) — and `auto` resolves OFF on a
    CPU-only backend (the healthy-device clause), ON over the chip
    (CONSTDB_TEST_TPU=1) and ON when forced."""
    import jax
    assert TpuMergeEngine(resident=True).steady is \
        (jax.default_backend() != "cpu")  # auto
    monkeypatch.setenv("CONSTDB_RESIDENT", "1")
    assert TpuMergeEngine(resident=True).steady is True
    monkeypatch.setenv("CONSTDB_RESIDENT", "0")
    eng = TpuMergeEngine(resident=True)
    assert eng.steady is False
    frames, _ = coalescable_stream(300)
    n1 = Node(node_id=1, engine=eng)
    drive(n1, frames, max_frames=32)
    assert eng.dev_rounds_resident == 0
    assert eng.host_micro_rounds > 0
    assert not eng.needs_flush  # host path leaves nothing on device
    n2 = Node(node_id=2)
    drive(n2, frames, max_frames=1)
    assert n1.canonical() == n2.canonical()


def test_host_stale_reports_touched_families():
    """host_stale narrows exactly to families with unflushed device
    state; env stays host-authoritative so dt reads never flush."""
    frames, _ = coalescable_stream(200)
    eng = steady_engine()
    n1 = Node(node_id=1, engine=eng)
    drive(n1, frames, max_frames=64)
    assert eng.needs_flush
    assert not eng.host_stale(("env",))
    assert eng.host_stale(("reg", "cnt", "el"))
    n1.ensure_flushed()
    assert not eng.host_stale(("reg", "cnt", "el"))


@pytest.mark.parametrize("fold", ("xla", "pallas-interpret"))
def test_micro_delete_survives_forced_fold_bulk_round(fold):
    """Review-round regression: a micro-round element DELETE advances
    host del_t; the device mirror's del_t must advance in lockstep, or a
    later FORCED-dense_fold bulk round (whose kernels read and
    re-download del_t) merges against the stale plane and resurrects the
    deleted member at flush."""
    from constdb_tpu.engine.base import ColumnarBatch
    from constdb_tpu.crdt import semantics as S
    from constdb_tpu.engine.cpu import CpuMergeEngine

    def el_batch(member_ts, del_ts, unique):
        b = ColumnarBatch()
        b.keys = [b"s1"]
        b.key_enc = np.full(1, S.ENC_SET, dtype=np.int8)
        b.key_ct = np.array([u(1)], dtype=np.int64)
        b.key_mt = np.array([u(1)], dtype=np.int64)
        b.key_dt = np.zeros(1, dtype=np.int64)
        b.key_expire = np.zeros(1, dtype=np.int64)
        b.reg_val = [None]
        b.reg_t = np.zeros(1, dtype=np.int64)
        b.reg_node = np.zeros(1, dtype=np.int64)
        n = len(member_ts)
        b.el_ki = np.zeros(n, dtype=np.int64)
        b.el_member = [m for m, _ in member_ts]
        b.el_val = [None] * n
        b.el_add_t = np.fromiter((t for _, t in member_ts), np.int64, n)
        b.el_add_node = np.full(n, 3, dtype=np.int64)
        b.el_del_t = np.fromiter(del_ts, np.int64, n)
        b.rows_unique_per_slot = unique
        return b

    def run(engine):
        from constdb_tpu.store.keyspace import KeySpace
        ks = KeySpace()
        # micro round: add m1/m2, then a micro round observed-removes m1
        engine.merge_many(ks, [el_batch([(b"m1", u(2)), (b"m2", u(2))],
                                        [0, 0], False)])
        engine.merge_many(ks, [el_batch([(b"m1", 0)], [u(5)], False)])
        # forced-fold BULK round re-touching the same rows (unique batch)
        engine.merge_many(ks, [el_batch([(b"m1", u(3)), (b"m2", u(3))],
                                        [0, 0], True)])
        if getattr(engine, "needs_flush", False):
            engine.flush(ks)
        return ks.canonical()

    got = run(steady_engine(fold))
    want = run(CpuMergeEngine())
    assert got == want  # m1 stays dead (del u(5) > add u(3))


def test_merge_stats_carry_transfer_deltas():
    """merge_many slices per-call transfer deltas out of the cumulative
    gauges (the MergeStats surface INFO and the bench legs read)."""
    from constdb_tpu.replica.coalesce import BatchBuilder
    from constdb_tpu.resp.message import Bulk
    from constdb_tpu.server.commands import COLUMNAR_ENCODERS

    eng = steady_engine()
    n1 = Node(node_id=1, engine=eng)
    bb = BatchBuilder(n1.ks)
    recs = [(b"k%d" % i, 7, u(i + 1),
             [None] * 6 + [Bulk(b"v%d" % i)])
            for i in range(32)]
    COLUMNAR_ENCODERS[b"set"](bb, recs)
    st = eng.merge_many(n1.ks, [bb.finalize()])
    assert st.dev_rounds_resident == 1
    assert st.dev_upload_bytes > 0
    eng.flush(n1.ks)
    assert eng.flush_rows_downloaded > 0


def test_depth1_sets_of_fifty_connections_reach_the_device_planes(tmp_path):
    """memtier's shape at a small size: 50 closed-loop connections, one
    command in flight each, SET:GET 1:10 on shared keys.  The loop-pass
    gather (server/io.py) plans what a pass delivers as one chunk, so the
    SETs form runs, the runs land as resident `reg` rounds
    (`merge_rows_dev_reg` > 0) — and the store equals a CPU-engine
    node's fed the same commands in the gathered order."""
    import random

    from test_serve_coalesce import cmd
    from test_serve_gather import drive_gathered, replay_per_command

    rng = random.Random(37)
    work = [[[cmd(b"set", b"memtier-%d" % rng.randrange(400),
                  b"v%06d" % rng.getrandbits(19))
              if rng.randrange(11) == 0 else
              cmd(b"get", b"memtier-%d" % rng.randrange(400))]
             for _ in range(40)] for _ in range(50)]
    got = asyncio.run(drive_gathered(tmp_path, "tpu", work,
                                     [[] for _ in work]))
    want = replay_per_command("cpu", got)
    assert got["raw"] == want["raw"]
    assert got["canonical"] == want["canonical"]
    assert got["repl"] == want["repl"]
    info = got["info"]
    assert info["merge_rows_dev_reg"] > 0
    assert info["dev_rounds_resident"] > 0
    st = got["stats"]
    assert st.serve_gather_msgs == 50 * 40
    assert st.serve_gather_msgs / st.serve_gather_passes > 2
    assert st.serve_msgs_coalesced > 0


# ------------------------------------------- the micro round's link protocol
# A resident micro round sends one block up and gets its win vector back
# (ops/bulk.py bulk_lww_win); the flush launches nothing and applies the
# rounds in order (engine/tpu.py _apply_wins).  Differentials against the
# host twin (engine/hostbatch.py through CpuMergeEngine): the same batches,
# byte-identical host columns, values and canonical export after ONE flush.

def micro(keys, enc, reg=(), cnt=(), el=(), unique=False):
    """One op-stream batch.  reg: (ki, t, node, val); cnt: (ki, node, val,
    uuid, base, base_t); el: (ki, member, val, add_t, add_node, del_t)."""
    def col(rows, i):
        return np.array([r[i] for r in rows], dtype=np.int64)

    b = ColumnarBatch()
    nk = len(keys)
    b.keys = list(keys)
    b.key_enc = np.full(nk, getattr(S, "ENC_" + enc), dtype=np.int8)
    b.key_ct = np.full(nk, u(1), dtype=np.int64)
    b.key_mt = np.full(nk, u(1), dtype=np.int64)
    b.key_dt = np.zeros(nk, dtype=np.int64)
    b.key_expire = np.zeros(nk, dtype=np.int64)
    b.reg_val = [None] * nk
    b.reg_t = np.zeros(nk, dtype=np.int64)
    b.reg_node = np.zeros(nk, dtype=np.int64)
    for ki, t, node, val in reg:
        b.reg_val[ki], b.reg_t[ki], b.reg_node[ki] = val, t, node
    if cnt:
        (b.cnt_ki, b.cnt_node, b.cnt_val, b.cnt_uuid, b.cnt_base,
         b.cnt_base_t) = (col(cnt, i) for i in range(6))
    if el:
        b.el_ki, b.el_add_t, b.el_add_node, b.el_del_t = (
            col(el, i) for i in (0, 3, 4, 5))
        b.el_member = [r[1] for r in el]
        b.el_val = [r[2] for r in el]
    b.rows_unique_per_slot = unique
    return b


def reg_rounds():
    """Four rounds on the same three registers: a first write, a newer
    one (wins), an older one (loses), a tie on t broken by node."""
    keys = [b"r0", b"r1", b"r2"]

    def rnd(ts, nodes, tag):
        return micro(keys, "BYTES",
                     reg=[(i, u(t), nd, b"%s%d" % (tag, i))
                          for i, (t, nd) in enumerate(zip(ts, nodes))])
    return [rnd((5, 5, 5), (1, 1, 1), b"a"), rnd((9, 2, 5), (1, 1, 2), b"b"),
            rnd((7, 6, 5), (3, 3, 1), b"c"), rnd((9, 6, 1), (2, 1, 9), b"d")]


def el_rounds(values: bool):
    """Set members (valueless) or dict fields (valued): the same rows
    written by four rounds, wins and losses interleaved, a valueless
    winner over a valued slot, and a delete in the third batch."""
    keys = [b"e0", b"e1"]
    v = (lambda s: s) if values else (lambda s: None)

    def rnd(rows):
        return micro(keys, "DICT" if values else "SET",
                     el=[(ki, m, val, u(t) if t else 0, nd, u(d) if d else 0)
                         for ki, m, val, t, nd, d in rows])
    return [rnd([(0, b"f0", v(b"a0"), 5, 1, 0), (0, b"f1", v(b"a1"), 5, 1, 0),
                 (1, b"f0", v(b"a2"), 5, 1, 0)]),
            rnd([(0, b"f0", v(b"b0"), 8, 1, 0), (0, b"f1", v(b"b1"), 3, 1, 0),
                 (1, b"f0", None, 9, 2, 0)]),
            rnd([(0, b"f0", v(b"c0"), 6, 4, 0), (0, b"f1", None, 0, 0, 7),
                 (1, b"f0", v(b"c2"), 9, 3, 0), (1, b"f9", v(b"c3"), 2, 1, 0)]),
            rnd([(0, b"f0", v(b"d0"), 8, 2, 0), (0, b"f1", v(b"d1"), 9, 1, 0),
                 (1, b"f0", v(b"c2"), 9, 3, 0)])]    # a redelivery: no win


def cnt_rounds():
    """Counter slots of two keys: the (uuid, val) pair and a NON-neutral
    (base_t, base) pair, each winning in some rounds and losing in others
    on the same rows."""
    keys = [b"c0", b"c1"]

    def rnd(rows):
        return micro(keys, "COUNTER",
                     cnt=[(ki, nd, val, u(t), base, u(bt) if bt else S.NEUTRAL_T)
                          for ki, nd, val, t, base, bt in rows])
    return [rnd([(0, 1, 10, 5, 0, 0), (0, 2, 20, 5, 0, 0), (1, 1, 30, 5, 0, 0)]),
            rnd([(0, 1, 11, 7, 4, 6), (0, 2, 19, 4, 0, 0), (1, 1, 31, 5, 9, 3)]),
            rnd([(0, 1, 12, 6, 2, 6), (0, 2, 25, 8, 7, 2), (1, 1, 29, 5, 1, 2)]),
            rnd([(0, 1, 13, 7, 5, 5), (0, 2, 1, 8, 3, 9), (1, 1, 40, 9, 9, 3)])]


ROUNDS = {"reg": reg_rounds, "el-values": lambda: el_rounds(True),
          "el-members": lambda: el_rounds(False), "cnt": cnt_rounds}


def host_state(ks):
    """Every host column the micro path writes, and the object values."""
    n_el, n_cnt, n_k = ks.el.n, ks.cnt.n, ks.keys.n
    cols = {"rv_t": ks.keys.rv_t[:n_k], "rv_node": ks.keys.rv_node[:n_k],
            "cnt_sum": ks.keys.cnt_sum[:n_k]}
    cols.update({c: ks.el.col(c)[:n_el]
                 for c in ("add_t", "add_node", "del_t")})
    # (the two engines hand out counter slots in different orders)
    by_slot = np.lexsort((ks.cnt.node[:n_cnt], ks.cnt.kid[:n_cnt]))
    cols.update({c: ks.cnt.col(c)[:n_cnt][by_slot]
                 for c in ("kid", "node", "val", "uuid", "base", "base_t")})
    return ({k: v.tolist() for k, v in cols.items()},
            list(ks.reg_val[:n_k]), list(ks.el_val[:n_el]))


def flush(engine, ks):
    if getattr(engine, "needs_flush", False):      # the CPU engine has none
        engine.flush(ks)


def merged(engine, batches):
    ks = KeySpace()
    for b in batches:
        engine.merge_many(ks, [b])
    flush(engine, ks)
    return ks


@pytest.mark.parametrize("case", list(ROUNDS))
def test_rounds_on_the_same_rows_before_one_flush_equal_the_host_twin(case):
    eng = steady_engine()
    got = merged(eng, ROUNDS[case]())
    want = merged(CpuMergeEngine(), ROUNDS[case]())
    assert host_state(got) == host_state(want)
    assert got.canonical() == want.canonical()
    # every scatter returned its win vector; nothing tracked `src`
    pairs = 2 if case == "cnt" else 1
    assert eng.dev_rounds_resident == 4
    assert eng.micro_src_scatters == 0
    assert eng.micro_win_scatters >= 4 * pairs - 1   # r1's base is neutral
    assert eng.micro_win_rows > 0
    assert eng.stages.snapshot()["state_alloc"][1] <= 1   # _warm_patch only
    assert not eng.host_stale(("reg", "cnt", "el"))


@pytest.mark.parametrize("case", list(ROUNDS))
def test_a_family_is_stale_while_it_holds_unapplied_win_vectors(case):
    fam = case.split("-")[0]
    eng = steady_engine()
    ks = KeySpace()
    eng.merge_many(ks, [ROUNDS[case]()[0]])
    res = eng._res[fam]
    assert res["wins"] and not res["written"] and res["src"] is None
    assert eng.host_stale((fam,))
    assert not eng.host_stale(tuple({"reg", "cnt", "el"} - {fam}))
    eng.flush(ks)
    assert not res["wins"] and not eng.host_stale((fam,))


@pytest.mark.parametrize("case", list(ROUNDS))
def test_a_micro_round_after_a_bulk_round_falls_back_to_src(case):
    """A whole-plane round (unique batch: the bulk path, `src` tracked,
    dirty None), then micro rounds, then ONE flush: the micro scatters
    keep the bulk protocol while the family carries unflushed `src`
    (their vectors and the bulk round's `src` resolve in one flush), and
    take the win-vector protocol again after the flush."""
    rounds = ROUNDS[case]()
    first = ROUNDS[case]()[0]
    first.rows_unique_per_slot = True

    def run(engine):
        ks = KeySpace()
        for b in [first] + rounds[1:3]:
            engine.merge_many(ks, [b])
        mid = (getattr(engine, "micro_src_scatters", 0),
               getattr(engine, "micro_win_scatters", 0))
        flush(engine, ks)
        engine.merge_many(ks, [rounds[3]])
        flush(engine, ks)
        return ks, mid

    eng = steady_engine()
    got, (n_src, n_win) = run(eng)
    want, _ = run(CpuMergeEngine())
    assert host_state(got) == host_state(want)
    assert got.canonical() == want.canonical()
    # (the counter base pair is never tracked in `src`: win vectors)
    assert n_src == 2 and n_win == (2 if case == "cnt" else 0)
    assert eng.micro_win_scatters >= 1 and eng.micro_src_scatters == n_src


@pytest.mark.parametrize("case", list(ROUNDS))
def test_micro_rounds_then_a_bulk_round_before_one_flush(case):
    """The other order: win-vector rounds, then a whole-plane round on
    the same rows, one flush — the vectors apply first, `src` over them."""
    rounds = ROUNDS[case]()
    rounds[2].rows_unique_per_slot = True
    got = merged(steady_engine(), rounds[:3])
    want = merged(CpuMergeEngine(), ROUNDS[case]()[:3])
    assert host_state(got) == host_state(want)
    assert got.canonical() == want.canonical()


@pytest.mark.parametrize("fam", ("reg", "el"))
def test_a_round_and_its_flush_cross_the_link_once_each_way(fam):
    """The protocol, on the stage clocks: k micro rounds each followed by
    a flush make k uploads (`h2d` entries), k launches (`dispatch`), no
    `state_alloc`, and no scatter tracks `src`."""
    eng = steady_engine()
    ks = KeySpace()

    def rnd(i, rows=(0, 1)):
        t = {0: u(i + (i % 3)), 1: u(9 - i)}     # row 0 wins two rounds of
        if fam == "reg":                         # three, row 1 none
            return micro([b"p%03d" % r for r in rows], "BYTES",
                         reg=[(j, t.get(r, u(1)), 1, b"v%d-%d" % (r, i))
                              for j, r in enumerate(rows)])
        return micro([b"h"], "DICT",
                     el=[(0, b"f%03d" % r, b"v%d-%d" % (r, i), t.get(r, u(1)),
                          1, 0) for r in rows])

    # 300 rows: the plane outgrows a batch, so a batch pads to the floor;
    # this first round builds the mirror and its flush warms the patch
    eng.merge_many(ks, [rnd(0, range(300))])
    eng.flush(ks)
    up0 = eng.bytes_h2d
    before = eng.stages.snapshot()
    k = 6
    for i in range(1, k + 1):
        eng.merge_many(ks, [rnd(i)])
        assert eng.host_stale((fam,))
        eng.flush(ks)
    after = eng.stages.snapshot()

    def moved(stage):
        return after[stage][1] - before[stage][1]
    assert moved("h2d") == k
    assert moved("dispatch") == k
    assert moved("d2h_flush") == k
    assert moved("state_alloc") == 0
    assert moved("mirror_rebuild") == moved("mirror_patch") == 0
    assert eng.micro_src_scatters == 0
    assert eng.micro_win_scatters == k + 1
    assert eng.micro_win_rows == 300 + 4
    # one 5 x 256 int32 block a round, nothing else up
    assert eng.bytes_h2d - up0 == k * 5 * eng.MICRO_SCATTER_PAD * 4
