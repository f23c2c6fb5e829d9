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
    state.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")  # noqa: F841

from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.server.node import Node
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
