"""Digest-driven delta anti-entropy (replica/link.py + store/digest.py).

The protocol under test: a pusher whose peer's resume point fell off the
repl_log ring exchanges a two-level state digest over the crc32 shard
partition — per-shard rollups, then per-key-range leaf digests for the
shards that mismatch — and streams ONLY the divergent buckets as a
snapshot-format delta, instead of re-shipping the whole keyspace.
Soundness rests on the digest being a pure function of logical CRDT
state (store/digest.py module header): any two stores holding the same
state produce the same matrix, whatever engine merged it, however its
shards are laid out, in whatever order the ops arrived.  The
determinism suite pins that; the e2e suites pin the wire protocol, the
O(divergence) transfer, the threshold demotion, and the mid-stream
ring-falloff recovery riding the same negotiation.
"""

import asyncio
import io
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_link_pushloop import _Writer, _mk_link  # noqa: E402

from constdb_tpu.crdt import semantics as S  # noqa: E402
from constdb_tpu.engine.base import batch_from_keyspace  # noqa: E402
from constdb_tpu.engine.cpu import CpuMergeEngine  # noqa: E402
from constdb_tpu.persist.snapshot import SectionDemux  # noqa: E402
from constdb_tpu.replica.link import (CAP_DELTA_SYNC,  # noqa: E402
                                      CAP_FULLSYNC_RESET, DELTASYNC, DIGEST,
                                      DIGESTACK, FULLSYNC, REPLICATE)
from constdb_tpu.resp.codec import make_parser  # noqa: E402
from constdb_tpu.resp.message import Arr, Bulk, Int, as_bytes, as_int  # noqa: E402
from constdb_tpu.server.node import Node  # noqa: E402
from constdb_tpu.store import digest as D  # noqa: E402
from constdb_tpu.store.keyspace import KeySpace  # noqa: E402

MS0 = 1_600_000_000_000 << 22  # uuid base well below any live HLC tick


# --------------------------------------------------------------------------
# state builders


def _mixed_ops(n_keys: int = 160, seed: int = 3):
    """A deterministic mixed op list [(kind, key, member/val, uuid)]
    covering registers, counters, and sets — applied through whichever
    path a test exercises."""
    import random
    rng = random.Random(seed)
    ops = []
    t = 0
    for i in range(n_keys):
        t += 1 + rng.randrange(3)
        r = i % 10
        key = b"k%04d" % i
        if r < 4:
            ops.append(("set", key, b"v%06d" % rng.randrange(10_000),
                        MS0 + (t << 10)))
        elif r < 7:
            ops.append(("cnt", key, rng.randrange(-50, 50),
                        MS0 + (t << 10)))
        else:
            for m in range(3):
                t += 1
                ops.append(("sadd", key, b"m%02d" % rng.randrange(8),
                           MS0 + (t << 10)))
            if rng.random() < 0.5:
                t += 1
                ops.append(("srem", key, b"m%02d" % rng.randrange(8),
                           MS0 + (t << 10)))
    return ops


def _apply_ops(ks: KeySpace, ops, node: int = 7) -> None:
    for kind, key, x, uuid in ops:
        if kind == "set":
            kid, _ = ks.get_or_create(key, S.ENC_BYTES, uuid)
            ks.register_set(kid, x, uuid, node)
        elif kind == "cnt":
            kid, _ = ks.get_or_create(key, S.ENC_COUNTER, uuid)
            ks.counter_change(kid, node, x, uuid)
        elif kind == "sadd":
            kid, _ = ks.get_or_create(key, S.ENC_SET, uuid)
            ks.elem_add(kid, x, None, uuid, node)
            ks.updated_at(kid, uuid)
        elif kind == "srem":
            kid, _ = ks.get_or_create(key, S.ENC_SET, uuid)
            ks.elem_rem(kid, x, uuid)


def _digest_of(ks: KeySpace, fanout: int = 16, leaves: int = 8):
    return D.state_digest_matrix(ks, fanout, leaves)


def test_full_state_digest_is_geometry_independent():
    """The scalar fold (the chaos oracle's digest-agreement law and the
    resync bench's cross-check) is the mod-2^64 sum of the matrix, so
    every (fanout, leaves) layout of one state agrees — and two states
    that differ by one write do not."""
    ks = KeySpace()
    _apply_ops(ks, _mixed_ops())
    want = D.full_state_digest(ks)
    for fanout, leaves in ((1, 1), (4, 2), (16, 8), (64, 1)):
        assert D.full_state_digest(ks, fanout, leaves) == want
    other = KeySpace()
    _apply_ops(other, _mixed_ops())
    assert D.full_state_digest(other) == want  # same ops, same state
    kid, _ = other.get_or_create(b"extra", S.ENC_COUNTER, 77 << 22)
    other.counter_change(kid, 9, 1, 77 << 22)
    assert D.full_state_digest(other) != want


# --------------------------------------------------------------------------
# digest determinism: one logical state, many construction routes


def test_digest_engine_and_shard_determinism():
    """CPU engine merge, TPU (XLA) engine merge, and the hash-sharded
    plane at 1/2/3 shards all produce the SAME per-shard digest matrix
    for the same logical state — the invariant the whole anti-entropy
    protocol rests on (a sharded-serving node SUMS its workers'
    matrices, so plane-wide must equal single-store)."""
    ops = _mixed_ops()
    ref = KeySpace()
    _apply_ops(ref, ops)
    want = _digest_of(ref)
    dump = batch_from_keyspace(ref)

    # CPU engine replay of the state dump
    ks_cpu = KeySpace()
    CpuMergeEngine().merge(ks_cpu, dump)
    assert (_digest_of(ks_cpu) == want).all()

    # XLA engine replay (the batched device path)
    from constdb_tpu.engine.tpu import TpuMergeEngine
    eng = TpuMergeEngine()
    ks_tpu = KeySpace()
    eng.merge(ks_tpu, batch_from_keyspace(ref))
    if getattr(eng, "needs_flush", False):
        eng.flush(ks_tpu)
    assert (_digest_of(ks_tpu) == want).all()

    # sharded plane, 1/2/3 shards: per-shard stores digest their
    # disjoint keys; the plane matrix is the SUM (store/digest.py)
    from constdb_tpu.store.sharded_keyspace import ShardedKeySpace
    for n in (1, 2, 3):
        sks = ShardedKeySpace(n_shards=n, mode="local",
                              engine_factory=CpuMergeEngine)
        sks.submit(batch_from_keyspace(ref))
        sks.flush()
        mats = [D.state_digest_matrix(s, 16, 8) for s in sks.stores]
        got = D.sum_matrices(mats, 16, 8)
        assert (got == want).all(), f"shards={n} digest diverged"
        sks.close()


def test_digest_order_independence_and_locality():
    """Row order and merge order are invisible — one store built by a
    single whole-state merge, another by permuted partial merges (with
    an idempotent re-merge on top), digest identically; and a single
    divergent write flags exactly its own bucket."""
    ops = _mixed_ops()
    ref = KeySpace()
    _apply_ops(ref, ops)
    a, b = KeySpace(), KeySpace()
    CpuMergeEngine().merge(a, batch_from_keyspace(ref))
    n = ref.keys.n
    perm = np.random.RandomState(7).permutation(n)
    eng = CpuMergeEngine()
    # halves land in swapped order, rows permuted, then the whole state
    # re-merges on top: state merges are idempotent + commutative, and
    # the digest sees only the landed result
    eng.merge(b, batch_from_keyspace(ref, key_sel=perm[n // 2:]))
    eng.merge(b, batch_from_keyspace(ref, key_sel=perm[:n // 2]))
    eng.merge(b, batch_from_keyspace(ref, key_sel=perm))
    assert a.canonical() == b.canonical()
    assert (_digest_of(a) == _digest_of(b)).all()

    kid = a.lookup(b"k0000")
    a.register_set(kid, b"DIVERGED", MS0 + (1 << 30), 9)
    da, db = _digest_of(a), _digest_of(b)
    assert int((da != db).sum()) == 1
    # and the divergent bucket's export re-converges the digests
    mask = (da != db).reshape(-1)
    CpuMergeEngine().merge(b, D.export_bucket_batch(a, 16, 8, mask))
    assert (_digest_of(b) == da).all()


def test_digest_inert_tombstone_and_gc_invariance():
    """The two GC-related normalizations: an element del_t at or below
    its add_t is inert and digests as 0 (GC-timing skew must not flag
    spurious divergence), and same-horizon GC on two replicas leaves
    their digests equal (collected rows drop out of the fold on both)."""
    a, b = KeySpace(), KeySpace()
    for ks in (a, b):
        kid, _ = ks.get_or_create(b"s1", S.ENC_SET, MS0 + 100)
        ks.elem_add(kid, b"m1", None, MS0 + 100, 7)
        ks.updated_at(kid, MS0 + 100)
    # an older remove lands on `a` only: semantically inert (the add
    # wins), and the digest must agree it is invisible
    a.elem_merge(a.lookup(b"s1"), b"m1", MS0 + 100, 7, MS0 + 50, None)
    b.elem_merge(b.lookup(b"s1"), b"m1", MS0 + 100, 7, 0, None)
    assert a.canonical() == b.canonical()
    assert (_digest_of(a) == _digest_of(b)).all()

    # dead tombstones + key deletes, collected at the SAME horizon
    ops = _mixed_ops(80, seed=11)
    for ks in (a, b):
        _apply_ops(ks, ops)
        kid = ks.lookup(b"k0004")
        ks.set_delete_time(kid, MS0 + (2 << 30))
        ks.record_key_delete(b"k0004", MS0 + (2 << 30))
        kid = ks.lookup(b"k0007")
        ks.elem_rem(kid, b"m01", MS0 + (2 << 30))
    assert (_digest_of(a) == _digest_of(b)).all()
    horizon = MS0 + (3 << 30)
    assert a.gc(horizon) == b.gc(horizon)
    assert not a.key_deletes and b.lookup(b"k0004") >= 0
    assert (_digest_of(a) == _digest_of(b)).all()


def test_digest_matches_after_coalesced_stream_apply():
    """A node fed by the COALESCED replication applier digests
    identically to one fed the exact per-frame path — the digest is
    computed over landed state, so the micro-batch route is invisible."""
    from constdb_tpu.replica.coalesce import CoalescingApplier
    from constdb_tpu.replica.manager import ReplicaMeta

    frames = []
    prev = 0
    for i, (kind, key, x, uuid) in enumerate(_mixed_ops(120, seed=5)):
        if kind == "set":
            body = [Bulk(b"set"), Bulk(key), Bulk(x)]
        elif kind == "cnt":
            body = [Bulk(b"cntset"), Bulk(key), Int(x)]
        elif kind == "sadd":
            body = [Bulk(b"sadd"), Bulk(key), Bulk(x)]
        else:
            body = [Bulk(b"srem"), Bulk(key), Bulk(x)]
        frames.append([Bulk(b"replicate"), Int(99), Int(prev),
                       Int(MS0 + ((i + 1) << 12)), *body])
        prev = MS0 + ((i + 1) << 12)

    nodes = []
    for batch in (256, 1):  # coalesced vs exact per-frame
        node = Node(node_id=1, engine=CpuMergeEngine())
        applier = CoalescingApplier(node, ReplicaMeta("p:0"),
                                    max_frames=batch, max_latency=10.0)
        for items in frames:
            applier.apply(items)
        applier.flush()
        node.ensure_flushed()
        nodes.append(node)
    d0, d1 = (_digest_of(n.ks) for n in nodes)
    assert (d0 == d1).all()


# --------------------------------------------------------------------------
# e2e: partitioned pair resyncs by delta, not by snapshot


async def _sever(apps) -> None:
    for app in apps:
        for m in list(app.node.replicas.peers.values()):
            m.dial_suspended = True
            if m.link is not None:
                await m.link.stop()
    await asyncio.sleep(0.1)


async def _until(cond, timeout: float) -> None:
    """Poll until `cond()` holds (an assertion after it says what did not)."""
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond() and asyncio.get_running_loop().time() < deadline:
        await asyncio.sleep(0.02)


def _rejoin(apps) -> None:
    for app in apps:
        for m in app.node.replicas.peers.values():
            m.dial_suspended = False
            app.ensure_link(m)


def test_delta_resync_e2e(tmp_path):
    """Partition a converged pair, diverge a small key set past the
    repl_log ring, reconnect: the resync must go DELTA (not snapshot),
    ship less than the full dump would, and land byte-identical
    canonical state; the stream then keeps replicating normally."""
    from cluster_util import Client, close_cluster, converge, make_cluster

    async def main():
        # wire_compress=False pins the pre-compression byte accounting
        # this test is ABOUT (delta bytes vs the full dump it replaced);
        # at this toy scale a compressed full dump is ~2KB and the
        # digest negotiation's frames alone would drown the comparison.
        # Compressed delta/fullsync transfers ride tests/
        # test_wire_compress.py and the chaos compression cells.
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=3000,
                                  wire_compress=False)
        a, b = apps
        try:
            c = await Client().connect(a.advertised_addr)
            for i in range(1000):
                await c.cmd("set", f"k{i:04d}", "v" * 24)
            await c.cmd("meet", b.advertised_addr)
            await converge(apps, timeout=30)
            # the JOIN sync (empty peer = total divergence) must have
            # demoted to a full snapshot, loudly
            assert a.node.stats.repl_full_syncs >= 1
            assert a.node.stats.extra.get("repl_delta_demotions", 0) >= 1
            full_bytes = a.node.stats.extra["last_snapshot_bytes"]
            full0 = a.node.stats.repl_full_syncs

            await _sever(apps)
            # overwrite 20 distinct keys, enough times to evict the ring
            for r in range(12):
                for i in range(20):
                    await c.cmd("set", f"k{i:04d}",
                                f"D{r}-{i}" + "x" * 16)
            resume = b.node.replicas.get(a.advertised_addr).uuid_he_sent
            assert not a.node.repl_log.can_resume_from(resume), \
                "divergence did not evict the ring; test is vacuous"
            b_in0 = b.node.stats.repl_in_bytes
            _rejoin(apps)
            await converge(apps, timeout=30)

            st = a.node.stats
            # b may land the delta (and `converge` see it) before a's push
            # loop runs on from its stream's last drain to count the
            # resync: wait for the count, not for the scheduler
            await _until(lambda: st.repl_delta_syncs
                         + st.repl_full_syncs - full0 >= 1, timeout=15)
            assert st.repl_delta_syncs >= 1, "resync did not go delta"
            assert st.repl_full_syncs == full0, \
                "delta resync fell back to a snapshot"
            assert st.repl_digest_rounds >= 2
            assert 0 < st.repl_delta_bytes < full_bytes
            resync_in = b.node.stats.repl_in_bytes - b_in0
            assert resync_in < full_bytes, \
                f"resync moved {resync_in}B >= full dump {full_bytes}B"
            assert a.node.canonical() == b.node.canonical()

            # the same connection keeps streaming after the delta
            deltas = st.repl_delta_syncs
            for i in range(30):
                await c.cmd("set", f"post{i}", "z")
            await converge(apps, timeout=15)
            assert st.repl_delta_syncs == deltas  # no re-negotiation
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_delta_disabled_pins_full_sync(tmp_path):
    """CONSTDB_DELTA_SYNC=0 (ServerApp delta_sync=False): the identical
    scenario ships a full snapshot — the delta path is opt-out-able."""
    from cluster_util import Client, close_cluster, converge, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=2000,
                                  delta_sync=False)
        a, b = apps
        try:
            c = await Client().connect(a.advertised_addr)
            for i in range(300):
                await c.cmd("set", f"k{i:04d}", "v" * 24)
            await c.cmd("meet", b.advertised_addr)
            await converge(apps, timeout=30)
            full0 = a.node.stats.repl_full_syncs
            assert full0 >= 1
            await _sever(apps)
            for r in range(12):
                for i in range(10):
                    await c.cmd("set", f"k{i:04d}",
                                f"D{r}-{i}" + "x" * 16)
            _rejoin(apps)
            await converge(apps, timeout=30)
            st = a.node.stats
            assert st.repl_delta_syncs == 0
            assert st.repl_digest_rounds == 0
            assert st.repl_full_syncs > full0
            assert a.node.canonical() == b.node.canonical()
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


# --------------------------------------------------------------------------
# mid-stream ring falloff recovers via digest negotiation (satellite:
# the PR-2 in-place fallback no longer costs a full snapshot)


def _log_write(node: Node, i: int) -> None:
    """One logged `set` mirroring the REAL op exactly (get_or_create
    with ENC_BYTES + register_set + repl_log append) — unlike the
    pushloop suite's enc-agnostic stub, because the loopback sim below
    applies the replicated frames through apply_replicated for real and
    the converged canonical states must match."""
    uuid = node.hlc.tick(True)
    key = b"k%d" % i
    kid, _ = node.ks.get_or_create(key, S.ENC_BYTES, uuid)
    node.ks.register_set(kid, b"x" * 40, uuid, node.node_id)
    node.replicate_cmd(uuid, b"set", [Bulk(key), Bulk(b"x" * 40)])


class _PullerSim:
    """Simulated CAP_DELTA_SYNC puller for a unit-harness pusher: holds
    a real Node, parses every frame the pusher writes, answers digest
    questions through the link's ack queue, applies delta payloads and
    replicate frames — a loopback replica without sockets."""

    def __init__(self, link, writer, node: Node):
        self.link = link
        self.writer = writer
        self.node = node
        self.parser = make_parser()
        self.fed = 0
        self.kinds: list = []
        self._matrix = {}
        self._want_raw = 0
        self._raw = bytearray()

    def _feed(self) -> None:
        buf = self.writer.buf
        if len(buf) > self.fed:
            self.parser.feed(bytes(buf[self.fed:]))
            self.fed = len(buf)

    async def run(self) -> None:
        while True:
            await asyncio.sleep(0.005)
            self._feed()
            while True:
                if self._want_raw:
                    raw = self.parser.take_raw(self._want_raw)
                    if not raw:
                        break
                    self._raw += raw
                    self._want_raw -= len(raw)
                    if self._want_raw:
                        break
                    self._apply_delta(bytes(self._raw))
                    self._raw.clear()
                msg = self.parser.next_msg()
                if msg is None:
                    break
                items = msg.items if isinstance(msg, Arr) else None
                assert items, f"bad frame {msg!r}"
                kind = as_bytes(items[0]).lower()
                self.kinds.append(kind)
                if kind == DIGEST:
                    self._answer(items)
                elif kind == DELTASYNC:
                    self._want_raw = as_int(items[1])
                    self.node.hlc.observe(as_int(items[2]))
                elif kind == FULLSYNC:
                    self._want_raw = as_int(items[1])
                elif kind == REPLICATE:
                    self.node.apply_replicated(
                        as_bytes(items[4]), items[5:], as_int(items[1]),
                        as_int(items[3]))

    def _answer(self, items) -> None:
        token, level = as_int(items[1]), as_int(items[2])
        fanout, leaves = as_int(items[3]), as_int(items[4])
        if level == 0:
            mat = D.state_digest_matrix(self.node.ks, fanout, leaves)
            self._matrix[token] = mat
            theirs = np.frombuffer(as_bytes(items[5]), dtype="<u8")
            mine = mat.sum(axis=1, dtype=np.uint64)
            reply = np.nonzero(mine != theirs)[0].astype("<i8").tobytes()
        else:
            shards = np.frombuffer(as_bytes(items[5]),
                                   dtype="<i8").astype(np.int64)
            sub = np.frombuffer(as_bytes(items[6]), dtype="<u8") \
                .reshape(len(shards), leaves)
            mine = self._matrix[token][shards]
            srow, leaf = np.nonzero(mine != sub)
            reply = (shards[srow] * leaves + leaf).astype("<i8").tobytes()
        self.link._digest_acks.put_nowait(
            [Bulk(DIGESTACK), Int(token), Int(level), Bulk(reply)])

    def _apply_delta(self, payload: bytes) -> None:
        demux = SectionDemux(io.BytesIO(payload))
        eng = CpuMergeEngine()
        for b in demux.batches():
            eng.merge(self.node.ks, b)


def test_midstream_falloff_resyncs_by_delta(tmp_path):
    """Evict the ring past the send cursor mid-stream against a
    CAP_DELTA_SYNC peer: the in-place recovery must run the digest
    negotiation and stream a DELTA — never a full snapshot, never a
    gapped frame — and the loopback puller must converge."""
    async def main():
        node, app, link = _mk_link(tmp_path, cap=100_000)
        # flush+drain per 64-frame run (the pre-wire-buffer cadence):
        # this test's eviction is rigged to fire at drain #1, which must
        # land MID-backlog for the horizon to pass the send cursor
        app.wire_latency = 0.0
        for i in range(100):
            _log_write(node, i)
        link._peer_caps = CAP_FULLSYNC_RESET | CAP_DELTA_SYNC
        link._digest_acks = asyncio.Queue()

        puller = Node(node_id=2)
        CpuMergeEngine().merge(puller.ks, batch_from_keyspace(node.ks))

        def evict(drain_no):
            if drain_no == 1:
                # a burst of 8 large writes on a shrunken ring: eviction
                # races the in-flight stream, divergence stays small
                # enough that the digest path must NOT demote
                node.repl_log.cap = 400
                for i in range(8):
                    _log_write(node, 1000 + i)

        writer = _Writer(on_drain=evict)
        sim = _PullerSim(link, writer, puller)
        sim_task = asyncio.create_task(sim.run())
        push = asyncio.create_task(link._push_loop(writer, peer_resume=0))
        try:
            for _ in range(600):  # phase 1: delta negotiated + applied
                await asyncio.sleep(0.01)
                if node.stats.repl_delta_syncs and not sim._want_raw \
                        and DELTASYNC in sim.kinds:
                    break
            for i in range(2):  # the stream continues after the delta
                _log_write(node, 5000 + i)
            for _ in range(600):  # phase 2: post-delta frames land
                await asyncio.sleep(0.01)
                if puller.ks.lookup(b"k5001") >= 0:
                    break
        finally:
            push.cancel()
            sim_task.cancel()
        assert FULLSYNC not in sim.kinds, \
            "mid-stream falloff still paid a full snapshot"
        assert sim.kinds.count(DIGEST) == 2
        assert DELTASYNC in sim.kinds
        assert node.stats.repl_delta_syncs == 1
        assert node.stats.repl_full_syncs == 0
        assert app.shared_dump.dumps == 0
        # replay is complete: every frame the sim applied + the delta
        # re-based it onto the pusher's state
        assert puller.canonical() == node.canonical()
        assert not writer.closed
    asyncio.run(main())


# --------------------------------------------------------------------------
# serve-plane pusher: digests sum over workers, buckets export encoded


@pytest.mark.slow
def test_delta_resync_from_sharded_pusher(tmp_path):
    """A shard-per-core node (CONSTDB_SERVE_SHARDS=2) answers the same
    protocol: worker digests sum into the plane matrix, divergent
    buckets export worker-encoded, and the plain peer converges by
    delta."""
    from cluster_util import Client, close_cluster, converge, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=3000,
                                  serve_shards=2)
        a, b = apps  # a is sharded; b (also sharded) pulls by delta too
        try:
            c = await Client().connect(a.advertised_addr)
            for i in range(600):
                await c.cmd("set", f"k{i:04d}", "v" * 24)
            await c.cmd("meet", b.advertised_addr)
            await converge_plane(apps)
            await _sever(apps)
            # every shard SEGMENT carries the full byte cap, so eviction
            # needs ~n_shards times the single-ring divergence volume
            for r in range(30):
                for i in range(15):
                    await c.cmd("set", f"k{i:04d}",
                                f"D{r}-{i}" + "x" * 16)
            resume = b.node.replicas.get(a.advertised_addr).uuid_he_sent
            assert not a.node.repl_log.can_resume_from(resume), \
                "divergence did not evict the ring; test is vacuous"
            _rejoin(apps)
            await converge_plane(apps)
            st = a.node.stats
            await _until(lambda: st.repl_delta_syncs >= 1, timeout=15)
            assert st.repl_delta_syncs >= 1, "plane pusher never went delta"
            await c.close()
        finally:
            await close_cluster(apps)

    async def converge_plane(apps, timeout=30.0):
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while True:
            canons = []
            for app in apps:
                if app.node.serve_plane is not None:
                    canons.append(await app.node.serve_plane.canonical())
                else:
                    canons.append(app.node.canonical())
            if all(c == canons[0] for c in canons[1:]):
                return
            assert loop.time() < deadline, "no convergence"
            await asyncio.sleep(0.1)

    asyncio.run(main())
