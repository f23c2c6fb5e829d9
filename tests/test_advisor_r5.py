"""Regression tests for the round-4 advisor findings (ADVICE.md, fixed in
round 5).

Each test pins one fixed behavior: GC peer retention defaults OFF and,
when enabled, a returning excluded peer gets a STATE-CLEARING full resync
(no mesh-wide resurrection); the native RESP batch scan stops at a
FULLSYNC frame; the flush-before-touch invariant raises (not assert);
engine='tpu' fails the boot without an accelerator and INFO names the
backend; a negative per-slot bytes-column length is rejected at the
section.
"""

import asyncio

import numpy as np
import pytest

from constdb_tpu.conf import Config
from constdb_tpu.replica.manager import ReplicaManager
from constdb_tpu.resp.message import Arr, Bulk, Int
from constdb_tpu.server.node import Node

from cluster_util import Client, close_cluster, converge, make_cluster, FAST


def _cmd(node, *parts):
    return node.execute([Bulk(p if isinstance(p, bytes) else str(p).encode())
                         for p in parts])


# ------------------------------------------------- 1: gc_peer_retention


def test_retention_defaults_off_everywhere():
    """Default = reference behavior (a dead peer pins GC forever); the
    lossy exclusion rule is opt-in (advisor round-4 medium)."""
    from constdb_tpu.server.io import ServerApp

    assert Config().gc_peer_retention == 0
    assert ReplicaManager().gc_peer_retention_ms == 0
    node = Node(node_id=1)
    ServerApp(node, work_dir="/tmp")
    assert node.replicas.gc_peer_retention_ms == 0


class _StubLink:
    def __init__(self):
        self.kicked = 0

    def kick(self):
        self.kicked += 1


def test_reset_for_full_resync_wipes_state():
    node = Node(node_id=1)
    _cmd(node, b"set", b"k", b"v")
    _cmd(node, b"sadd", b"s", b"m")
    node.replicas.add("peer:1", uuid=5)
    node.replicas.get("peer:1").uuid_he_sent = 99
    node.replicas.add("peer:2", uuid=5)
    keep = _StubLink()
    other = _StubLink()
    node.replicas.get("peer:1").link = keep
    node.replicas.get("peer:2").link = other
    old_last = node.repl_log.last_uuid
    assert old_last > 0
    epoch0 = node.reset_epoch
    node.reset_for_full_resync(keep_link=keep)
    assert node.ks.keys.n == 0
    # the fresh log is FENCED at the pre-wipe watermark: peers resuming
    # below it must get a full snapshot, never a PARTSYNC of nothing
    assert len(node.repl_log) == 0
    assert node.repl_log.evicted_up_to >= old_last
    assert not node.repl_log.can_resume_from(old_last - 1)
    # membership survives, pull watermarks do not
    m = node.replicas.get("peer:1")
    assert m is not None and m.alive and m.uuid_he_sent == 0
    # other streams are kicked into a fresh handshake; the delivering
    # stream (keep_link) survives; stale-stream beacons are fenced off
    assert other.kicked == 1 and keep.kicked == 0
    assert node.reset_epoch == epoch0 + 1
    # the node still serves writes afterwards
    _cmd(node, b"set", b"k2", b"v2")
    assert _cmd(node, b"get", b"k2") == Bulk(b"v2")


def test_excluded_peer_gets_state_clearing_resync(tmp_path):
    """The full scenario from the advisor finding: node B goes silent past
    the retention window, A collects B's unseen tombstones AND B's resume
    point falls off A's repl_log.  On return, B must be wiped + resynced —
    the deleted key must NOT resurrect mesh-wide."""
    async def main():
        from constdb_tpu.server.io import ServerApp

        apps = await make_cluster(2, str(tmp_path), repl_log_cap=600,
                                  gc_peer_retention=3600.0)
        try:
            a, b = apps
            c = await Client().connect(a.advertised_addr)
            await c.cmd("meet", b.advertised_addr)
            await converge(apps)
            await c.cmd("sadd", "s", "stale")
            await c.cmd("set", "doomed", "v")
            await converge(apps)

            # B goes dark (warm: keeps its Node state, loses connections)
            b_port = b.port
            await b.close()
            await asyncio.sleep(0.1)

            # A deletes while B is away, then the silence exceeds the window
            await c.cmd("srem", "s", "stale")
            await c.cmd("del", "doomed")
            meta_b = a.node.replicas.get(b.advertised_addr)
            meta_b.last_seen_ms -= 10_000_000  # silent "forever"
            # horizon unpins, tombstones collect, needs_full latches
            a.node.gc()
            assert meta_b.needs_full is True
            assert len(a.node.ks.garbage) == 0  # tombstones physically gone
            # enough traffic to evict B's resume point off the tiny ring
            for i in range(60):
                await c.cmd("set", f"fill{i}", "x" * 32)
            assert not a.node.repl_log.can_resume_from(meta_b.uuid_i_sent)

            # B returns with the stale member/key still live locally
            assert b"stale" in {m for m, _, _ in
                                b.node.ks.elem_live(b.node.ks.lookup(b"s"))}
            b2 = ServerApp(b.node, host="127.0.0.1", port=b_port,
                           work_dir=str(tmp_path), **FAST)
            await b2.start()
            apps[1] = b2
            await converge(apps, timeout=20.0)
            # no resurrection anywhere: the delete sticks on BOTH nodes
            for app in apps:
                cx = await Client().connect(app.advertised_addr)
                from constdb_tpu.resp.message import Nil
                assert isinstance(await cx.cmd("get", "doomed"), Nil)
                got = await cx.cmd("smembers", "s")
                assert b"stale" not in {i.val for i in got.items}
                assert await cx.cmd("get", "fill59") == Bulk(b"x" * 32)
                await cx.close()
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_reset_resync_rekicks_surviving_streams(tmp_path):
    """Ops applied just before a wipe must be RE-delivered by the peers
    that originated them: after B wipes, C's surviving idle stream resends
    nothing, and C's REPLACK beacon would quietly re-advance B's zeroed
    pull watermark past C's ops — losing them forever.  The wipe must kick
    C's connection (fresh handshake at resume 0) and fence stale-stream
    beacons behind the reset epoch (code-review round-5 finding).

    Deterministic shape: only C holds its origin ops when B wipes (there
    is no third node whose snapshot could smuggle them back), and C is
    idle afterwards, so ONLY a kicked re-handshake can restore them."""
    async def main():
        apps = await make_cluster(2, str(tmp_path))
        try:
            b, c = apps
            cc = await Client().connect(c.advertised_addr)
            await cc.cmd("meet", b.advertised_addr)
            await converge(apps)
            await cc.cmd("set", "late", "from-c")
            await converge(apps)
            assert b.node.ks.lookup(b"late") >= 0

            # B is wiped (the receive side of a reset-fullsync from some
            # excluding peer; keep_link=None — the exciser is gone)
            b.node.reset_for_full_resync()
            assert b.node.ks.lookup(b"late") < 0
            # C is idle: no new ops will ever arrive.  Only the kick-forced
            # re-handshake (resume 0 → C replays its log from the start)
            # can re-deliver "late"; without it, C's idle beacon advances
            # B's zeroed watermark and convergence never happens.
            await converge(apps, timeout=15.0)
            assert b.node.ks.lookup(b"late") >= 0
            got = await cc.cmd("get", "late")
            assert got == Bulk(b"from-c")
            await cc.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


# ------------------------------------- 2: native scan stops at FULLSYNC


def test_native_scan_stops_at_fullsync_frame():
    from constdb_tpu.resp.codec import NativeRespParser, _ext, encode_msg

    if _ext() is None:
        pytest.skip("native extension not built")
    p = NativeRespParser()
    frame = encode_msg(Arr([Bulk(b"fullsync"), Int(10), Int(7)]))
    # raw snapshot bytes that LOOK like RESP (':' int frames) — the exact
    # corruption the advisor demonstrated
    raw = b":123\r\n:456\r\nXY"
    p.feed(encode_msg(Arr([Bulk(b"partsync")])) + frame + raw)
    assert p.next_msg().items[0].val == b"partsync"
    msg = p.next_msg()
    assert msg.items[0].val == b"fullsync"
    # the scan must NOT have consumed the raw run as frames
    assert p.take_raw(10) == raw[:10]
    assert p.take_raw(4) == raw[10:]


# ------------------------------------ 3: invariant raises, not asserts


def test_mirror_invariant_raises_runtime_error():
    jax = pytest.importorskip("jax")  # noqa: F841
    from constdb_tpu.engine.tpu import TpuMergeEngine
    from constdb_tpu.store.keyspace import KeySpace

    eng = TpuMergeEngine(resident=True)
    store = KeySpace()
    eng._res["el"] = {"cols": {}, "n": 0, "cap": 0, "ver": -12345,
                      "src": None, "written": {"add_t"}}
    with pytest.raises(RuntimeError, match="flush-before-touch"):
        eng._resident_state(store, "el", 0)


# ------------------------------------------- 4: strict engine variant


def test_engine_tpu_fails_the_boot_without_an_accelerator():
    """engine='tpu' on a host whose JAX backend is the CPU is a boot
    failure naming the backend — never a slower engine under the same
    name."""
    import constdb_tpu.conf as conf

    with pytest.raises(RuntimeError, match="requires an accelerator"):
        conf.build_engine("tpu")
    with pytest.raises(ValueError):
        conf.build_engine("tpu!")  # one spelling of strict, not two


def test_info_reports_what_the_engine_runs_on():
    """INFO names the backend beside `engine:` as JAX reports it, so
    what ran is never inferred from the engine's name."""
    import jax

    from constdb_tpu.engine.tpu import TpuMergeEngine

    def fields(node):
        out = _cmd(node, b"info", b"stats").val.decode()
        return dict(line.split(":", 1) for line in out.splitlines()
                    if ":" in line)

    f = fields(Node(node_id=1))
    assert (f["engine"], f["jax_backend"], f["device_kind"],
            f["device_count"]) == ("cpu", "none", "none", "0")
    f = fields(Node(node_id=2, engine=TpuMergeEngine(resident=True)))
    d = jax.devices()
    assert (f["engine"], f["jax_backend"], f["device_kind"],
            f["device_count"]) == ("tpu", d[0].platform, d[0].device_kind,
                                   str(len(d)))
    assert not any("degraded" in k or "fallback" in k for k in f)


def test_info_memory_rss_current_and_peak():
    node = Node(node_id=1)
    out = _cmd(node, b"info", b"memory").val.decode()
    fields = dict(line.split(":", 1) for line in out.splitlines()
                  if ":" in line)
    rss = int(fields["used_memory_rss"])
    peak = int(fields["used_memory_peak"])
    assert 0 < rss <= peak


# ------------------------------------- 5: negative bytes-column length


def test_snapshot_rejects_negative_slot_length():
    from constdb_tpu.persist.snapshot import _read_bytes_list
    from constdb_tpu.utils.varint import VarintReader

    # mixed corruption whose TOTAL is still positive: [-5, +9] → total 2
    # with one slot walking pos backwards — must fail at the section
    lens = np.array([-5, 9], dtype="<i4").tobytes()
    r = VarintReader(lens + b"payloadbytes")
    with pytest.raises(ValueError, match="negative"):
        _read_bytes_list(r, 2)
