"""Negotiated replication compression (CAP_COMPRESS) + the container.

The load-bearing claims, each pinned here:
  * the chunked framing (utils/compressio.py) roundtrips exactly under
    every alg/filter combination, and EVERY structural defect —
    truncation, bit flips across the whole container, trailing garbage
    — raises CompressFormatError (a consumer never acts on bytes it
    could not fully validate);
  * the push loop compresses REPLBATCH payloads only over the floor and
    only for peers that advertised CAP_COMPRESS — a batch-only peer's
    payloads are the byte-exact plain encoding;
  * the receiver lands a compressed stream identically to the per-frame
    oracle, and a malformed compressed payload demotes that peer LOUDLY
    (repl_wire_demotions + compress_wire_off + the capability disappears
    from the next handshake) with the watermark untouched;
  * the compressed snapshot container roundtrips through dump/load,
    pre-PR plain files stay loadable, and a corrupt container is
    quarantined as InvalidSnapshot;
  * the shared full-sync dump produces at most one file per variant,
    and the compressed variant really is the container.
"""

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_link_pushloop import _mk_link  # noqa: E402
from test_wire_batch import (drive_pushloop, mixed_bodies,  # noqa: E402
                             perframe_reference, replay_stream_frames, u)

from constdb_tpu.errors import CstError, InvalidSnapshot  # noqa: E402
from constdb_tpu.persist.snapshot import (NodeMeta,  # noqa: E402
                                          dump_keyspace, load_snapshot)
from constdb_tpu.replica.coalesce import CoalescingApplier  # noqa: E402
from constdb_tpu.replica.link import (CAP_BATCH_STREAM,  # noqa: E402
                                      CAP_COMPRESS, REPLBATCH, REPLICATE,
                                      my_caps)
from constdb_tpu.replica.manager import ReplicaMeta  # noqa: E402
from constdb_tpu.resp.message import (Arr, Bulk, Int,  # noqa: E402
                                      as_bytes)
from constdb_tpu.server.node import Node  # noqa: E402
from constdb_tpu.server.repl_log import ReplEntry  # noqa: E402
from constdb_tpu.store.keyspace import KeySpace  # noqa: E402
from constdb_tpu.utils import compressio as zio  # noqa: E402

CAPS_Z = CAP_BATCH_STREAM | CAP_COMPRESS


# --------------------------------------------------------------- framing


@pytest.mark.parametrize("alg", ["zlib", "lzma"])
@pytest.mark.parametrize("filt", ["none", "transpose", "auto"])
def test_framing_roundtrip(alg, filt):
    data = bytes(range(256)) * 3000 + b"odd-tail"
    c = zio.compress_bytes(data, level=6, filt=filt, alg=alg)
    assert zio.decompress_bytes(c) == data
    assert zio.is_compressed(c)
    # empty payload roundtrips too (zero chunks)
    assert zio.decompress_bytes(
        zio.compress_bytes(b"", alg=alg)) == b""


def test_framing_rejects_every_defect():
    data = os.urandom(512) + bytes(5000)
    c = zio.compress_bytes(data, level=1, filt="auto", alg="lzma")
    # every byte position flipped must be caught (magic, alg, chunk
    # headers, payload, end marker)
    for pos in range(len(c)):
        bad = bytearray(c)
        bad[pos] ^= 0xFF
        with pytest.raises(zio.CompressFormatError):
            zio.decompress_bytes(bytes(bad))
    # every truncation point
    for cut in range(len(c)):
        with pytest.raises(zio.CompressFormatError):
            zio.decompress_bytes(c[:cut])
    with pytest.raises(zio.CompressFormatError):
        zio.decompress_bytes(c + b"x")
    with pytest.raises(zio.CompressFormatError):
        zio.decompress_bytes(c, max_raw=len(data) - 1)


# ------------------------------------------------------------- push side


def test_pushloop_compresses_over_the_floor(tmp_path):
    bodies = [(b"set", b"r%03d" % (i % 40), b"v" * 64)
              for i in range(400)]
    node, writer, frames = drive_pushloop(
        tmp_path, bodies, CAPS_Z, app_tweaks={"wire_compress_min": 64})
    payloads = [as_bytes(items[5]) for k, items in frames
                if k == REPLBATCH]
    assert payloads, "no batches shipped"
    assert any(zio.is_compressed(p) for p in payloads), \
        "no payload compressed over the floor"
    st = node.stats
    assert st.repl_comp_raw_bytes > st.repl_comp_wire_bytes > 0
    # the receiver lands the compressed stream identically to the
    # per-frame oracle
    got = replay_stream_frames(frames)
    entries = node.repl_log.run_after(0, len(bodies) + 1)
    want = perframe_reference(entries, origin=node.node_id)
    assert got.canonical() == want.canonical()


def test_floor_and_capability_gate_compression(tmp_path):
    bodies = [(b"set", b"r%03d" % (i % 40), b"v" * 64)
              for i in range(200)]
    # huge floor: nothing compresses even for a capable peer
    node, _, frames = drive_pushloop(
        tmp_path, bodies, CAPS_Z,
        app_tweaks={"wire_compress_min": 1 << 30})
    assert all(not zio.is_compressed(as_bytes(items[5]))
               for k, items in frames if k == REPLBATCH)
    assert node.stats.repl_comp_wire_bytes == 0
    # batch-only peer: plain payloads regardless of the floor
    node2, _, frames2 = drive_pushloop(
        tmp_path, bodies, CAP_BATCH_STREAM,
        app_tweaks={"wire_compress_min": 1})
    assert all(not zio.is_compressed(as_bytes(items[5]))
               for k, items in frames2 if k == REPLBATCH)


def test_kill_switch_withholds_capability():
    class _On:
        pass

    class _Off:
        wire_compress = False
    assert my_caps(_On()) & CAP_COMPRESS
    assert not (my_caps(_Off()) & CAP_COMPRESS)
    # a peer that shipped a malformed compressed frame is pinned plain
    meta = ReplicaMeta("p:1")
    meta.compress_wire_off = True
    assert not (my_caps(_On(), meta) & CAP_COMPRESS)


# ---------------------------------------------------------- receive side


def _compressed_batch_frame(node):
    """A valid REPLBATCH frame whose payload is compressed."""
    from constdb_tpu.replica import wire
    entries = []

    prev = 0
    for i in range(1, 9):
        e = ReplEntry(u(i), prev, b"set",
                      [Bulk(b"k%d" % i), Bulk(b"v" * 64)], 0)
        prev = e.uuid
        entries.append(e)
    payload = wire.build_wire_batch(entries, 7)
    assert payload is not None
    z = zio.compress_bytes(payload, level=1)
    return [Bulk(b"replbatch"), Int(7), Int(0), Int(entries[-1].uuid),
            Int(len(entries)), Bulk(z)], entries


def test_compressed_batch_applies_and_corrupt_demotes_loudly():
    frame, entries = _compressed_batch_frame(None)
    node = Node(node_id=2)
    meta = ReplicaMeta("peer:1")
    ap = CoalescingApplier(node, meta, max_frames=64)
    ap.apply_wire_batch(frame)
    assert meta.uuid_he_sent == entries[-1].uuid
    assert node.stats.extra.get("repl_comp_batches_in") == 1
    want = perframe_reference(entries, origin=7)
    assert node.canonical() == want.canonical()

    # corrupt INSIDE the compressed payload: loud demotion, watermark
    # untouched, capability withdrawn from the next handshake
    frame2, entries2 = _compressed_batch_frame(None)
    z = bytearray(as_bytes(frame2[5]))
    z[len(z) // 2] ^= 0xFF
    frame2[5] = Bulk(bytes(z))
    node2 = Node(node_id=3)
    meta2 = ReplicaMeta("peer:2")
    ap2 = CoalescingApplier(node2, meta2, max_frames=64)
    with pytest.raises(CstError):
        ap2.apply_wire_batch(frame2)
    st = node2.stats
    assert st.repl_wire_demotions == 1
    assert st.extra.get("repl_compress_demotions") == 1
    assert meta2.compress_wire_off
    assert not meta2.batch_wire_off  # the BATCH layer stays negotiated
    assert meta2.uuid_he_sent == 0   # watermark untouched
    assert node2.ks.n_keys() == 0    # nothing partially applied

    class _App:
        pass
    assert not (my_caps(_App(), meta2) & CAP_COMPRESS)
    assert my_caps(_App(), meta2) & CAP_BATCH_STREAM


# ----------------------------------------------------- snapshot container


def _filled_node(n=300):
    node = Node(node_id=1)
    for i in range(n):
        uu = node.hlc.tick(True)
        kid, _ = node.ks.get_or_create(b"key%06d" % i, 1, uu)
        node.ks.register_set(kid, b"val%06d" % i, uu, 1)
    return node


def test_container_dump_roundtrip_and_quarantine(tmp_path):
    node = _filled_node()
    plain = os.path.join(str(tmp_path), "plain.snapshot")
    comp = os.path.join(str(tmp_path), "z.snapshot")
    s_plain = dump_keyspace(plain, node.ks, NodeMeta(node_id=1))
    s_comp = dump_keyspace(comp, node.ks, NodeMeta(node_id=1),
                           container_level=6)
    with open(comp, "rb") as f:
        assert zio.is_compressed(f.read(8))
    with open(plain, "rb") as f:
        assert not zio.is_compressed(f.read(8))
    canons = []
    for p in (plain, comp):
        ks = KeySpace()
        load_snapshot(p, ks)  # loader sniffs the magic — both formats
        canons.append(ks.canonical())
    assert canons[0] == canons[1] == node.ks.canonical()
    assert s_comp < s_plain  # the container actually pays

    # a flipped byte inside the container quarantines as InvalidSnapshot
    data = bytearray(open(comp, "rb").read())
    data[len(data) // 2] ^= 0xFF
    bad = os.path.join(str(tmp_path), "bad.snapshot")
    open(bad, "wb").write(bytes(data))
    with pytest.raises(InvalidSnapshot):
        load_snapshot(bad, KeySpace())


def test_shared_dump_variants(tmp_path):
    """One dump per VARIANT: a mixed-capability mesh costs at most two
    files, and each is reused while the log covers its watermark."""
    import types

    from constdb_tpu.persist.share import SharedDump

    node = _filled_node(100)
    app = types.SimpleNamespace(node=node, work_dir=str(tmp_path),
                                advertised_addr="t:1",
                                snapshot_chunk_keys=1 << 16,
                                snapshot_compress_level=1)

    async def main():
        sd = SharedDump(app)
        d_plain = await sd.acquire(compressed=False)
        d_comp = await sd.acquire(compressed=True)
        assert sd.dumps_taken == 2
        # reuse: same variant, no new dump
        assert (await sd.acquire(compressed=False)).path == d_plain.path
        assert (await sd.acquire(compressed=True)).path == d_comp.path
        assert sd.dumps_taken == 2
        with open(d_comp.path, "rb") as f:
            assert zio.is_compressed(f.read(8))
        with open(d_plain.path, "rb") as f:
            assert not zio.is_compressed(f.read(8))
        assert d_comp.size < d_plain.size
    asyncio.run(main())


# ------------------------------------------------------------ e2e fullsync


def test_compressed_fullsync_on_the_wire(tmp_path):
    """A fenced pusher full-syncs a CAP_COMPRESS peer: the streamed
    window IS the compressed container, and the peer converges."""
    from cluster_util import Client, close_cluster, converge, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path))
        try:
            a, b = apps
            c = await Client().connect(a.advertised_addr)
            for i in range(300):
                await c.cmd("set", f"key:{i:06d}", "v" * 64)
            top = a.node.repl_log.last_uuid
            a.node.repl_log.evicted_up_to = top  # force FULLSYNC
            await c.cmd("meet", b.advertised_addr)
            await converge(apps, timeout=20.0)
            assert a.node.stats.repl_full_syncs >= 1
            assert "last_snapshot_z_bytes" in a.node.stats.extra
            got = await c.cmd("get", "key:000299")
            assert got == Bulk(b"v" * 64)
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_info_broadcast_gauges(tmp_path):
    """Satellite: per-peer wire observability — replica<i> rows carry
    bytes_out / compressed_ratio / cache counts, and the node-level
    encode-cache + compression gauges ride the stats section."""
    from cluster_util import (Client, close_cluster, converge, full_mesh,
                              make_cluster)
    from constdb_tpu.resp.codec import encode_msg

    async def main():
        apps = await make_cluster(3, str(tmp_path),
                                  wire_compress_min=64)
        try:
            c = await Client().connect(apps[0].advertised_addr)
            await c.cmd("meet", apps[1].advertised_addr)
            await c.cmd("meet", apps[2].advertised_addr)
            # both push loops stream before the run lands (a write that
            # beats a link's full sync reaches that peer in the snapshot,
            # and there is no second reader to share an encoding with)
            await full_mesh(apps)
            # a pipelined chunk logs one consecutive run, so BOTH push
            # loops drain the same cursor range (encode-once food)
            buf = bytearray()
            for i in range(300):
                buf += encode_msg(Arr([Bulk(b"set"),
                                       Bulk(b"k%d" % (i % 16)),
                                       Bulk(b"v" * 48)]))
            c.writer.write(bytes(buf))
            await c.writer.drain()
            got = 0
            while got < 300:
                if c.parser.next_msg() is not None:
                    got += 1
                    continue
                data = await asyncio.wait_for(c.reader.read(1 << 16), 10)
                if not data:
                    raise ConnectionError("EOF")
                c.parser.feed(data)
            await converge(apps, timeout=20.0)
            st = apps[0].node.stats
            assert st.repl_comp_wire_bytes > 0, "stream never compressed"
            assert st.repl_encode_cache_hits > 0, \
                "fan-out never reused an encoding"
            info = (await c.cmd("info", "stats")).val
            for gauge in (b"repl_encode_cache_hits",
                          b"repl_encode_cache_misses",
                          b"repl_encode_cache_bytes",
                          b"repl_compress_ratio"):
                assert gauge in info, gauge
            info = (await c.cmd("info", "replication")).val
            for field in (b"bytes_out=", b"compressed_ratio=",
                          b"cache_hits=", b"cache_misses="):
                assert field in info, field
            await c.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())
