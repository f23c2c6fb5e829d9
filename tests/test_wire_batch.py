"""Batch wire protocol (REPLBATCH): codec, push loop, receiver intake.

The load-bearing claims, each pinned here:
  * codec roundtrip is exact — a run group-encoded on the pusher and
    decoded on the receiver lands byte-identically to the per-frame
    path, element key-delete rule included (evaluated against the
    RECEIVING store);
  * the push loop ships runs of consecutive encodable ops as REPLBATCH
    frames, breaks runs at barriers, and degenerates to the byte-exact
    per-frame stream for legacy peers and CONSTDB_WIRE_BATCH=1;
  * every-prefix truncation and every bit flip of a payload raise
    WireFormatError — a batch decodes whole or advances nothing;
  * a malformed payload demotes that peer to per-frame delivery LOUDLY
    (counter + batch_wire_off + the capability disappears from the next
    handshake) without desyncing the stream (watermark untouched);
  * per-batch delivery bookkeeping: duplicate batches skip, gapped
    batches raise ReplicateCommandsLost, the watermark advances only
    after the covering batch lands;
  * MergedReplLog.run_after emits maximal single-segment runs that
    never violate HLC order or cross the floor, and concatenated runs
    replay to the identical per-op stream;
  * the receiver REPLACKs once per landed batch (EVENT_PULL_LANDED),
    with watermark/beacon advancement unchanged vs per-frame acks.
"""

import asyncio
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_link_pushloop import _Writer, _mk_link  # noqa: E402

from constdb_tpu.errors import CstError, ReplicateCommandsLost  # noqa: E402
from constdb_tpu.replica import wire  # noqa: E402
from constdb_tpu.replica.coalesce import CoalescingApplier  # noqa: E402
from constdb_tpu.replica.link import (CAP_BATCH_STREAM,  # noqa: E402
                                      PARTSYNC, REPLACK, REPLBATCH,
                                      REPLICATE, my_caps)
from constdb_tpu.replica.manager import ReplicaMeta  # noqa: E402
from constdb_tpu.resp.codec import encode_msg, make_parser  # noqa: E402
from constdb_tpu.resp.message import (Arr, Bulk, Int, as_bytes,  # noqa: E402
                                      as_int)
from constdb_tpu.server.node import Node  # noqa: E402
from constdb_tpu.server.repl_log import MergedReplLog  # noqa: E402
from constdb_tpu.utils.hlc import SEQ_BITS  # noqa: E402

MS0 = 1_700_000_000_000


def u(i: int) -> int:
    return (MS0 + i) << SEQ_BITS


def mixed_bodies(n: int, seed: int = 3, keys: int = 60):
    """Deterministic op bodies covering every encodable command plus
    barrier classes (the test_coalesce_apply mix, entry-shaped)."""
    rng = random.Random(seed)
    out = []
    for i in range(1, n + 1):
        r = rng.random()
        k = b"k%03d" % rng.randrange(keys)
        if r < 0.22:
            f = (b"set", b"r" + k, b"v%d" % i)
        elif r < 0.40:
            f = (b"cntset", b"c" + k, rng.randrange(-50, 50))
        elif r < 0.56:
            f = (b"sadd", b"s" + k, b"m%d" % rng.randrange(10),
                 b"m%d" % rng.randrange(10))
        elif r < 0.64:
            f = (b"hset", b"h" + k, b"f%d" % rng.randrange(6), b"v%d" % i)
        elif r < 0.70:
            f = (b"srem", b"s" + k, b"m%d" % rng.randrange(10))
        elif r < 0.74:
            f = (b"hdel", b"h" + k, b"f%d" % rng.randrange(6))
        elif r < 0.78:
            f = (b"lins", b"l" + k, b"p%04d" % i, b"val%d" % i)
        elif r < 0.80:
            f = (b"lremat", b"l" + k, b"p%04d" % (i - 1))
        elif r < 0.86:
            f = (b"delbytes", b"r" + k)
        elif r < 0.90:
            f = (b"delcnt", b"c" + k, 7, rng.randrange(50))
        elif r < 0.95:
            f = (b"delset", b"s" + k)       # barrier: breaks runs
        else:
            f = (b"meet", b"10.9.9.%d:7%03d" % (rng.randrange(9), i % 999))
        out.append(f)
    return out


def fill_log(node: Node, bodies) -> list:
    """Push op bodies into the node's repl_log; returns the entries."""
    for i, body in enumerate(bodies, 1):
        args = [Int(a) if isinstance(a, int) else Bulk(a)
                for a in body[1:]]
        node.repl_log.push(u(i), body[0], args)
    return node.repl_log.run_after(0, len(bodies) + 1)


def perframe_reference(entries, origin: int = 7) -> Node:
    """The oracle: every entry applied on the exact per-frame path."""
    node = Node(node_id=99)
    ap = CoalescingApplier(node, ReplicaMeta("oracle:1"), max_frames=1)
    prev = 0
    for e in entries:
        ap.apply([Bulk(b"replicate"), Int(origin), Int(prev), Int(e.uuid),
                  Bulk(e.name), *e.args])
        prev = e.uuid
    ap.flush()
    return node


def scan(buf: bytes):
    """Parse a written stream into (kind, items) tuples."""
    parser = make_parser()
    parser.feed(bytes(buf))
    out = []
    while (msg := parser.next_msg()) is not None:
        items = msg.items if isinstance(msg, Arr) else None
        assert items, f"unexpected frame {msg!r}"
        out.append((as_bytes(items[0]).lower(), items))
    return out


# ------------------------------------------------------------ codec unit


def test_codec_roundtrip_equals_per_frame():
    pusher = Node(node_id=7)
    bodies = [b for b in mixed_bodies(600)
              if b[0] not in (b"delset", b"meet")]  # encodable run
    entries = fill_log(pusher, bodies)
    payload = wire.build_wire_batch(entries, 7)
    assert payload is not None
    n2 = Node(node_id=2)
    wb = wire.decode_wire_batch(payload, n2.ks, 7, entries[0].prev_uuid)
    assert wb.n_frames == len(entries)
    n2.merge_stream_batch(wb, wb.n_frames)
    want = perframe_reference(entries)
    assert n2.canonical() == want.canonical()
    # the wire is the point: columnar payload well under the per-frame
    # RESP bytes for the same run
    per_frame = sum(len(encode_msg(Arr([
        Bulk(b"replicate"), Int(7), Int(e.prev_uuid), Int(e.uuid),
        Bulk(e.name), *e.args]))) for e in entries)
    assert len(payload) * 3 <= per_frame, \
        f"payload {len(payload)}B vs per-frame {per_frame}B"


def test_codec_key_delete_rule_runs_on_receiver():
    """An element add below the RECEIVER's key delete time must land
    tombstoned — the dt rule evaluates against the receiving store."""
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"sadd", b"s1", b"m1"),
                                (b"sadd", b"s2", b"m2")])
    payload = wire.build_wire_batch(entries, 7)

    def receiver_with_delete():
        n = Node(node_id=2)
        ap = CoalescingApplier(n, ReplicaMeta("x:1"), max_frames=1)
        # a LOCAL delete of s1 newer than both adds
        ap.apply([Bulk(b"replicate"), Int(9), Int(0), Int(u(50)),
                  Bulk(b"delset"), Bulk(b"s1")])
        return n

    n_batch = receiver_with_delete()
    wb = wire.decode_wire_batch(payload, n_batch.ks, 7,
                                entries[0].prev_uuid)
    n_batch.merge_stream_batch(wb, wb.n_frames)
    n_frame = receiver_with_delete()
    ap = CoalescingApplier(n_frame, ReplicaMeta("y:1"), max_frames=1)
    prev = 0
    for e in entries:
        ap.apply([Bulk(b"replicate"), Int(7), Int(prev), Int(e.uuid),
                  Bulk(e.name), *e.args])
        prev = e.uuid
    assert n_batch.canonical() == n_frame.canonical()
    canon = n_batch.canonical()
    # s1's add predates the local delete: the member lands tombstoned at
    # the key's delete time; s2's add (no local delete) lands live
    s1_members = canon[b"s1"][5]
    assert any(m[0] == b"m1" and m[3] == u(50) for m in s1_members), \
        s1_members
    s2_members = canon[b"s2"][5]
    assert any(m[0] == b"m2" and m[3] == 0 for m in s2_members), s2_members


def test_unencodable_run_returns_none():
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k1", b"v"),
                                (b"meet", b"10.0.0.1:9")])
    assert wire.build_wire_batch(entries, 7) is None  # KeyError: meet


# ------------------------------------------------------------------ fuzz


def test_every_prefix_truncation_raises():
    pusher = Node(node_id=7)
    bodies = [b for b in mixed_bodies(40, seed=11)
              if b[0] not in (b"delset", b"meet")]
    entries = fill_log(pusher, bodies)
    payload = wire.build_wire_batch(entries, 7)
    ks = Node(node_id=2).ks
    base = entries[0].prev_uuid
    for cut in range(len(payload)):
        with pytest.raises(wire.WireFormatError):
            wire.decode_wire_batch(payload[:cut], ks, 7, base)


def test_every_bit_flip_raises():
    """crc32 integrity: ANY single-byte corruption fails the decode
    loudly (sampling every byte position, one flip each)."""
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v%d" % i)
                                for i in range(20)])
    payload = bytearray(wire.build_wire_batch(entries, 7))
    ks = Node(node_id=2).ks
    base = entries[0].prev_uuid
    for pos in range(len(payload)):
        payload[pos] ^= 0x5A
        with pytest.raises(wire.WireFormatError):
            wire.decode_wire_batch(bytes(payload), ks, 7, base)
        payload[pos] ^= 0x5A
    # the restored payload still decodes (the loop really was the flip)
    wire.decode_wire_batch(bytes(payload), ks, 7, base)


def test_trailing_garbage_raises():
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k1", b"v"), (b"set", b"k2", b"w")])
    payload = wire.build_wire_batch(entries, 7)
    ks = Node(node_id=2).ks
    with pytest.raises(wire.WireFormatError):
        wire.decode_wire_batch(payload + b"x", ks, 7, entries[0].prev_uuid)


# --------------------------------------------------------- receiver intake


def batch_frame(entries, origin: int = 7):
    payload = wire.build_wire_batch(entries, origin)
    assert payload is not None
    return [Bulk(REPLBATCH), Int(origin), Int(entries[0].prev_uuid),
            Int(entries[-1].uuid), Int(len(entries)), Bulk(payload)]


def test_batch_dup_gap_and_watermark_after_land():
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v%d" % i)
                                for i in range(16)])
    a, b = entries[:8], entries[8:]
    node = Node(node_id=2)
    meta = ReplicaMeta("peer:1")
    ap = CoalescingApplier(node, meta, max_frames=64)
    ap.apply_wire_batch(batch_frame(a))
    assert meta.uuid_he_sent == a[-1].uuid  # landed => watermark covers it
    assert node.stats.repl_wire_batches_in == 1
    # duplicate redelivery: skipped whole, nothing re-merged
    flushes = node.stats.repl_coalesce_flushes
    ap.apply_wire_batch(batch_frame(a))
    assert node.stats.repl_coalesce_flushes == flushes
    assert meta.uuid_he_sent == a[-1].uuid
    # a gapped batch tears the stream down exactly like a gapped frame
    with pytest.raises(ReplicateCommandsLost):
        ap.apply_wire_batch(batch_frame(b[2:]))
    assert meta.uuid_he_sent == a[-1].uuid
    # the covering batch lands and the watermark follows
    ap.apply_wire_batch(batch_frame(b))
    assert meta.uuid_he_sent == b[-1].uuid
    assert node.canonical() == perframe_reference(entries).canonical()


def test_malformed_payload_demotes_loudly():
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v") for i in range(6)])
    frame = batch_frame(entries)
    frame[5] = Bulk(as_bytes(frame[5])[:-3] + b"zzz")  # corrupt payload
    node = Node(node_id=2)
    meta = ReplicaMeta("peer:1")
    ap = CoalescingApplier(node, meta, max_frames=64)
    with pytest.raises(CstError):
        ap.apply_wire_batch(frame)
    assert node.stats.repl_wire_demotions == 1
    assert meta.batch_wire_off is True
    assert meta.uuid_he_sent == 0, "a bad batch must not advance anything"
    # the next handshake stops inviting batches from this peer
    class _App:
        pass
    assert not (my_caps(_App(), meta) & CAP_BATCH_STREAM)
    assert my_caps(_App()) & CAP_BATCH_STREAM
    # the stream itself is not poisoned: per-frame redelivery lands
    prev = 0
    for e in entries:
        ap.apply([Bulk(b"replicate"), Int(7), Int(prev), Int(e.uuid),
                  Bulk(e.name), *e.args])
        prev = e.uuid
    ap.flush()
    assert meta.uuid_he_sent == entries[-1].uuid


def test_header_payload_frame_count_mismatch_is_malformed():
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v") for i in range(4)])
    frame = batch_frame(entries)
    frame[4] = Int(3)  # header lies about n
    node = Node(node_id=2)
    ap = CoalescingApplier(node, ReplicaMeta("peer:1"), max_frames=64)
    with pytest.raises(CstError):
        ap.apply_wire_batch(frame)
    assert node.stats.repl_wire_demotions == 1


# ------------------------------------------------------------- push loop


def drive_pushloop(tmp_path, bodies, peer_caps, app_tweaks=None,
                   rounds=400):
    """Run a real _push_loop over a filled log into a stub writer until
    the stream covers the last uuid; returns (node, writer, frames)."""
    async def main():
        node, app, link = _mk_link(tmp_path)
        for k, v in (app_tweaks or {}).items():
            setattr(app, k, v)
        last = 0
        for i, body in enumerate(bodies, 1):
            args = [Int(a) if isinstance(a, int) else Bulk(a)
                    for a in body[1:]]
            node.repl_log.push(u(i), body[0], args)
            last = u(i)
        link._peer_caps = peer_caps
        writer = _Writer()
        task = asyncio.create_task(link._push_loop(writer, peer_resume=0))
        try:
            for _ in range(rounds):
                await asyncio.sleep(0.01)
                frames = scan(writer.buf)
                covered = 0
                for kind, items in frames:
                    if kind == REPLICATE:
                        covered = as_int(items[3])
                    elif kind == REPLBATCH:
                        covered = as_int(items[3])
                if covered >= last:
                    break
        finally:
            task.cancel()
        return node, writer, scan(writer.buf)
    return asyncio.run(main())


def replay_stream_frames(frames, origin=1) -> Node:
    """Feed a scanned wire stream through a receiver applier."""
    node = Node(node_id=55)
    ap = CoalescingApplier(node, ReplicaMeta("rcv:1"), max_frames=64)
    for kind, items in frames:
        if kind == REPLICATE:
            ap.apply(items)
        elif kind == REPLBATCH:
            ap.apply_wire_batch(items)
        elif kind in (PARTSYNC, REPLACK):
            pass
        else:
            raise AssertionError(f"unexpected frame {kind!r}")
    ap.flush()
    return node


def test_pushloop_ships_runs_as_batches(tmp_path):
    bodies = mixed_bodies(500, seed=5)
    node, writer, frames = drive_pushloop(tmp_path, bodies,
                                          CAP_BATCH_STREAM)
    kinds = [k for k, _ in frames]
    assert REPLBATCH in kinds
    st = node.stats
    assert st.repl_wire_batches_out == kinds.count(REPLBATCH)
    assert st.repl_wire_batch_frames_out > kinds.count(REPLICATE)
    assert st.repl_wire_bytes_out > 0
    assert st.extra.get("repl_wire_encode_demotions", 0) == 0
    # barriers (delset/meet) broke runs and shipped per-frame (lone
    # encodable ops stranded between barriers legitimately do too)
    perframe_names = {as_bytes(items[4]).lower()
                      for k, items in frames if k == REPLICATE}
    assert {b"delset", b"meet"} <= perframe_names, perframe_names
    # the receiver lands the stream identically to the per-frame oracle
    # (origin = the pushing node's id, exactly what the wire stamps)
    got = replay_stream_frames(frames)
    entries = node.repl_log.run_after(0, len(bodies) + 1)
    want = perframe_reference(entries, origin=node.node_id)
    assert got.canonical() == want.canonical()


def test_legacy_peer_stream_is_byte_exact(tmp_path):
    """peer_caps without CAP_BATCH_STREAM: the wire opens with the exact
    pre-PR per-frame byte stream — PARTSYNC then every entry as a plain
    REPLICATE frame, byte for byte."""
    bodies = mixed_bodies(120, seed=9)
    node, writer, frames = drive_pushloop(tmp_path, bodies, peer_caps=0)
    want = bytearray(encode_msg(Arr([Bulk(PARTSYNC)])))
    for e in node.repl_log.run_after(0, len(bodies) + 1):
        want += encode_msg(Arr([
            Bulk(REPLICATE), Int(node.node_id), Int(e.prev_uuid),
            Int(e.uuid), Bulk(e.name), *e.args]))
    assert bytes(writer.buf[:len(want)]) == bytes(want)
    assert node.stats.repl_wire_batches_out == 0


def test_wire_batch_one_degenerates(tmp_path):
    """CONSTDB_WIRE_BATCH=1 (app.wire_batch=1): per-frame stream even
    for a capable peer, and the capability is not advertised."""
    bodies = mixed_bodies(80, seed=2)
    node, writer, frames = drive_pushloop(
        tmp_path, bodies, CAP_BATCH_STREAM, app_tweaks={"wire_batch": 1})
    assert all(k != REPLBATCH for k, _ in frames)
    assert node.stats.repl_wire_batches_out == 0

    class _App:
        wire_batch = 1
    assert not (my_caps(_App()) & CAP_BATCH_STREAM)


def test_apply_batch_one_withholds_the_capability():
    """CONSTDB_APPLY_BATCH=1 pins the whole replication intake to the
    per-frame apply path — inviting REPLBATCH frames would route ops
    through the columnar merge engine the pin exists to bypass."""
    class _App:
        apply_batch = 1
    assert not (my_caps(_App()) & CAP_BATCH_STREAM)

    class _Capable:
        apply_batch = 512
    assert my_caps(_Capable()) & CAP_BATCH_STREAM


def test_run_after_byte_cap():
    """A backlog of huge values must not balloon one wire frame: the
    run cuts at the byte cap (but always carries >= 1 entry)."""
    node = Node(node_id=1, repl_log_cap=1 << 30)
    big = b"x" * (1 << 16)
    for i in range(1, 33):
        node.repl_log.push(u(i), b"set", [Bulk(b"k%d" % i), Bulk(big)])
    run = node.repl_log.run_after(0, 512, 1 << 18)
    assert 1 <= len(run) <= 4  # ~64KB entries under a 256KB cap
    # a lone oversized entry still ships whole
    assert len(node.repl_log.run_after(0, 512, 16)) == 1
    # uncapped behavior is unchanged
    assert len(node.repl_log.run_after(0, 512)) == 32


# ---------------------------------------------- merged-log run extraction


def test_merged_log_run_extraction_property():
    """MergedReplLog.run_after: runs are single-segment, never out of
    HLC order, never cross the floor, and concatenated runs replay to
    the identical per-op stream (satellite: run-extraction property)."""
    rng = random.Random(17)
    for trial in range(20):
        n_shards = rng.randrange(1, 5)
        merged = MergedReplLog(n_shards, cap_bytes=1 << 24)
        uuids = []
        for i in range(1, rng.randrange(50, 300)):
            seg = rng.randrange(n_shards + 1)
            merged.segments[seg].push(u(i), b"set",
                                      [Bulk(b"k%d" % i), Bulk(b"v")])
            uuids.append(u(i))
        floor_val = [None]
        merged.floor = lambda: floor_val[0]
        if rng.random() < 0.5:
            floor_val[0] = uuids[rng.randrange(len(uuids))]
        # oracle: the per-op merged stream under the same floor
        expected = []
        cursor = 0
        while (e := merged.next_after(cursor)) is not None:
            expected.append(e.uuid)
            cursor = e.uuid
        # extraction: concatenated runs with random caps
        got = []
        cursor = 0
        while True:
            run = merged.run_after(cursor, rng.randrange(1, 40))
            if not run:
                # a run bounded to zero length by another segment's next
                # entry still has a nonempty per-op stream — but only
                # the FLOOR can bound the FIRST entry away
                assert merged.next_after(cursor) is None
                break
            segs = {id(s) for s in merged.segments
                    if any(s.at(e.uuid) is not None for e in run)}
            assert len(segs) == 1, "run spans segments"
            for e in run:
                assert e.uuid > cursor, "run out of HLC order"
                if floor_val[0] is not None:
                    assert e.uuid < floor_val[0], "run crossed the floor"
                cursor = e.uuid
            got.extend(e.uuid for e in run)
        assert got == expected, f"trial {trial}: replay diverged"


def test_merged_log_runs_interleave_in_hlc_order():
    """Two segments with interleaved uuids: no run may contain an entry
    newer than another segment's pending one."""
    merged = MergedReplLog(1, cap_bytes=1 << 24)
    s0, s1 = merged.segments[0], merged.segments[1]
    s0.push(u(1), b"set", [Bulk(b"a"), Bulk(b"v")])
    s0.push(u(2), b"set", [Bulk(b"b"), Bulk(b"v")])
    s1.push(u(3), b"set", [Bulk(b"c"), Bulk(b"v")])
    s0.push(u(4), b"set", [Bulk(b"d"), Bulk(b"v")])
    run = merged.run_after(0, 100)
    assert [e.uuid for e in run] == [u(1), u(2)]  # bounded by s1's u(3)
    run = merged.run_after(u(2), 100)
    assert [e.uuid for e in run] == [u(3)]
    run = merged.run_after(u(3), 100)
    assert [e.uuid for e in run] == [u(4)]


# ------------------------------------------------------- REPLACK batching


def test_replack_once_per_landed_batch(tmp_path):
    """The receiver acks once per covering land (EVENT_PULL_LANDED
    wake), not per frame and not a heartbeat later — and the watermark
    it acks matches the per-frame applier's advancement exactly."""
    async def main():
        node, app, link = _mk_link(tmp_path)
        app.heartbeat = 30.0  # isolate event-driven acks from heartbeats
        meta = link.meta
        writer = _Writer()
        task = asyncio.create_task(link._push_loop(writer, peer_resume=0))

        async def acks_at_least(n: int) -> int:
            for _ in range(400):
                got = sum(1 for k, _ in scan(writer.buf) if k == REPLACK)
                if got >= n:
                    return got
                await asyncio.sleep(0.01)
            raise AssertionError(f"never saw {n} REPLACKs")

        base_acks = await acks_at_least(1)  # initial ack (last_ack=0)

        pusher = Node(node_id=7)
        entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v%d" % i)
                                    for i in range(64)])
        ap = CoalescingApplier(node, meta, max_frames=512,
                               max_latency=999.0)
        # per-frame twin for the watermark-equivalence pin
        twin_node = Node(node_id=8)
        twin_meta = ReplicaMeta("twin:1")
        twin = CoalescingApplier(twin_node, twin_meta, max_frames=1)
        acks_seen = []
        for lo, hi in ((0, 32), (32, 64)):
            prev = entries[lo].prev_uuid
            for e in entries[lo:hi]:
                f = [Bulk(b"replicate"), Int(7), Int(prev), Int(e.uuid),
                     Bulk(e.name), *e.args]
                ap.apply(f)
                twin.apply(f)
                prev = e.uuid
            ap.flush()  # ONE land covering the 32-frame window
            assert meta.uuid_he_sent == twin_meta.uuid_he_sent == \
                entries[hi - 1].uuid
            n_acks = await acks_at_least(len(acks_seen) + base_acks + 1)
            acks = [items for k, items in scan(writer.buf)
                    if k == REPLACK]
            acks_seen.append(len(acks))
            assert as_int(acks[-1][1]) == entries[hi - 1].uuid
            assert n_acks >= len(acks_seen) + base_acks
        task.cancel()
        # one ack per landed batch (not per frame): exactly two more
        # than the baseline after two lands (the 30s heartbeat cannot
        # have contributed)
        assert acks_seen[-1] - base_acks == 2, \
            f"expected 2 batch acks, saw {acks_seen[-1] - base_acks}"
    asyncio.run(main())


def test_beacon_handling_unchanged_with_wire_batches():
    """A drained-stream beacon stashed during a wire batch applies
    after the covering land, exactly like the per-frame path."""
    pusher = Node(node_id=7)
    entries = fill_log(pusher, [(b"set", b"k%d" % i, b"v") for i in range(8)])
    beacon = entries[-1].uuid + (10 << SEQ_BITS)
    node = Node(node_id=2)
    meta = ReplicaMeta("peer:1")
    ap = CoalescingApplier(node, meta, max_frames=512, max_latency=999.0)
    # frames pending -> beacon must stash, not advance
    prev = 0
    for e in entries[:4]:
        ap.apply([Bulk(b"replicate"), Int(7), Int(prev), Int(e.uuid),
                  Bulk(e.name), *e.args])
        prev = e.uuid
    ap.observe_beacon(beacon)
    assert meta.uuid_he_sent == 0
    # the wire batch flushes the pending window first, lands, and the
    # stashed beacon advances with it
    ap.apply_wire_batch(batch_frame(entries[4:]))
    assert meta.uuid_he_sent == beacon
    assert ap.cursor == beacon
