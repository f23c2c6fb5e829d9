"""Device-resident merge state: streaming chunk merges must equal the CPU
engine after flush, survive interleaved op-path writes, and fall back
correctly when a family takes the scatter path."""

import numpy as np
import pytest

from constdb_tpu.engine.base import ColumnarBatch, batch_from_keyspace
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.persist.snapshot import batch_chunks
from constdb_tpu.resp.message import Bulk, NIL
from constdb_tpu.server.node import Node
from constdb_tpu.store.keyspace import KeySpace

from test_merge_properties import gen_store


def _cmd(node, *parts):
    return node.execute([Bulk(p if isinstance(p, bytes) else str(p).encode())
                         for p in parts])


def chunked(ks, chunk_keys=29):
    return list(batch_chunks(batch_from_keyspace(ks), chunk_keys))


@pytest.mark.parametrize("seed", range(4))
def test_streaming_chunks_match_cpu(seed):
    """Apply R replicas' snapshots chunk-by-chunk (the replica link's real
    access pattern) through a resident engine; flushed state must equal the
    CPU engine fed the same chunks."""
    srcs = [gen_store(seed=seed * 10 + i, node=i + 1) for i in range(3)]
    all_chunks = [c for src in srcs for c in chunked(src)]

    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for c in all_chunks:
        cpu.merge(cpu_store, c)

    res_store = KeySpace()
    eng = TpuMergeEngine(resident=True)
    for c in all_chunks:
        eng.merge(res_store, c)
    assert eng.needs_flush
    eng.flush(res_store)
    assert not eng.needs_flush
    assert res_store.canonical() == cpu_store.canonical()
    # flush is idempotent and a second flush with no merges is a no-op
    eng.flush(res_store)
    assert res_store.canonical() == cpu_store.canonical()


def test_interleaved_op_writes():
    """Node-level: op-path writes between resident merges see flushed state
    and invalidate the device mirror safely."""
    src = Node(node_id=2)
    for i in range(60):
        _cmd(src, b"incr", b"c%d" % (i % 7))
        _cmd(src, b"sadd", b"s%d" % (i % 5), b"m%d" % i)
        _cmd(src, b"set", b"r%d" % (i % 3), b"v%d" % i)

    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    chunks = chunked(src.ks, chunk_keys=7)
    half = len(chunks) // 2
    for c in chunks[:half]:
        node.merge_batch(c)
    # reads flush lazily; writes bump the keyspace version
    assert node.engine.needs_flush
    _cmd(node, b"incr", b"c0")
    assert not node.engine.needs_flush  # execute() flushed first
    _cmd(node, b"sadd", b"s0", b"extra")
    for c in chunks[half:]:
        node.merge_batch(c)
    node.ensure_flushed()

    # oracle: CPU node fed the same sequence
    ref = Node(node_id=1)
    for c in chunks[:half]:
        ref.merge_batch(c)
    _cmd(ref, b"incr", b"c0")
    _cmd(ref, b"sadd", b"s0", b"extra")
    for c in chunks[half:]:
        ref.merge_batch(c)
    # uuids minted by the two nodes differ (wall clock) — compare values
    for key in (b"c%d" % i for i in range(7)):
        assert _cmd(node, b"get", key) == _cmd(ref, b"get", key)
    got = _cmd(node, b"smembers", b"s0")
    want = _cmd(ref, b"smembers", b"s0")
    assert {m.val for m in got.items} == {m.val for m in want.items}


def test_scatter_fallback_drops_mirror():
    """A non-unique (op-stream) batch takes the scatter path; resident
    mirrors must flush+drop so host state stays authoritative."""
    src = gen_store(seed=3, node=1)
    eng = TpuMergeEngine(resident=True)
    store = KeySpace()
    for c in chunked(src):
        eng.merge(store, c)
    assert eng.needs_flush

    # craft a duplicate-slot batch (same key twice)
    b = ColumnarBatch()
    b.rows_unique_per_slot = False
    b.keys = [b"dup", b"dup"]
    b.key_enc = np.array([3, 3], dtype=np.int8)  # ENC_BYTES
    b.key_ct = np.array([5 << 22, 6 << 22], dtype=np.int64)
    b.key_mt = np.array([5 << 22, 6 << 22], dtype=np.int64)
    b.key_dt = np.zeros(2, dtype=np.int64)
    b.key_expire = np.zeros(2, dtype=np.int64)
    b.reg_val = [b"a", b"b"]
    b.reg_t = np.array([5 << 22, 6 << 22], dtype=np.int64)
    b.reg_node = np.array([1, 1], dtype=np.int64)
    eng.merge(store, b)

    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for c in chunked(src):
        cpu.merge(cpu_store, c)
    cpu.merge(cpu_store, b)
    eng.flush(store)
    assert store.canonical() == cpu_store.canonical()
    kid = store.lookup(b"dup")
    assert store.register_get(kid) == b"b"


def test_gc_compaction_invalidates_resident_mirror():
    """gc() and element compaction reorder/shrink the element table; a
    resident engine that kept its device mirror would flush stale
    add_t/add_node/del_t over the compacted rows.  KeySpace.version must
    bump so the next merge re-uploads from the host."""
    src = Node(node_id=2)
    for i in range(40):
        _cmd(src, b"sadd", b"s%d" % (i % 4), b"m%d" % i)

    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    ref = Node(node_id=1)  # oracle: CPU engine, same op sequence
    for c in chunked(src.ks, chunk_keys=11):
        node.merge_batch(c)
        ref.merge_batch(c)
    node.ensure_flushed()

    # tombstone half the members, collect them, and force the compaction
    # path (row REORDER) regardless of the production thresholds
    for i in range(0, 40, 2):
        _cmd(node, b"srem", b"s%d" % (i % 4), b"m%d" % i)
        _cmd(ref, b"srem", b"s%d" % (i % 4), b"m%d" % i)
    v0 = node.ks.version
    assert node.gc() > 0
    assert node.ks.version > v0
    node.ks._compact_elements()
    ref.gc()
    ref.ks._compact_elements()

    src2 = Node(node_id=3)
    for i in range(40):
        _cmd(src2, b"sadd", b"s%d" % (i % 4), b"n%d" % i)
    for c in chunked(src2.ks, chunk_keys=11):
        node.merge_batch(c)
        ref.merge_batch(c)
    node.ensure_flushed()

    for s in range(4):
        got = _cmd(node, b"smembers", b"s%d" % s)
        want = _cmd(ref, b"smembers", b"s%d" % s)
        assert {m.val for m in got.items} == {m.val for m in want.items}


def test_resident_grows_across_merges():
    """State arrays grow (neutral-filled) as later chunks add new slots."""
    eng = TpuMergeEngine(resident=True)
    store = KeySpace()
    src1 = gen_store(seed=11, node=1)
    src2 = gen_store(seed=12, node=2)
    for c in chunked(src1, chunk_keys=13):
        eng.merge(store, c)
    for c in chunked(src2, chunk_keys=13):
        eng.merge(store, c)
    eng.flush(store)

    cpu_store = KeySpace()
    cpu = CpuMergeEngine()
    for src in (src1, src2):
        cpu.merge(cpu_store, batch_from_keyspace(src))
    assert store.canonical() == cpu_store.canonical()


def test_mixed_traffic_rebuilds_stay_per_family():
    """Interleaving op-path writes with streaming chunk merges must only
    rebuild the mirrors of the planes the ops touched — a counter INCR
    between element-heavy chunks cannot re-upload the element table
    (VERDICT r3 item 6: uploads stay O(families), not O(ops))."""
    src = Node(node_id=2)
    for i in range(200):
        _cmd(src, b"sadd", b"s%d" % (i % 40), b"m%d" % i)
        _cmd(src, b"incr", b"c%d" % (i % 40))
    chunks = chunked(src.ks, 8)
    assert len(chunks) > 4

    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    eng = node.engine
    for i, c in enumerate(chunks):
        node.merge_batch(c)
        # op write to the COUNTER plane between chunks (flush + touch)
        _cmd(node, b"incr", b"hits")
    node.ensure_flushed()

    # every INCR made the counter mirror stale: it is repaired once per
    # following merge round (O(writes-to-that-plane)) — by a patch of the
    # INCR's one journaled row, where it used to be a whole rebuild...
    assert eng.mirror_patches["cnt"] >= len(chunks) - 1, eng.mirror_patches
    assert eng.mirror_patch_rows["cnt"] == eng.mirror_patches["cnt"]
    assert eng.mirror_rebuilds["cnt"] == 0, eng.mirror_rebuilds
    # ...while the element plane, which no op touched, never rebuilds
    assert eng.mirror_rebuilds["el"] == 0, eng.mirror_rebuilds
    # and the result is still exact
    ref = Node(node_id=1)
    for c in chunks:
        CpuMergeEngine().merge(ref.ks, c)
    for i in range(len(chunks)):
        _cmd(ref, b"incr", b"hits")
    # counter values differ (different uuids) — compare the element plane
    for k in (b"s%d" % i for i in range(40)):
        kid_a = node.ks.lookup(k)
        kid_b = ref.ks.lookup(k)
        a = sorted(m for m, *_ in node.ks.elem_live(kid_a))
        b = sorted(m for m, *_ in ref.ks.elem_live(kid_b))
        assert a == b


def test_lazy_expiry_survives_resident_flush():
    """A read-path lazy expiry writes the env plane (query() sets dt); the
    resident env mirror must rebuild afterwards, or its flush would write
    the older dt back and resurrect the expired key."""
    import time
    from constdb_tpu.utils.hlc import SEQ_BITS, now_ms

    src = Node(node_id=2)
    for i in range(30):
        _cmd(src, b"set", b"w%d" % i, b"v")
    chunk = batch_from_keyspace(src.ks)

    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    _cmd(node, b"set", b"victim", b"gone-soon")
    _cmd(node, b"expireat", b"victim", b"%d" % ((now_ms() + 40) << SEQ_BITS))
    node.merge_batch(chunk)          # env mirror built (includes victim row)
    time.sleep(0.08)
    assert _cmd(node, b"get", b"victim") == NIL   # lazy expiry fires (read)
    kid = node.ks.lookup(b"victim")
    dt_expired = int(node.ks.keys.dt[kid])
    assert dt_expired > 0
    node.merge_batch(batch_from_keyspace(src.ks))  # mirror must rebuild
    node.ensure_flushed()
    # a re-read would self-heal (lazy expiry re-fires), hiding the bug —
    # the raw dt column is the truth the snapshot/replication paths see
    assert int(node.ks.keys.dt[kid]) >= dt_expired, \
        "flush reverted the expiry tombstone"
