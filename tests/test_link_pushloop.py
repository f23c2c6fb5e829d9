"""Push-loop ring-falloff fallback (replica/link.py `_push_loop`).

The module header documents: a pusher that falls off its own repl_log ring
mid-stream re-sends a full snapshot ON THE SAME CONNECTION (the reference
leaves the case as a TODO — pull.rs:167-172).  Before this PR the push loop
would stream the next surviving entry with a gapped prev_uuid, the peer
would raise ReplicateCommandsLost, and recovery rode a teardown + redial.
These tests drive the eviction mid-drain and assert the in-place fallback:
no gapped frame is ever written, and a FULLSYNC follows on the same writer.
"""

import asyncio
import os
import types

from constdb_tpu.persist.snapshot import NodeMeta, dump_keyspace
from constdb_tpu.replica.link import FULLSYNC, PARTSYNC, REPLACK, \
    REPLICATE, ReplicaLink
from constdb_tpu.replica.manager import ReplicaMeta
from constdb_tpu.resp.codec import make_parser
from constdb_tpu.resp.message import Arr, Bulk, as_bytes, as_int
from constdb_tpu.server.node import Node


class _Writer:
    """Stub StreamWriter collecting every frame; `on_drain` fires on each
    drain so the test can evict the ring exactly at a yield point."""

    def __init__(self, on_drain=None):
        self.buf = bytearray()
        self.on_drain = on_drain
        self.drains = 0
        self.closed = False

    def write(self, data: bytes) -> None:
        self.buf += data

    async def drain(self) -> None:
        self.drains += 1
        if self.on_drain is not None:
            self.on_drain(self.drains)
        await asyncio.sleep(0)

    def close(self) -> None:
        self.closed = True


class _SharedDumpStub:
    def __init__(self, node, work_dir):
        self.node = node
        self.work_dir = work_dir
        self.dumps = 0

    async def acquire(self, compressed=False):
        from constdb_tpu.persist.share import Dump
        self.dumps += 1
        path = os.path.join(self.work_dir, f"dump{self.dumps}.snapshot")
        size = dump_keyspace(path, self.node.ks,
                             NodeMeta(node_id=self.node.node_id))
        return Dump(path=path, repl_last=self.node.repl_log.last_uuid,
                    size=size)


def _mk_link(tmp_path, cap=100_000):
    node = Node(node_id=1, repl_log_cap=cap)
    app = types.SimpleNamespace(node=node, heartbeat=0.05,
                                reconnect_delay=0.05,
                                handshake_timeout=1.0, work_dir=str(tmp_path))
    app.shared_dump = _SharedDumpStub(node, str(tmp_path))
    meta = ReplicaMeta(addr="127.0.0.1:1")
    return node, app, ReplicaLink(app, meta)


def _log_write(node, i):
    """One logged write (k{i}) through the node's keyspace + repl_log."""
    uuid = node.hlc.tick(True)
    key = b"k%d" % i
    kid, _ = node.ks.get_or_create(key, 1, uuid)
    node.ks.register_set(kid, b"x" * 40, uuid, node.node_id)
    node.replicate_cmd(uuid, b"set", [Bulk(key), Bulk(b"x" * 40)])


def _scan_frames(buf: bytes):
    """Parse the written stream; returns (kinds, gap_frames) where
    gap_frames collects REPLICATE frames whose prev_uuid skipped past the
    last streamed uuid (the bug this PR removes)."""
    parser = make_parser()
    parser.feed(bytes(buf))
    kinds = []
    gaps = []
    cursor = 0
    while True:
        msg = parser.next_msg()
        if msg is None:
            break
        items = msg.items if isinstance(msg, Arr) else None
        assert items, f"unexpected frame {msg!r}"
        kind = as_bytes(items[0]).lower()
        kinds.append(kind)
        if kind == FULLSYNC:
            size = as_int(items[1])
            cursor = as_int(items[2])  # dump watermark = new resume point
            raw = parser.take_raw(size)
            while len(raw) < size:  # skip the snapshot bytes
                more = parser.take_raw(size - len(raw))
                assert more, "snapshot bytes truncated in stream"
                raw += more
        elif kind == REPLICATE:
            prev, uuid = as_int(items[2]), as_int(items[3])
            if prev > cursor:
                gaps.append((cursor, prev, uuid))
            cursor = uuid
        elif kind in (PARTSYNC, REPLACK):
            pass
        else:  # pragma: no cover - future frame kinds
            raise AssertionError(f"unknown frame {kind!r}")
    return kinds, gaps


def test_midstream_eviction_resyncs_in_place(tmp_path):
    """Evict the ring past the send cursor at a mid-stream drain: the
    pusher must stop, send a FULLSYNC on the SAME writer, and continue
    gap-free — never writing a gapped REPLICATE frame."""
    async def main():
        node, app, link = _mk_link(tmp_path, cap=100_000)
        for i in range(100):
            _log_write(node, i)

        def evict(drain_no):
            if drain_no == 1:
                # shrink the ring so eviction races the in-flight stream
                # exactly the way a burst of writes would
                node.repl_log.cap = 500
                for i in range(100, 160):
                    _log_write(node, 1000 + i)

        writer = _Writer(on_drain=evict)
        task = asyncio.create_task(link._push_loop(writer, peer_resume=0))
        try:
            for _ in range(400):  # phase 1: in-place snapshot sent
                await asyncio.sleep(0.01)
                kinds, _ = _scan_frames(writer.buf)
                if FULLSYNC in kinds:
                    break
            for i in range(2):  # the log moves on after the snapshot...
                _log_write(node, 5000 + i)
            for _ in range(400):  # ...phase 2: the SAME stream resumes
                await asyncio.sleep(0.01)
                kinds, _ = _scan_frames(writer.buf)
                if REPLICATE in kinds[kinds.index(FULLSYNC):]:
                    break
        finally:
            task.cancel()
        kinds, gaps = _scan_frames(writer.buf)
        assert not gaps, f"gapped REPLICATE frames written: {gaps}"
        assert FULLSYNC in kinds, "no in-place full resync on the stream"
        assert kinds[0] == PARTSYNC  # fresh log: first round is partial
        # the snapshot was produced once, for this same connection
        assert app.shared_dump.dumps == 1
        assert not writer.closed  # recovery never tore the stream down
    asyncio.run(main())


def test_no_eviction_stays_partial(tmp_path):
    """Control: with the ring intact the loop streams gap-free and never
    dumps a snapshot."""
    async def main():
        node, app, link = _mk_link(tmp_path)
        for i in range(80):
            _log_write(node, i)
        writer = _Writer()
        task = asyncio.create_task(link._push_loop(writer, peer_resume=0))
        for _ in range(100):
            await asyncio.sleep(0.01)
            kinds, _ = _scan_frames(writer.buf)
            if kinds.count(REPLICATE) >= 80:
                break
        task.cancel()
        kinds, gaps = _scan_frames(writer.buf)
        assert not gaps
        assert FULLSYNC not in kinds
        assert app.shared_dump.dumps == 0
    asyncio.run(main())


def test_closed_app_does_not_keep_applying(tmp_path):
    """Regression for the close-window zombie: a connection upgraded to a
    replica link while ServerApp.close() is sweeping must not keep the
    "closed" node applying its peer's stream (this silently kept a downed
    peer caught up, masking the full-resync path mesh-wide)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from cluster_util import Client, close_cluster, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=2_000)
        try:
            c1 = await Client().connect(apps[0].advertised_addr)
            await c1.cmd("meet", apps[1].advertised_addr)
            # close n2 immediately — racing the first SYNC handshake
            await apps[1].close()
            for i in range(200):
                await c1.cmd("set", f"k{i}", "x" * 32)
            await asyncio.sleep(0.6)
            assert apps[1].node.ks.n_keys() == 0, \
                "a zombie link kept the closed node applying"
            await c1.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_sharded_snapshot_ingest_e2e(tmp_path):
    """Full-sync catch-up through the process-parallel sharded ingest
    (ServerApp ingest_shards > 1): a joiner whose resume point is off the
    pusher's ring downloads a snapshot, fans it out to shard workers, and
    consolidates into its serving keyspace — converging to the same state
    the plain path produces."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from cluster_util import Client, close_cluster, converge, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path), repl_log_cap=2_000,
                                  ingest_shards=2, ingest_shard_min_bytes=0)
        try:
            c1 = await Client().connect(apps[0].advertised_addr)
            # enough bytes that the joiner's resume=0 falls off the ring
            # (cap 2000 holds ~50 of these entries): the sync decision
            # then must ship a snapshot
            for i in range(160):
                await c1.cmd("set", f"k{i}", "v" * 32)
            await c1.cmd("sadd", "members", "a", "b", "c")
            await c1.cmd("incr", "hits")
            await c1.cmd("meet", apps[1].advertised_addr)
            await converge(apps, timeout=30.0)
            n2 = apps[1].node
            assert n2.ks.n_keys() >= 162
            assert n2.stats.extra.get("sharded_ingests", 0) >= 1, \
                "snapshot did not take the sharded ingest path"
            assert n2.stats.extra.get("sharded_ingest_workers") == 2
            await c1.close()
        finally:
            await close_cluster(apps)
    asyncio.run(main())


def test_device_engine_node_ingests_in_process(tmp_path):
    """One process per chip: shard workers are CPU-engine processes, so
    a node whose engine batches on a device never fans a snapshot out
    to them, whatever ingest_shards asks for — the decision is taken
    and lands on the in-process path."""
    from constdb_tpu.engine.tpu import TpuMergeEngine
    from constdb_tpu.server.io import ServerApp
    from constdb_tpu.server.node import Node

    dev = ServerApp(Node(node_id=1, engine=TpuMergeEngine(resident=True)),
                    work_dir=str(tmp_path), ingest_shards=4,
                    ingest_shard_min_bytes=0)
    assert dev.snapshot_ingest_shards(1 << 30) == 1
    cpu = ServerApp(Node(node_id=2), work_dir=str(tmp_path),
                    ingest_shards=4, ingest_shard_min_bytes=0)
    assert cpu.snapshot_ingest_shards(1 << 30) == 4
