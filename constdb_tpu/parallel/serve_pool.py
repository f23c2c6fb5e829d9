"""Process pool for shard-per-core client serving.

PR 5 coalesced pipelined client chunks through the merge engine, but the
whole client path — parse, plan, merge, reply, repl-log — still ran on
ONE event loop: BENCH_r09 pins serving at ~15 µs/cmd of irreducible
per-command Python on this box, all of it single-core.  Per-key CRDT
state is independent across keys (the same property that made snapshot
merge shard in PR 2), and every data command is first-key-confined (the
KEY-CONFINED lint rule), so the serving hot path shards by key hash too.

This module runs N serve WORKERS, each a separate forkserver process
owning one `Node` (shard keyspace + merge engine + repl-log tap), so
planning, merging, reply computation, and log-entry production all scale
with cores.  The PARENT process stays the authority for everything
global — it accepts connections, parses, routes whole pipelined
sub-chunks per shard, **mints every HLC uuid at route time** (so the
uuid stream is byte-identical to the single-loop path's), owns
membership/replication/GC scheduling, and mirrors each worker's log
entries into that shard's repl-log segment as acks land (see
server/serve_shards.py for the plane and server/repl_log.py
MergedReplLog for the merge-sorted peer stream).

Transport: one pipe per worker.  Requests are small pickled tuples
(serve chunks ship the commands re-encoded as RESP bytes — the native
codec is faster than pickling message trees); replies stream back FIFO
per worker and resolve asyncio futures via a reader thread.  Sends are
SYNCHRONOUS on the event loop — this is load-bearing, not a shortcut:
the parent mints uuids at classification time, and a suspension point
between minting and the pipe write would let another connection's newer
uuids reach the worker first, breaking the per-segment
strictly-increasing contract the merged peer stream rests on.  A send
can only block when the OS pipe buffer is full (natural backpressure);
the reader thread keeps draining replies meanwhile, so it cannot
deadlock.
"""

from __future__ import annotations

import asyncio
import threading
import traceback
from collections import deque
from typing import Optional

from .host_pool import _capture_env


class _TapLog:
    """Worker-side repl-log stand-in: records every locally-replicated
    command for the ack instead of retaining a ring — the authoritative
    segments live in the PARENT (mirrored in ack order).  Keeps the
    strictly-increasing-uuid contract so a routing bug cannot silently
    reorder a shard's stream."""

    __slots__ = ("tap", "last_uuid", "evicted_up_to")

    def __init__(self) -> None:
        self.tap: list = []
        self.last_uuid = 0
        self.evicted_up_to = 0

    def push(self, uuid: int, name: bytes, args: list) -> None:
        if uuid <= self.last_uuid:
            raise ValueError(
                f"shard log uuids must be increasing: {uuid} <= "
                f"{self.last_uuid}")
        self.tap.append((uuid, name, args))
        self.last_uuid = uuid

    def push_many(self, cmds: list) -> None:
        for uuid, name, args in cmds:
            self.push(uuid, name, args)

    def drain(self) -> list:
        out, self.tap = self.tap, []
        return out


def _worker_stats(node) -> dict:
    st = node.stats
    # the sampled plan->land latency ring drains into each ack so the
    # parent's INFO percentiles cover sharded serving too
    lat = list(st.serve_lat)
    st.serve_lat.clear()
    rc = node.read_cache
    return {
        "cmds": st.cmds_processed,
        "repl": st.cmds_replicated,
        "msgs": st.serve_msgs_coalesced,
        "flushes": st.serve_flushes,
        "barriers": st.serve_barriers,
        "apply_barriers": st.repl_apply_barriers,
        "gc_freed": st.gc_freed,
        "keys": node.ks.n_keys(),
        "used_bytes": node.governor.used_memory(),
        "oom_shed": st.oom_shed_writes,
        # the read plane's worker-side gauges (the parent folds the
        # counters into the node totals and publishes the bytes gauge
        # per shard — server/serve_shards.py _fold_stats)
        "reads": st.serve_reads_coalesced,
        "read_flushes": st.serve_read_flushes,
        "reads_direct": st.serve_read_replies_direct,
        "scans_native": st.serve_read_scans_native,
        "cache_hits": rc.hits,
        "cache_misses": rc.misses,
        "cache_inv": rc.invalidations,
        "cache_bytes": rc.bytes,
        "lat": lat,
    }


def _serve_worker_main(conn, shard: int, n_shards: int,
                       env: dict, node_id: int, alias: str,
                       serve_batch: int, maxmemory=None,
                       maxmemory_soft_pct=None) -> None:
    """Serve worker loop: one shard-confined Node + ServeCoalescer."""
    import os

    os.environ.update(env)
    import numpy as np

    from ..engine.base import batch_from_keyspace
    from ..persist.snapshot import _decode_batch, _encode_batch
    from ..resp.codec import make_parser
    from ..resp.message import NoReply, as_bytes, as_int
    from ..resp.codec import encode_into
    from ..server.node import Node
    from ..server.serve import ServeCoalescer
    from ..store.sharded_keyspace import keyspace_state_bytes

    # Node's default engine, the CPU engine, like every worker process:
    # a chip belongs to ONE process (parallel/host_pool.py)
    node = Node(node_id=node_id, alias=alias)
    if maxmemory is not None or maxmemory_soft_pct is not None:
        # each worker governs its slice of the node cap (the plane
        # passed maxmemory // n_shards): the keys are hash-partitioned,
        # so per-shard caps bound the node total while the shed decision
        # stays local to the worker owning the written key
        node.governor.configure(maxmemory, maxmemory_soft_pct)
    # a worker's own gc_horizon would be its LOCAL clock (no peers in
    # its ReplicaManager) — unsound for tombstone collection; the
    # parent cron drives worker GC with the real coverage-gated
    # cluster horizon ("gc" command below), so the hard-watermark
    # reclaim must not sweep on its own (server/overload.py)
    node.governor.reclaim_gc = False
    node.repl_log = _TapLog()
    deleted = [False]

    def wire_ks():
        node.ks.on_key_delete = lambda: deleted.__setitem__(0, True)

    wire_ks()
    coal = ServeCoalescer(node, max_run=serve_batch) if serve_batch > 1 \
        else None
    parser = make_parser()

    while True:
        try:
            msg = conn.recv()
        except EOFError:
            break
        cmd = msg[0]
        try:
            if cmd == "serve":
                _, payload, uuids, n = msg
                parser.feed(payload)
                msgs = parser.drain()
                out = bytearray()
                spans: list = []
                deleted[0] = False
                if coal is not None:
                    coal.run_chunk(msgs, out, uuids=uuids, spans=spans)
                else:
                    # CONSTDB_SERVE_BATCH<=1: the exact per-command loop
                    for i, m in enumerate(msgs):
                        reply = node.execute(m, uuid=uuids[i])
                        if not isinstance(reply, NoReply):
                            encode_into(out, reply)
                        spans.append(len(out))
                conn.send(("ok", (bytes(out), spans,
                                  node.repl_log.drain(), deleted[0],
                                  _worker_stats(node))))
            elif cmd == "apply":
                # one peer-stream sub-chunk: full REPLICATE wire frames,
                # applied per-key in stream order (the exact op path —
                # cross-shard parallelism replaces in-shard coalescing;
                # frames here are NOT barriers, so the PR 4
                # repl_apply_barriers stat keeps its single-loop
                # meaning: only non-routable frames, counted by the
                # parent-side ShardApplier)
                _, payload, n = msg
                parser.feed(payload)
                frames = parser.drain()
                deleted[0] = False
                for fr in frames:
                    it = fr.items
                    node.apply_replicated(as_bytes(it[4]), it[5:],
                                          as_int(it[1]), as_int(it[3]))
                conn.send(("ok", (node.repl_log.drain(), deleted[0],
                                  _worker_stats(node))))
            elif cmd == "merge":
                # snapshot-codec encoded sub-batch (catch-up ingest);
                # the key count rides back so INFO's per-shard gauges
                # are populated by restores too, not only serve acks
                b = _decode_batch(msg[1])
                node.merge_batches([b])
                conn.send(("ok", (b.n_rows, node.ks.n_keys())))
            elif cmd == "canonical":
                node.ensure_flushed()
                conn.send(("ok", node.ks.canonical(keys=msg[1])))
            elif cmd == "state_bytes":
                node.ensure_flushed()
                conn.send(("ok", keyspace_state_bytes(node.ks)))
            elif cmd == "export":
                node.ensure_flushed()
                conn.send(("ok", bytes(_encode_batch(
                    batch_from_keyspace(node.ks)))))
            elif cmd == "digest":
                # anti-entropy digest of THIS shard's keys (the crc32
                # partition is layout-invariant, so the parent SUMS the
                # workers' matrices — store/digest.py sum_matrices)
                from ..store.digest import state_digest_matrix
                node.ensure_flushed()
                conn.send(("ok", state_digest_matrix(
                    node.ks, msg[1], msg[2]).astype("<u8").tobytes()))
            elif cmd == "n_keys":
                # live key count (delta-sync leaf sizing): the serving
                # stat gauges can be zero on a node whose state arrived
                # purely via the replication stream, so the plane asks
                # the workers directly
                node.ensure_flushed()
                conn.send(("ok", node.ks.n_keys()))
            elif cmd == "digest_export":
                # encoded BATCH chunks of the masked buckets' state —
                # the delta-sync stream's payload (replica/link.py
                # _send_delta writes them via write_chunk_raw)
                from ..persist.snapshot import batch_chunks
                from ..store.digest import export_bucket_batch
                _, fanout, leaves, mask_bytes, chunk_keys = msg
                node.ensure_flushed()
                mask = np.frombuffer(mask_bytes, dtype=bool)
                b = export_bucket_batch(node.ks, fanout, leaves, mask)
                conn.send(("ok", [bytes(_encode_batch(c))
                                  for c in batch_chunks(b, chunk_keys)]))
            elif cmd == "memory":
                node.ensure_flushed()
                conn.send(("ok", node.ks.memory_report()))
            elif cmd == "gc":
                node.ensure_flushed()
                freed = node.ks.gc(msg[1])
                node.stats.gc_freed += freed
                conn.send(("ok", freed))
            elif cmd == "ident":
                node.node_id = msg[1]
                node.alias = msg[2]
                conn.send(("ok", None))
            elif cmd == "freeze":
                # a restore or full sync has landed in this shard: its
                # table leaves every later collection (utils/stagetime.py)
                conn.send(("ok", node.freeze_heap()))
            elif cmd == "reset":
                # state-clearing full resync: fresh keyspace, tap kept
                # ("freeze" follows once the new state lands)
                node.replace_keyspace()
                wire_ks()
                node.repl_log = _TapLog()
                if coal is not None:
                    coal.reset_caches()
                conn.send(("ok", None))
            elif cmd == "ping":
                conn.send(("ok", None))
            elif cmd == "close":
                break
            else:
                raise ValueError(f"unknown serve-pool command {cmd!r}")
        except BaseException:
            try:
                conn.send(("err", traceback.format_exc()))
            except (BrokenPipeError, OSError):  # parent already gone
                break
    conn.close()


class ServeShardPool:
    """N forkserver serve workers with asyncio request/reply transport.

    `request(shard, msg)` returns an awaitable resolving to the worker's
    reply; per-worker FIFO is preserved (requests are sent under a
    per-worker lock, replies correlate in order), so a shard worker is a
    serialization point exactly like the single event loop was — for
    its shard only."""

    def __init__(self, n_shards: int,
                 node_id: int = 0, alias: str = "", serve_batch: int = 512,
                 env: Optional[dict] = None,
                 start_method: str = "forkserver",
                 maxmemory: Optional[int] = None,
                 maxmemory_soft_pct: Optional[float] = None):
        import multiprocessing as mp

        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        wenv = _capture_env()
        if env:
            wenv.update(env)
        try:
            ctx = mp.get_context(start_method)
        except ValueError:  # pragma: no cover - non-POSIX
            ctx = mp.get_context("spawn")
        self._loop = asyncio.get_running_loop()
        self._conns = []
        self._procs = []
        self._pending: list[deque] = []
        self._closed = False
        for s in range(n_shards):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_serve_worker_main,
                            args=(child, s, n_shards, wenv,
                                  node_id, alias, serve_batch,
                                  maxmemory, maxmemory_soft_pct),
                            daemon=True)
            p.start()
            child.close()
            self._conns.append(parent)
            self._procs.append(p)
            self._pending.append(deque())
        self._readers = [
            threading.Thread(target=self._reader, args=(s,), daemon=True)
            for s in range(n_shards)]
        for t in self._readers:
            t.start()

    # ----------------------------------------------------------- transport

    def _reader(self, shard: int) -> None:
        conn = self._conns[shard]
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                if not self._closed:
                    try:
                        self._loop.call_soon_threadsafe(
                            self._fail_all, shard,
                            RuntimeError(f"serve worker {shard} died"))
                    except RuntimeError:  # loop already closed
                        pass
                return
            try:
                self._loop.call_soon_threadsafe(self._resolve, shard, msg)
            except RuntimeError:  # loop closed mid-shutdown
                return

    def _resolve(self, shard: int, msg) -> None:
        if not self._pending[shard]:  # late reply after close
            return
        fut = self._pending[shard].popleft()
        if fut.done():
            return
        if msg[0] == "err":
            fut.set_exception(RuntimeError(
                f"serve worker {shard} failed:\n{msg[1]}"))
        else:
            fut.set_result(msg[1])

    def _fail_all(self, shard: int, exc: BaseException) -> None:
        while self._pending[shard]:
            fut = self._pending[shard].popleft()
            if not fut.done():
                fut.set_exception(exc)

    def submit(self, shard: int, msg: tuple) -> asyncio.Future:
        """Send one request SYNCHRONOUSLY, returning the reply future —
        no suspension point between the caller's uuid minting and the
        pipe write (see module docstring), and the plane's ack
        callbacks run in reply order (floor windows, segment
        mirroring)."""
        fut = self._loop.create_future()
        pending = self._pending[shard]
        pending.append(fut)
        try:
            self._conns[shard].send(msg)
        except BaseException:
            pending.remove(fut)
            raise
        return fut

    async def request(self, shard: int, msg: tuple):
        """Send one request and await its reply (FIFO per worker)."""
        return await self.submit(shard, msg)

    # -------------------------------------------------------- conveniences

    async def call_all(self, *msg) -> list:
        """One control command on every worker, replies in shard order.
        FIFO pipes make this an implicit barrier: everything previously
        sent to a worker completes before its reply."""
        futs = [self.submit(s, tuple(msg)) for s in range(self.n_shards)]
        return list(await asyncio.gather(*futs))

    async def barrier(self) -> None:
        """Drain every worker's queue (quiesce)."""
        await self.call_all("ping")

    # ----------------------------------------------------------- lifecycle

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for conn in self._conns:
            try:
                conn.send(("close",))
            except (BrokenPipeError, OSError):
                pass
        loop = self._loop

        def join_all():
            for p in self._procs:
                p.join(timeout=10)
                if p.is_alive():  # pragma: no cover - hung worker
                    p.terminate()

        await loop.run_in_executor(None, join_all)
        for conn in self._conns:
            conn.close()
        for s in range(self.n_shards):
            self._fail_all(s, RuntimeError("serve pool closed"))
