"""Process pool for hash-sharded host merge work.

The PR-1 pipeline overlapped host staging with device compute, but every
staged byte was still produced by ONE Python process — BENCH_r06 shows the
10M-key merge spending ~54s of a 62.5s wall in single-threaded host work
(cnt/el staging + flush apply) while the device link sits ~98% idle.  Slots,
counter ranks, and set members are independent across keys (per-key CRDT
merges commute), so the host side shards embarrassingly by key hash.

This module runs N shard WORKERS, each a separate process owning one
`KeySpace` + `CpuMergeEngine` pair, so staging, native-table assigns, and
flush apply all scale with cores instead of fighting the GIL.  Workers
build the CPU engine and nothing else: a chip belongs to ONE process, so
a child that initialized a device backend while its parent held the chip
would fail or hang.  A node whose engine is the device engine ingests
in-process (server/io.py snapshot_ingest_shards).

  * workers come from a **forkserver** context: they are forked from a
    clean helper process, never from the (possibly JAX-threaded) parent —
    forking a JAX-threaded process can deadlock the child;
  * batch planes cross the process boundary via **shared-memory buffers**
    (one segment per job, holding the snapshot-codec encoding of every
    chunk in the group plus its per-key shard-id column), not pickle; all
    N workers map the SAME segment and each extracts only its shard's
    rows — the parent does zero per-row split work;
  * completions stream back asynchronously over per-worker pipes; the
    parent consumes them as they land (`reap`) and enforces a bounded
    in-flight window, the process-level analogue of PR 1's double
    buffering.

Control messages (flush / canonical / state_bytes / …) ride the same pipes
after a barrier, so replies never interleave with merge acks.
"""

from __future__ import annotations

import os
import traceback
from typing import Optional


def _attach_shm(name: str):
    """Open an existing shared-memory segment.  Forkserver children share
    the parent's resource tracker, so the attach-side registration is a
    set-level no-op and exactly one unregister fires at unlink time —
    no extra bookkeeping needed (and explicitly unregistering here would
    strip the parent's registration, making its unlink() warn)."""
    from multiprocessing import shared_memory

    return shared_memory.SharedMemory(name=name)


def _worker_main(conn, shard: int, n_shards: int, env: dict) -> None:
    """Shard worker loop: one KeySpace + one lazily-built CpuMergeEngine."""
    # the parent's CONSTDB_* settings were captured at pool creation,
    # which may post-date the forkserver's inherited environment
    os.environ.update(env)
    from ..engine.base import batch_from_keyspace
    from ..engine.cpu import CpuMergeEngine
    from ..persist.snapshot import (_decode_batch, _encode_batch,
                                    _read_bytes_list)
    from ..store.keyspace import KeySpace
    from ..store.sharded_keyspace import (extract_shard,
                                          keyspace_state_bytes, shard_ids)
    from ..utils.varint import VarintReader

    store = KeySpace()
    engine = None
    export_shm = None  # last export segment, freed on "export_free"

    def ensure_engine():
        nonlocal engine
        if engine is None:
            engine = CpuMergeEngine()
        return engine

    def flushed_store():
        if engine is not None and getattr(engine, "needs_flush", False):
            engine.flush(store)
        return store

    while True:
        try:
            msg = conn.recv()
        except EOFError:
            break
        cmd = msg[0]
        try:
            if cmd == "merge":
                _, jid, shm_name, planes, entries = msg
                shm = _attach_shm(shm_name)
                try:
                    buf = shm.buf
                    # shared bytes planes (keys / members) decode ONCE
                    # per job, however many replica chunks reference them
                    plane_cache: dict = {}

                    def plane(pid):
                        got = plane_cache.get(pid)
                        if got is None:
                            o, ln = planes[pid]
                            r = VarintReader(bytes(buf[o:o + ln]))
                            got = _read_bytes_list(r, r.uvarint())
                            plane_cache[pid] = got
                        return got

                    sid_cache: dict = {}  # key token -> shard column
                    ex_memo: dict = {}    # extract_shard's plane memo
                    subs = []
                    for off, plen, tok_k, tok_e, hv, kpid, epid in entries:
                        b = _decode_batch(
                            bytes(buf[off:off + plen]),
                            keys=plane(kpid) if kpid >= 0 else None,
                            el_member=plane(epid) if epid >= 0 else None)
                        b.key_shape = tok_k
                        b.el_shape = tok_e
                        b.el_has_vals = hv
                        # hash once per shared key plane; N workers hash
                        # in parallel (the parent ships only bytes)
                        sids = sid_cache.get(tok_k) if tok_k is not None \
                            else None
                        if sids is None:
                            sids = shard_ids(b.keys, n_shards)
                            if tok_k is not None:
                                sid_cache[tok_k] = sids
                        dsids = shard_ids(b.del_keys, n_shards) \
                            if b.del_keys else None
                        sub = extract_shard(b, sids, dsids, shard,
                                            memo=ex_memo)
                        if sub.n_rows or sub.del_keys:
                            subs.append(sub)
                finally:
                    shm.close()
                rows = sum(s.n_rows for s in subs)
                if subs:
                    ensure_engine().merge_many(store, subs)
                conn.send(("done", jid, {"rows": rows}))
            elif cmd == "flush":
                flushed_store()
                conn.send(("ok", None))
            elif cmd == "canonical":
                conn.send(("ok", flushed_store().canonical(keys=msg[1])))
            elif cmd == "state_bytes":
                conn.send(("ok", keyspace_state_bytes(flushed_store())))
            elif cmd == "export":
                # whole-shard columnar state (consolidation): encoded with
                # the snapshot codec into a worker-owned shm segment; the
                # parent copies it out then sends "export_free"
                from multiprocessing import shared_memory
                payload = bytes(_encode_batch(
                    batch_from_keyspace(flushed_store())))
                # ownership transfers across messages BY DESIGN: the
                # parent copies the segment out, then sends export_free,
                # whose branch below close()s + unlink()s it; a crashed
                # worker's segment is reclaimed by the shared resource
                # tracker at exit.  # lint: ignore[SHM-LIFECYCLE]
                export_shm = shared_memory.SharedMemory(
                    create=True, size=max(len(payload), 1))
                export_shm.buf[: len(payload)] = payload
                conn.send(("ok", (export_shm.name, len(payload))))
            elif cmd == "export_free":
                if export_shm is not None:
                    export_shm.close()
                    export_shm.unlink()
                    export_shm = None
                conn.send(("ok", None))
            elif cmd == "secs":
                conn.send(("ok", {
                    "family_secs": dict(getattr(engine, "family_secs",
                                                {}) or {}),
                    "stage_secs": dict(getattr(engine, "stage_secs",
                                               {}) or {}),
                    "bytes_h2d": getattr(engine, "bytes_h2d", 0),
                    "bytes_d2h": getattr(engine, "bytes_d2h", 0),
                    "folds": getattr(engine, "folds", 0),
                    "dev_rounds_resident": getattr(engine,
                                                   "dev_rounds_resident", 0),
                    "host_micro_rounds": getattr(engine,
                                                 "host_micro_rounds", 0),
                    "flush_rows_downloaded": getattr(
                        engine, "flush_rows_downloaded", 0),
                    "flush_rows_full_equiv": getattr(
                        engine, "flush_rows_full_equiv", 0),
                }))
            elif cmd == "memory":
                conn.send(("ok", flushed_store().memory_report()))
            elif cmd == "reset":
                if engine is not None and hasattr(engine, "close"):
                    engine.close()
                if engine is not None and \
                        hasattr(engine, "discard_resident"):
                    engine.discard_resident()
                store = KeySpace()
                engine = None
                conn.send(("ok", None))
            elif cmd == "close":
                break
            else:
                raise ValueError(f"unknown pool command {cmd!r}")
        except BaseException:
            try:
                conn.send(("err", msg[1] if cmd == "merge" else None,
                           traceback.format_exc()))
            except (BrokenPipeError, OSError):  # parent already gone
                break
    conn.close()


_ENV_PREFIXES = ("JAX_", "XLA_", "CONSTDB_", "TPU_")


def _capture_env() -> dict:
    return {k: v for k, v in os.environ.items()
            if k.startswith(_ENV_PREFIXES)}


class HostShardPool:
    """N forkserver shard workers + shared-memory job transport.

    `submit_group(prepped)` ships one encoded group (see
    `ShardedKeySpace._prep_batch` for the entry layout) to EVERY worker;
    each extracts its own shard.  Submission is asynchronous: acks drain
    through `reap()` and a bounded in-flight window (`max_inflight`
    groups) backpressures the producer — the caller consumes per-shard
    completions as they land instead of barriering per group.
    """

    def __init__(self, n_shards: int, max_inflight: int = 2,
                 env: Optional[dict] = None,
                 start_method: str = "forkserver"):
        import multiprocessing as mp

        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self.max_inflight = max(1, max_inflight)
        wenv = _capture_env()
        if env:
            wenv.update(env)
        try:
            ctx = mp.get_context(start_method)
        except ValueError:  # pragma: no cover - non-POSIX
            ctx = mp.get_context("spawn")
        self._conns = []
        self._procs = []
        for s in range(n_shards):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_worker_main,
                            args=(child, s, n_shards, wenv),
                            daemon=True)
            p.start()
            child.close()
            self._conns.append(parent)
            self._procs.append(p)
        self._next_jid = 0
        # jid -> {"acks": remaining, "shm": segment, "pins": refs}
        self._jobs: dict[int, dict] = {}
        self.rows_merged = [0] * n_shards
        self._closed = False

    # ------------------------------------------------------------- submit

    def submit_group(self, planes: list, entries: list,
                     pins: list = ()) -> int:
        """Ship one group.  `planes` is a list of encoded shared bytes
        planes (uvarint count + bytes-list blob), each shipped ONCE and
        referenced by index from the entries; `entries` is a list of
        (payload_bytes, tok_k, tok_e, hv, kpid, epid) where kpid/epid
        index `planes` (-1 = plane embedded in the payload).  `pins`
        holds whatever must stay alive until the job completes (token
        validity).  Blocks (reaping completions) while the in-flight
        window is full."""
        from multiprocessing import shared_memory

        while len(self._jobs) >= self.max_inflight:
            self.reap(block=True)
        total = sum(len(p) for p in planes) + \
            sum(len(e[0]) for e in entries)
        shm = shared_memory.SharedMemory(create=True, size=max(total, 1))
        try:
            # population + registration under a guard: a failure in here
            # (a bad buffer write, a dead worker pipe) would otherwise
            # leak the /dev/shm segment until process exit — from
            # registration onward, reap()/close() own the cleanup
            off = 0
            plane_spans = []
            for p in planes:
                shm.buf[off:off + len(p)] = p
                plane_spans.append((off, len(p)))
                off += len(p)
            wire = []
            for payload, tok_k, tok_e, hv, kpid, epid in entries:
                shm.buf[off:off + len(payload)] = payload
                wire.append((off, len(payload), tok_k, tok_e, hv, kpid,
                             epid))
                off += len(payload)
            jid = self._next_jid
            self._next_jid += 1
            self._jobs[jid] = {"acks": self.n_shards, "shm": shm,
                               "pins": list(pins)}
        except BaseException:
            shm.close()
            shm.unlink()
            raise
        for conn in self._conns:
            conn.send(("merge", jid, shm.name, plane_spans, wire))
        return jid

    def reap(self, block: bool = False) -> int:
        """Consume any landed completions; returns how many acks arrived.
        With `block`, waits for at least one."""
        from multiprocessing.connection import wait as conn_wait

        got = 0
        while self._jobs:
            ready = conn_wait(self._conns,
                              None if (block and got == 0) else 0)
            if not ready:
                break
            for conn in ready:
                msg = conn.recv()
                self._handle_ack(self._conns.index(conn), msg)
                got += 1
        return got

    def _handle_ack(self, shard: int, msg) -> None:
        kind = msg[0]
        if kind == "err":
            raise RuntimeError(
                f"shard worker {shard} failed:\n{msg[2]}")
        if kind != "done":
            raise RuntimeError(
                f"unexpected pool reply {msg[0]!r} from shard {shard}")
        jid = msg[1]
        self.rows_merged[shard] += msg[2].get("rows", 0)
        job = self._jobs[jid]
        job["acks"] -= 1
        if job["acks"] == 0:
            job["shm"].close()
            job["shm"].unlink()
            del self._jobs[jid]

    def barrier(self) -> None:
        """Drain every in-flight merge."""
        while self._jobs:
            self.reap(block=True)

    # ------------------------------------------------------ control calls

    def call_all(self, cmd: str, *args) -> list:
        """Barrier, then run one control command on every worker and
        collect the per-shard replies (in shard order)."""
        self.barrier()
        for conn in self._conns:
            conn.send((cmd,) + args)
        out = []
        for s, conn in enumerate(self._conns):
            msg = conn.recv()
            if msg[0] == "err":
                raise RuntimeError(f"shard worker {s} failed:\n{msg[2]}")
            out.append(msg[1])
        return out

    def call_one(self, shard: int, cmd: str, *args):
        self.barrier()
        conn = self._conns[shard]
        conn.send((cmd,) + args)
        msg = conn.recv()
        if msg[0] == "err":
            raise RuntimeError(f"shard worker {shard} failed:\n{msg[2]}")
        return msg[1]

    def export_shard(self, shard: int) -> bytes:
        """Copy one shard's whole-state columnar export out of the
        worker's shared-memory segment."""
        name, size = self.call_one(shard, "export")
        shm = _attach_shm(name)
        try:
            payload = bytes(shm.buf[:size])
        finally:
            shm.close()
        self.call_one(shard, "export_free")
        return payload

    def export_all(self) -> list:
        """Whole-state exports from EVERY shard, with the expensive
        worker-side encodes running concurrently: the export command goes
        to all workers first, then the parent copies each segment out as
        its reply lands (vs export_shard in a loop, which would leave
        N-1 workers idle per round-trip)."""
        self.barrier()
        for conn in self._conns:
            conn.send(("export",))
        out = []
        for s, conn in enumerate(self._conns):
            msg = conn.recv()
            if msg[0] == "err":
                raise RuntimeError(f"shard worker {s} failed:\n{msg[2]}")
            name, size = msg[1]
            shm = _attach_shm(name)
            try:
                out.append(bytes(shm.buf[:size]))
            finally:
                shm.close()
            conn.send(("export_free",))
            ack = conn.recv()
            if ack[0] == "err":  # pragma: no cover - free cannot fail
                raise RuntimeError(f"shard worker {s} failed:\n{ack[2]}")
        return out

    # ---------------------------------------------------------- lifecycle

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for conn in self._conns:
            try:
                conn.send(("close",))
            except (BrokenPipeError, OSError):
                pass
        for p in self._procs:
            p.join(timeout=10)
            if p.is_alive():  # pragma: no cover - hung worker
                p.terminate()
        for conn in self._conns:
            conn.close()
        for job in self._jobs.values():
            try:
                job["shm"].close()
                job["shm"].unlink()
            except OSError:  # pragma: no cover - already gone
                pass
        self._jobs.clear()

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
