"""Shared-memory segment lifecycle (parallel/host_pool.py).

A leaked /dev/shm segment survives the creating process on Linux; at
snapshot-merge scale (one segment per job group) leaks fill the tmpfs
and take the box down.  These tests pin the SHM-LIFECYCLE invariant the
lint rule checks statically, at runtime: no segment outlives the pool
after (a) normal completion, (b) a worker crash mid-job, and (c) pool
shutdown with jobs still in flight."""

import os
import signal

import numpy as np
import pytest

import bench
from constdb_tpu.parallel.host_pool import HostShardPool
from constdb_tpu.persist.snapshot import _encode_batch
from constdb_tpu.store.sharded_keyspace import ShardedKeySpace

_I64 = np.int64


@pytest.fixture
def segments(monkeypatch):
    """Names of the segments the pool under test makes: every one this
    process creates or attaches by name while the test runs (a worker's
    export segment reaches the parent by name).  Scoped to them because
    /dev/shm is the machine's: another test process's live `psm_*`
    segment is not this pool's leak."""
    from multiprocessing import shared_memory
    if not os.path.isdir("/dev/shm"):  # pragma: no cover - non-Linux
        pytest.skip("/dev/shm not available on this platform")
    names = set()

    class Recorded(shared_memory.SharedMemory):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            names.add(self.name)

    monkeypatch.setattr(shared_memory, "SharedMemory", Recorded)
    return names


def _assert_none_left(segments: set) -> None:
    assert segments, "the pool under test made no segment"
    left = {n for n in segments if os.path.exists("/dev/shm/" + n)}
    assert left == set(), "leaked /dev/shm segments"


def _chunks(n_keys=240, n_rep=2, chunk=80):
    return bench.chunk_batches(bench.make_workload(n_keys, n_rep, seed=7),
                               chunk)


def _raw_entries(chunks):
    """Encoded batch sections in the submit_group wire shape (the
    submit_raw path: workers decode + hash themselves)."""
    return [(bytes(_encode_batch(c)), None, None, None, -1, -1)
            for c in chunks]


def test_no_leak_after_normal_completion(segments):
    sks = ShardedKeySpace(n_shards=2, mode="process", engine_spec="cpu",
                          group=3)
    for c in _chunks():
        sks.submit(c)
    sks.flush()
    assert sks.n_keys() > 0  # the merge actually happened
    sks.close()
    _assert_none_left(segments)


def test_no_leak_after_worker_crash_mid_job(segments):
    """SIGKILL a worker while groups are in flight: the parent's reap
    surfaces the dead pipe as an error and close() still unlinks every
    job segment."""
    pool = HostShardPool(2, max_inflight=2)
    try:
        entries = _raw_entries(_chunks())
        pool.submit_group([], entries[:2])
        os.kill(pool._procs[1].pid, signal.SIGKILL)
        with pytest.raises((EOFError, OSError, RuntimeError)):
            # keep feeding until the dead pipe surfaces (the first
            # submit may have fully completed before the kill landed)
            for _ in range(20):
                pool.submit_group([], entries[2:4])
                pool.barrier()
    finally:
        pool.close()
    _assert_none_left(segments)


def test_no_leak_on_shutdown_with_jobs_in_flight(segments):
    sks = ShardedKeySpace(n_shards=2, mode="process", engine_spec="cpu",
                          group=1)  # group=1: every submit ships a segment
    for c in _chunks():
        sks.submit(c)
    sks.close()  # no barrier, no flush: jobs still in flight
    _assert_none_left(segments)


def test_submit_group_guard_frees_segment_on_failure(segments):
    """The new creation guard: a failure while POPULATING the segment
    (before registration hands ownership to reap/close) must close +
    unlink it instead of leaking until process exit."""
    pool = HostShardPool(1)
    try:
        # entry shaped to blow up inside the population loop: a str has
        # a len() (so sizing + creation succeed) but is not a buffer, so
        # the segment write raises after the segment exists
        with pytest.raises(TypeError):
            pool.submit_group([], [("x" * 64, None, None, None, -1, -1)])
    finally:
        pool.close()
    _assert_none_left(segments)
