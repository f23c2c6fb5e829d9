"""Stage clocks: where the host's time goes on the served path, from the
socket read to the reply write, and on the replication link beside it.

One `StageClock` per node (the engine builds it, the node adopts it — a
CPU-engine node builds its own).  Every host clock on the served path is
taken through it:

    with clock.stage("plan"):            # a counter
        ...
    with clock.stage("mirror_rebuild", fam):   # a counter AND a trace span
        ...

* **Self time.**  Stages nest per thread.  On exit a stage adds its
  duration MINUS the time its child stages took to `span_<name>_us` and 1
  to `span_<name>_n` (INFO, server/info.py), so the self times of nested
  stages on one thread add up to wall time and never exceed it.  A stage
  left by an exception is closed and counted like any other.  Every
  thread has a stack of its own: the bulk path's staging pool
  (`constdb-stage` threads) counts its `stage_rows` time but never nests
  into the event loop's stages.
* **The device trace's clock.**  A stage in `ANNOTATED` — entered at most
  once per coalescer flush, or rarer — also opens the trace annotation
  the engine handed in (`jax.profiler.TraceAnnotation`) as
  `cst.<name>[.<tag>]`, so it lands in the `/host:` plane of the same
  `.xplane.pb` as the device's `XLA Ops`: one clock, no offset to
  estimate.  This module never imports JAX; without an annotation (a
  CPU-engine node, a shard worker) such a stage is a counter like the
  others.  Per-chunk stages are counters only: ~1,200 chunks a second
  would put ~0.4 M events into a one-minute trace.
* **Declared names.**  `STAGES` is the whole vocabulary; INFO prints
  every one from boot, at 0, and a name outside it raises.

Rules for a call site: never hold a stage across an `await` (another
connection's work would be billed to it — the STAGE-AWAIT lint rule), and
never open one inside a per-operation or per-row loop.

`seconds_into(acc, key)` is the second, smaller clock: the inclusive
seconds of a block added to `acc[key]`, for the documented INFO totals
that overlap by design (`merge_seconds_total`, `merge_<fam>_seconds`,
`flush_seconds_total`).  It is not a stage: no self time, no stack.
"""

from __future__ import annotations

import threading
from functools import partial
from time import perf_counter_ns

# served path, in order: socket read -> the loop-pass gather (server/io.py:
# hand-over, concatenation, cut and wake-ups) -> ... -> reply write; then the
# replication link (replica/link.py, replica/coalesce.py): a peer's stream
# in (`repl_ingest` per socket read, `repl_flush` per landed batch) and the
# node's own log out (`repl_push` per drained run and per wake-up's tail)
STAGES = ("intake", "gather", "plan", "read_batch", "read_miss", "exec",
          "serve_flush", "stage_rows", "h2d", "dispatch", "host_twin",
          "mirror_rebuild", "mirror_patch", "state_alloc", "d2h_flush",
          "reply_write", "repl_ingest", "repl_flush", "repl_push")
# entered for every pipelined chunk, every hand-over and pass of the gather,
# every socket read of a peer's stream or every wake-up of a push loop:
# counters only.  The others come at most
# once per coalescer flush, or rarer, and also open a trace span
PER_CHUNK = frozenset(("intake", "gather", "plan", "read_batch", "read_miss", "exec",
                       "reply_write", "repl_ingest", "repl_push"))
ANNOTATED = frozenset(STAGES) - PER_CHUNK
MAX_ANNOTATION = 40     # benchmark/trace_reduce.py cuts a host name at 48

_INDEX = {name: i for i, name in enumerate(STAGES)}
_SPAN = tuple(name in ANNOTATED for name in STAGES)


class _Thread:
    """One thread's open stage and its counts (no lock: only its own
    thread writes them)."""

    __slots__ = ("top", "ns", "n")

    def __init__(self) -> None:
        self.top = None
        self.ns = [0] * len(STAGES)
        self.n = [0] * len(STAGES)


class _Stage:
    __slots__ = ("clock", "i", "span", "total", "th", "parent", "child",
                 "t0")

    def __init__(self, clock: "StageClock", name: str, tag: str = "",
                 total=None) -> None:
        i = _INDEX.get(name)
        if i is None:
            raise ValueError(f"stage {name!r} is not declared in "
                             f"stagetime.STAGES {STAGES}")
        self.clock = clock
        self.i = i
        self.total = total
        self.span = None
        if _SPAN[i] and clock.annotation is not None:
            label = f"cst.{name}.{tag}" if tag else f"cst.{name}"
            if len(label) > MAX_ANNOTATION:
                raise ValueError(f"annotation {label!r} is over "
                                 f"{MAX_ANNOTATION} characters")
            self.span = clock.annotation(label)

    def __enter__(self) -> "_Stage":
        tls = self.clock._tls
        try:
            th = tls.th
        except AttributeError:
            th = tls.th = self.clock._new_thread()
        self.th = th
        self.parent = th.top
        th.top = self
        self.child = 0
        if self.span is not None:
            self.span.__enter__()
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dt = perf_counter_ns() - self.t0
        if self.span is not None:
            self.span.__exit__(et, ev, tb)
        th = self.th
        parent = th.top = self.parent
        th.ns[self.i] += dt - self.child
        th.n[self.i] += 1
        if parent is not None:
            parent.child += dt
        if self.total is not None:
            acc, key = self.total
            acc[key] += dt * 1e-9
        return False


class StageClock:
    """The accumulator behind INFO `span_<name>_us` / `span_<name>_n`.

    `annotation`: a context-manager factory taking the span's name —
    `jax.profiler.TraceAnnotation`, handed in by TpuMergeEngine — or None
    (counters only)."""

    def __init__(self, annotation=None) -> None:
        self.annotation = annotation
        self._tls = threading.local()
        self._threads: list[_Thread] = []
        self._lock = threading.Lock()
        # stage(name, tag="", total=None) -> context manager; `tag` only
        # extends the trace span's name (a family — never per-call data),
        # `total=(acc, key)` also adds the stage's INCLUSIVE seconds to
        # acc[key]
        self.stage = partial(_Stage, self)

    def _new_thread(self) -> _Thread:
        th = _Thread()
        with self._lock:
            self._threads.append(th)
        return th

    def snapshot(self) -> dict:
        """{name: (self time in whole microseconds, entries)} summed over
        every thread that entered a stage, every declared name present."""
        with self._lock:
            threads = list(self._threads)
        return {name: (sum(t.ns[i] for t in threads) // 1000,
                       sum(t.n[i] for t in threads))
                for i, name in enumerate(STAGES)}


class seconds_into:
    """Inclusive seconds of the block, added to `acc[key]` (see the module
    docstring: the overlapping INFO totals, not a stage)."""

    __slots__ = ("acc", "key", "t0")

    def __init__(self, acc, key) -> None:
        self.acc = acc
        self.key = key

    def __enter__(self) -> None:
        self.t0 = perf_counter_ns()

    def __exit__(self, et, ev, tb) -> bool:
        self.acc[self.key] += (perf_counter_ns() - self.t0) * 1e-9
        return False
