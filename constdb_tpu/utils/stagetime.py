"""Stage clocks: where the host's time goes on the served path, from the
socket read to the reply write, on the replication link beside it, and in
the event loop around them.

One `StageClock` per node (the engine builds it, the node adopts it — a
CPU-engine node builds its own).  Every host clock on the served path is
taken through it:

    with clock.stage("plan"):
        ...
    with clock.stage("mirror_rebuild", fam):   # `fam` extends the span name
        ...

* **Self time.**  Stages nest per thread.  On exit a stage adds its
  duration MINUS the time its child stages took to `span_<name>_us` and 1
  to `span_<name>_n` (INFO, server/info.py), so the self times of nested
  stages on one thread add up to wall time and never exceed it.  A stage
  left by an exception is closed and counted like any other.  Every
  thread has a stack of its own: the bulk path's staging pool
  (`constdb-stage` threads) counts its `stage_rows` time but never nests
  into the event loop's stages.
* **The event loop's own clock.**  The server's loop polls through
  `TimedSelector`, whose wait in epoll for clients and peers is the stage
  `loop_poll` (`loop_poll_events` sums the ready fds it returned).  No
  other stage is open there, since none spans an `await`, so a window of
  the loop's thread is Σ stage self times + `loop_poll` + the rest
  (asyncio's callbacks, the transports' recv/send where the extension's
  reader and sender do not take them, time off the CPU).
  `gc` is entered from `gc.callbacks` on whichever thread collects,
  nested under the stage it interrupted, tagged with the generation.
  `loop_stats()` reads, at INFO time and from any thread, the loop
  thread's CPU time and context switches, the collections by
  generation, and the heap's frozen share.
* **The frozen heap.**  `freeze_heap()` collects once, then moves every
  object alive to CPython's permanent generation (`gc.freeze()`), which
  no later collection walks.  The node calls it once a table it will
  keep has landed — the boot restore, a full sync (server/node.py
  Node.freeze_heap) — so a full collection walks what was made since,
  not the restored table's blob planes and per-key row lists (a `list`
  entry per row).  A frozen cycle is never freed: the node thaws the
  heap before it drops a keyspace (Node.replace_keyspace), and a freeze
  is skipped while the dropped one is still alive (docs/INVARIANTS.md,
  "The frozen heap").
* **The device trace's clock.**  While a profiler trace runs (the enabled
  check the engine hands in with `jax.profiler.TraceAnnotation`), every
  stage also opens an annotation `cst.<name>[.<tag>]` in the `/host:` plane
  of the same `.xplane.pb` as the device's `XLA Ops`: one clock, no offset
  to estimate — except `gc` of generations 0 and 1, which collect too
  often for a span (`cst.gc.2` only).  With no trace running a stage
  builds no annotation.  This module never imports JAX; without the pair
  (a CPU-engine node, a shard worker) every stage is a counter only.
* **Declared names.**  `STAGES` is the whole vocabulary; INFO prints
  every one from boot, at 0, and a name outside it raises.

Rules for a call site: never hold a stage across an `await` (another
connection's work would be billed to it — the STAGE-AWAIT lint rule), and
never open one inside a per-operation or per-row loop.

`seconds_into(acc, key)` is the second, smaller clock: the inclusive
seconds of a block added to `acc[key]`, for the engine's `family_secs`
(read by bench.py; ROADMAP D1).  It is not a stage: no self time, no
stack.
"""

from __future__ import annotations

import gc
import selectors
import threading
import time
from functools import partial
from time import perf_counter_ns

# served path, in order: socket read -> the loop-pass gather (server/io.py:
# hand-over, concatenation, cut and wake-ups) -> ... -> reply write; then the
# replication link (replica/link.py, replica/coalesce.py): a peer's stream
# in (`repl_ingest` per socket read, `repl_flush` per landed batch) and the
# node's own log out (`repl_push` per drained run and per wake-up's tail);
# then the reader's take (`read_take`, one entry a take of
# server/read_pump.py: the eventfd read, the take call and the loop over
# what it delivered, less the `intake` and pass work nested in it); then
# the loop's poll (`loop_poll`, one entry per iteration) and the
# garbage collector (`gc`, one entry per collection).  `list_index`: a list
# key's ordered index brought up to date and read (store/keyspace.py
# ListIndex) — by a push, LRANGE / LLEN / LREM on either path.
# `key_create`: keys entering the key table — the creation block of a
# merge's key resolution (engine/hostbatch.py resolve_keys: the interner's
# insert of the new keys, the table's block append) and a per-command
# create (store/keyspace.py KeySpace.create_key)
STAGES = ("intake", "gather", "plan", "read_batch", "read_miss", "exec",
          "list_index", "key_create", "serve_flush", "stage_rows", "h2d",
          "dispatch", "host_twin", "mirror_rebuild", "mirror_patch",
          "state_alloc", "d2h_flush",
          "reply_write", "repl_ingest", "repl_flush", "repl_push",
          "read_take", "loop_poll", "gc")
MAX_ANNOTATION = 40     # benchmark/trace_reduce.py cuts a host name at 48

_INDEX = {name: i for i, name in enumerate(STAGES)}
# the longest tag a stage may take: `cst.<name>.<tag>` fits MAX_ANNOTATION
_ROOM = tuple(MAX_ANNOTATION - len(f"cst.{name}.") for name in STAGES)
_GEN = ("0", "1", "2")


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
                 total=None, span: bool = True) -> None:
        i = _INDEX.get(name)
        if i is None:
            raise ValueError(f"stage {name!r} is not declared in "
                             f"stagetime.STAGES {STAGES}")
        if len(tag) > _ROOM[i]:
            raise ValueError(f"annotation cst.{name}.{tag} is over "
                             f"{MAX_ANNOTATION} characters")
        self.clock = clock
        self.i = i
        self.total = total
        self.span = None
        tracing = clock.tracing
        if span and tracing is not None and tracing():
            self.span = clock.annotation(f"cst.{name}.{tag}" if tag
                                         else f"cst.{name}")

    def __enter__(self) -> "_Stage":
        # the span opens before the stage is on the stack and closes after
        # it is off: a collection inside the annotation's own code nests
        # under the parent, whose window holds it
        if self.span is not None:
            self.span.__enter__()
        tls = self.clock._tls
        try:
            th = tls.th
        except AttributeError:
            th = tls.th = self.clock._new_thread()
        self.th = th
        self.parent = th.top
        th.top = self
        self.child = 0
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dt = perf_counter_ns() - self.t0
        th = self.th
        parent = th.top = self.parent
        th.ns[self.i] += dt - self.child
        th.n[self.i] += 1
        if parent is not None:
            parent.child += dt
        if self.total is not None:
            acc, key = self.total
            acc[key] += dt * 1e-9
        if self.span is not None:
            self.span.__exit__(et, ev, tb)
        return False


class StageClock:
    """The accumulator behind INFO `span_<name>_us` / `span_<name>_n`,
    `loop_poll_events` and `loop_stats()`.

    `trace`: a pair (annotation factory taking the span's name, enabled
    check) — `jax.profiler.TraceAnnotation` and its `is_enabled`, handed
    in by TpuMergeEngine — or None (counters only)."""

    def __init__(self, trace=None) -> None:
        self.annotation, self.tracing = trace or (None, None)
        self._tls = threading.local()
        self._threads: list[_Thread] = []
        self._lock = threading.Lock()
        # stage(name, tag="", total=None) -> context manager; `tag` only
        # extends the trace span's name (a family — never per-call data),
        # `total=(acc, key)` also adds the stage's INCLUSIVE seconds to
        # acc[key]
        self.stage = partial(_Stage, self)
        # ready fds summed over the loop's polls (written by its thread)
        self.poll_events = 0
        self._loop_last = (0, 0, 0)  # loop_stats' last reading
        self._gc_open = None
        # seconds in generation-2 collections (INFO gc_full_us): the
        # part of `gc` a frozen heap shortens, told from the young ones
        self._gc_full_s = [0.0]
        # freeze_heap: freezes made, freezes skipped (the dropped
        # keyspace still alive), seconds spent (collect + freeze)
        self.freezes = 0
        self.freeze_skips = 0
        self.freeze_s = 0.0
        self._own_thread()           # the loop's until one attaches

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

    # ------------------------------------------------- the event loop

    def _own_thread(self) -> None:
        """Take the calling thread as the loop's (its CPU clock id is
        taken here, on the thread itself, so any thread can read it)."""
        try:
            cpu = time.pthread_getcpuclockid(threading.get_ident())
        except (AttributeError, OSError):     # not a POSIX thread clock
            cpu = None
        # (CPU clock id, native thread id)
        self._loop = (cpu, threading.get_native_id())

    def attach_loop(self) -> None:
        """Called on the event loop's thread by the server that runs it:
        that thread is the loop's, and every collection enters `gc`."""
        self._own_thread()
        if self._gc_hook not in gc.callbacks:
            gc.callbacks.append(self._gc_hook)

    def detach_loop(self) -> None:
        if self._gc_hook in gc.callbacks:
            gc.callbacks.remove(self._gc_hook)

    def _gc_hook(self, phase: str, info: dict) -> None:
        if phase == "start":
            gen = info["generation"]
            st = _Stage(self, "gc", _GEN[gen], span=gen == 2,
                        total=(self._gc_full_s, 0) if gen == 2 else None)
            st.__enter__()
            self._gc_open = st
        elif self._gc_open is not None:
            st, self._gc_open = self._gc_open, None
            st.__exit__(None, None, None)

    def freeze_heap(self, dropped=None) -> int:
        """Collect, then freeze every object alive (the module docstring:
        the frozen heap) — unless `dropped`, a weak reference to the
        keyspace the node last dropped, is still alive after the
        collection: something holds the old table, which a freeze would
        keep for good, so the freeze is skipped and counted.
        -> `gc.get_freeze_count()` after it."""
        t0 = perf_counter_ns()
        gc.collect()
        if dropped is not None and dropped() is not None:
            self.freeze_skips += 1
        else:
            gc.freeze()
            self.freezes += 1
        self.freeze_s += (perf_counter_ns() - t0) * 1e-9
        return gc.get_freeze_count()

    def loop_stats(self) -> list:
        """[(INFO field, value)]: the loop thread's CPU time in whole
        microseconds, its voluntary and involuntary context switches
        (`/proc/self/task/<tid>/status`), and the collections of each
        generation (`gc.get_stats()`) and the time in generation-2 ones,
        the objects frozen now, and this clock's `freeze_heap` freezes,
        skips and time.  Where a clock or the file cannot be
        read (not Linux, or the thread is gone) the last reading stays, 0
        from boot."""
        cpu_id, tid = self._loop
        cpu_us, nv, niv = self._loop_last
        try:
            if cpu_id is not None:
                cpu_us = time.clock_gettime_ns(cpu_id) // 1000
            with open(f"/proc/self/task/{tid}/status") as f:
                for line in f:
                    if line.startswith("voluntary_ctxt_switches:"):
                        nv = int(line.split()[1])
                    elif line.startswith("nonvoluntary_ctxt_switches:"):
                        niv = int(line.split()[1])
        except (OSError, ValueError, IndexError):
            pass
        self._loop_last = (cpu_us, nv, niv)
        out = [("loop_poll_events", self.poll_events),
               ("loop_cpu_us", cpu_us), ("loop_nvcsw", nv),
               ("loop_nivcsw", niv)]
        out += [(f"gc_collections_gen{g}", st["collections"])
                for g, st in enumerate(gc.get_stats())]
        out += [("gc_full_us", int(self._gc_full_s[0] * 1e6)),
                ("gc_frozen_objects", gc.get_freeze_count()),
                ("gc_freezes", self.freezes),
                ("gc_freeze_skips", self.freeze_skips),
                ("gc_freeze_us", int(self.freeze_s * 1e6))]
        return out


class _TimedPoll:
    """A selector's epoll (or poll) object whose `poll` — the wait in the
    system call — is the stage `loop_poll`; everything else passes
    through."""

    __slots__ = ("inner", "sel")

    def __init__(self, inner, sel: "TimedSelector") -> None:
        self.inner = inner
        self.sel = sel

    def poll(self, *args):
        clock = self.sel.clock
        if clock is None:
            return self.inner.poll(*args)
        with clock.stage("loop_poll"):
            ready = self.inner.poll(*args)
        clock.poll_events += len(ready)
        return ready

    def __getattr__(self, name: str):
        return getattr(self.inner, name)


class TimedSelector(selectors.DefaultSelector):
    """The server loop's selector (Linux: epoll): its `select()` waits in
    the stage `loop_poll` of the clock `watch` hands it, untimed until
    then.  The stage holds the system call alone: turning the ready fds
    into keys is CPU work on asyncio's side of the loop (two thirds of a
    busy `select()`), so `loop_poll` and the thread's CPU time overlap
    only by the call's own entry into the kernel."""

    clock = None

    def __init__(self) -> None:
        super().__init__()
        self._selector = _TimedPoll(self._selector, self)

    def watch(self, clock: StageClock) -> None:
        """On the loop's thread, once the node (and its clock) exists."""
        self.clock = clock
        clock.attach_loop()


class seconds_into:
    """Inclusive seconds of the block, added to `acc[key]` (see the module
    docstring: the engine's `family_secs`, not a stage)."""

    __slots__ = ("acc", "key", "t0")

    def __init__(self, acc, key) -> None:
        self.acc = acc
        self.key = key

    def __enter__(self) -> None:
        self.t0 = perf_counter_ns()

    def __exit__(self, et, ev, tb) -> bool:
        self.acc[self.key] += (perf_counter_ns() - self.t0) * 1e-9
        return False
