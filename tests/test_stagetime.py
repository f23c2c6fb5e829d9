"""Stage clocks on the served path (utils/stagetime.py) and the counters
that share their boundaries.

Pinned here:
  * self time: nested stages on one thread add up to the outer
    duration, a child's time is never counted twice, an exception still
    closes and counts the stage, threads never nest into each other;
  * the vocabulary is closed (an undeclared name raises) and a stage
    opens its trace span `cst.<name>[.<tag>]` only while a trace runs
    (the engine's enabled check), a young generation's `gc` never;
  * the event loop's own clock: `loop_poll` one entry per iteration of
    a loop built by bin/server's factory, never under another stage; a
    forced collection is the stage `gc`, taken out of the stage it
    interrupted; the loop thread's CPU time, context switches and the
    collections by generation in INFO from boot, never decreasing;
  * INFO lists every `span_*`, `merge_rows_*`, `mirror_rebuilds_cause_*`
    and `mirror_patch*` field from boot, at 0 — `readers._delta`
    (benchmark/readers.py) reads a missing counter as "no metric", and a
    traced line on the chip is refused for a missing metric;
  * a pipelined chunk through a real ServerApp socket moves the loop's
    stages, and their sum stays under the wall time of the exchange;
  * a device engine on JAX-CPU counts merged rows by path, mirror
    rebuilds by cause and mirror patches with their rows (a row-scoped
    write is patched, a GC rebuilds), the engine's inclusive
    `family_secs` still read above 0, and the INFO totals that overlapped
    the stages are gone;
  * every counter a per-layer metric names — the twenty-four in
    BENCHMARK.json (the gather's four and the `reg` rows, and the list
    index's and the plane grows' five among them) and the twenty-four specs
    of docs/stage_layers/ — is an INFO key of a
    device-engine node, and the existing readers turn each spec into a
    number.
"""

import asyncio
import gc
import glob
import json
import os
import socket
import sys
import threading
import time

import pytest

from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.server import info as info_mod
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer
from constdb_tpu.store.keyspace import JOURNAL_FAMILIES, TOUCH_CAUSES
from constdb_tpu.utils import stagetime
from constdb_tpu.utils.stagetime import STAGES, StageClock, TimedSelector

from cluster_util import FAST, Client
from test_serve_coalesce import cmd, read_replies

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMS = ("env", "reg", "cnt", "el")
# the replication link's counters beside its three stages (server/info.py)
LINK_COUNTERS = ["repl_apply_lag_ms_sum", "repl_apply_lag_n", "repl_ops_out",
                 "merge_rows_repl", "merge_rows_serve"]
# the loop-pass gather's counters beside its stage (server/io.py)
GATHER_COUNTERS = ["serve_gather_passes", "serve_gather_msgs",
                   "serve_gather_conns", "serve_lone_cmds"]
# the micro round's link protocol (engine/tpu.py _scatter_pair): scatters
# that returned a win vector / fell back to `src`, rows applied from vectors
MICRO_COUNTERS = ["micro_win_scatters", "micro_src_scatters",
                  "micro_win_rows"]
# the list index's counters beside its stage (server/commands.py
# list_positions) and the engine's plane grows, apart from its rebuilds
LIST_COUNTERS = ["list_inserts", "list_pos_bytes_sum"]
GROW_COUNTERS = [f"mirror_grows_{f}" for f in ("cnt", "el", "env", "reg",
                                               "tns")]
# keys created under load (server/serve.py run_chunk) and the read batches
# that landed their run to read a key it created, beside `key_create`
KEY_COUNTERS = ["serve_keys_created", "serve_read_flushes_created"]
# the event loop's four metrics (PR 39), specified for every cell
LOOP_SPECS = {"loop_poll_share.serve", "loop_cpu_share.serve",
              "loop_preempt_per_kop.serve", "gc_pause_share.serve"}
CELLS = ["ycsb-b", "ycsb-a", "aa-3node-ycsb-a", "memtier-default"]
# the reply write's three, in every cell since the reply sender
REPLY_SPECS = {"reply_write_us_per_op.serve", "reply_pump_share.serve",
               "reply_sender_busy_share.serve"}
# the reader's three, in all five cells
READ_SPECS = {"read_pump_share.serve", "reader_busy_share.serve",
              "read_take_us_per_op.serve"}


@pytest.fixture
def ticks(monkeypatch):
    """perf_counter_ns replaced by a counter: every read advances 1 ns,
    so durations are exact and the arithmetic can be asserted."""
    t = [0]
    lock = threading.Lock()

    def fake() -> int:
        with lock:
            t[0] += 1
            return t[0]
    monkeypatch.setattr(stagetime, "perf_counter_ns", fake)
    return t


def raw(clock: StageClock) -> dict:
    """{name: (self ns, entries)} — snapshot() rounds to microseconds."""
    return {name: (sum(t.ns[i] for t in clock._threads),
                   sum(t.n[i] for t in clock._threads))
            for i, name in enumerate(STAGES)}


def info_of(node) -> dict:
    out: list = []
    info_mod._section_stats(node, out)
    return dict(out)


def device_node(warmup: int = 1):
    pytest.importorskip("jax")
    from constdb_tpu.engine.tpu import TpuMergeEngine
    eng = TpuMergeEngine(resident=True, steady=True, warmup=warmup)
    return Node(node_id=1, engine=eng), eng


# ------------------------------------------------------------- the helper


def test_nested_self_times_add_up_to_the_outer_duration(ticks):
    clock = StageClock()
    with clock.stage("plan"):              # t0 = 1
        with clock.stage("read_batch"):    # t0 = 2
            with clock.stage("read_miss"):  # t0 = 3, exit 4
                pass
        # read_batch exit 5
        with clock.stage("exec"):          # t0 = 6, exit 7
            pass
    # plan exit 8
    got = raw(clock)
    assert got["read_miss"] == (1, 1)
    assert got["read_batch"] == (3 - 1, 1)
    assert got["exec"] == (1, 1)
    assert got["plan"] == (7 - 3 - 1, 1)
    assert sum(ns for ns, _ in got.values()) == 7   # the outer duration


def test_child_time_is_not_counted_twice(ticks):
    clock = StageClock()
    with clock.stage("serve_flush"):
        for _ in range(3):
            with clock.stage("host_twin"):
                with clock.stage("h2d"):
                    pass
    got = raw(clock)
    # each grandchild's tick belongs to h2d alone: its parent and its
    # grandparent both leave it out
    assert got["h2d"] == (3, 3)
    assert got["host_twin"] == (3 * 2, 3)
    whole = 1 + 3 * 4        # one read per enter and per exit below it
    assert got["serve_flush"][0] == whole - 3 * 3
    assert sum(ns for ns, _ in got.values()) == whole


def test_exception_still_closes_and_counts_the_stage(ticks):
    clock = StageClock()
    with pytest.raises(KeyError):
        with clock.stage("plan"):
            with clock.stage("exec"):
                raise KeyError("boom")
    got = raw(clock)
    assert got["exec"][1] == 1 and got["plan"][1] == 1
    assert clock._tls.th.top is None       # the stack is clean
    with clock.stage("intake"):            # and the next stage is a root
        pass
    assert raw(clock)["intake"] == (1, 1)


def test_threads_do_not_nest_into_each_other(ticks):
    clock = StageClock()
    inside = threading.Event()
    done = threading.Event()

    def worker() -> None:
        inside.wait(5)
        with clock.stage("stage_rows"):
            pass
        done.set()

    th = threading.Thread(target=worker)
    th.start()
    with clock.stage("dispatch"):
        inside.set()
        assert done.wait(5)
    th.join(5)
    assert not th.is_alive()
    got = raw(clock)
    assert got["stage_rows"] == (1, 1)
    # the worker's stage ran while `dispatch` was open on this thread and
    # took nothing from it: dispatch keeps its whole 3 ticks
    assert got["dispatch"] == (3, 1)
    assert len(clock._threads) == 2


def test_undeclared_stage_and_long_annotation_raise():
    clock = StageClock()
    with pytest.raises(ValueError, match="not declared"):
        clock.stage("spans")
    # a tag that would cut the span's name raises with or without a trace
    for spans in (clock, StageClock(trace=(lambda name: None, lambda: True))):
        with pytest.raises(ValueError, match="over 40"):
            spans.stage("mirror_rebuild", "x" * 30)
    assert all(len(f"cst.{name}.tns_read") <= stagetime.MAX_ANNOTATION
               for name in STAGES)


class Spans:
    """A fake annotation factory and enabled check: what was opened and
    closed, and a switch for "a trace runs"."""

    def __init__(self) -> None:
        self.seen = []
        self.on = False

    def __call__(self, name: str):
        seen = self.seen

        class Span:
            def __enter__(self):
                seen.append(("enter", name))

            def __exit__(self, *exc) -> None:
                seen.append(("exit", name))
        return Span()

    def enabled(self) -> bool:
        return self.on


def test_every_stage_opens_its_span_only_while_a_trace_runs():
    spans = Spans()
    clock = StageClock(trace=(spans, spans.enabled))
    for name in STAGES:
        with clock.stage(name):
            pass
    assert spans.seen == []          # no trace: no annotation is built
    spans.on = True
    for name in STAGES:
        with clock.stage(name):
            pass
    with pytest.raises(RuntimeError):
        with clock.stage("mirror_rebuild", "el"):
            raise RuntimeError
    names = [n for what, n in spans.seen if what == "enter"]
    assert names == [f"cst.{n}" for n in STAGES] + ["cst.mirror_rebuild.el"]
    assert spans.seen.count(("exit", "cst.mirror_rebuild.el")) == 1
    # without the pair every stage is a counter only
    plain = StageClock()
    with plain.stage("d2h_flush", "el"):
        pass
    assert plain.snapshot()["d2h_flush"][1] == 1


@pytest.mark.parametrize("name,tag", [
    ("intake", ""), ("plan", ""), ("read_miss", ""), ("reply_write", ""),
    ("repl_ingest", ""), ("loop_poll", ""),          # once a chunk or more
    ("serve_flush", ""), ("d2h_flush", ""), ("mirror_patch", "reg"),
    ("dispatch", "tns_read")])                        # once a flush or rarer
def test_a_span_opens_only_when_the_enabled_check_is_true(name, tag):
    spans = Spans()
    clock = StageClock(trace=(spans, spans.enabled))
    label = f"cst.{name}.{tag}" if tag else f"cst.{name}"
    for on in (False, True, False):
        spans.on = on
        before = len(spans.seen)
        with clock.stage(name, tag):
            assert spans.seen[before:] == ([("enter", label)] if on else [])
    assert spans.seen == [("enter", label), ("exit", label)]
    assert clock.snapshot()[name][1] == 3     # counted every time


def test_inclusive_totals_ride_the_same_clock(ticks):
    clock = StageClock()
    acc = {"flush": 0.0, "micro": 0.0}
    with stagetime.seconds_into(acc, "micro"):          # 1 .. 6
        with clock.stage("d2h_flush", total=(acc, "flush")):   # 2 .. 5
            with clock.stage("h2d"):                    # 3 .. 4
                pass
    assert acc["flush"] == pytest.approx(3e-9)   # inclusive: child and all
    assert acc["micro"] == pytest.approx(5e-9)
    assert raw(clock)["d2h_flush"] == (2, 1)     # self time leaves it out


def test_snapshot_lists_every_stage_in_whole_microseconds(ticks):
    clock = StageClock()
    assert clock.snapshot() == {name: (0, 0) for name in STAGES}
    ticks[0] = 0
    with clock.stage("plan"):
        ticks[0] += 2500
    assert clock.snapshot()["plan"] == (2, 1)


# ------------------------------------------------ the event loop's clock


@pytest.fixture
def collector():
    """Automatic collection off (only the test's own `gc.collect` runs
    the hook), and the hook of every clock attached here taken off."""
    clocks = []
    was = gc.isenabled()
    gc.disable()
    yield clocks
    for c in clocks:
        c.detach_loop()
    if was:
        gc.enable()


@pytest.mark.parametrize("rounds", [1, 4, 16])
def test_loop_poll_is_one_entry_per_iteration_and_never_nested(rounds):
    """A loop built by bin/server's factory over a socket pair: every
    iteration polls once, inside `loop_poll`, with no stage open around
    it; a byte written is a ready fd that poll returns."""
    from constdb_tpu.bin.server import loop_factory
    clock = StageClock()
    sel = TimedSelector()
    parents = []
    epoll = sel._selector.inner

    class Spy:
        """The epoll object, noting the stack at each timed wait."""

        def poll(self, *args):
            if sel.clock is not None:
                top = clock._tls.th.top
                assert STAGES[top.i] == "loop_poll"
                parents.append(top.parent)
            return epoll.poll(*args)

        def __getattr__(self, name):
            return getattr(epoll, name)
    sel._selector.inner = Spy()
    loop = loop_factory(sel)()
    a, b = socket.socketpair()
    a.setblocking(False)
    b.setblocking(False)
    iterations = [0]
    run_once = loop._run_once

    def counted() -> None:
        iterations[0] += 1
        run_once()
    loop._run_once = counted
    got = []

    async def main() -> None:
        sel.watch(clock)
        done = loop.create_future()

        def readable() -> None:
            got.append(b.recv(64))
            with clock.stage("plan"):        # a stage inside a callback
                pass
            if len(got) == rounds:
                done.set_result(None)
            else:
                loop.call_soon(a.send, b"x")
        loop.add_reader(b.fileno(), readable)
        a.send(b"x")
        await done
        loop.remove_reader(b.fileno())

    try:
        loop.run_until_complete(main())
    finally:
        clock.detach_loop()
        loop.close()
        a.close()
        b.close()
    snap = clock.snapshot()
    # every poll after `watch` is one entry; the first iteration polled
    # before the clock was handed over
    assert snap["loop_poll"][1] == iterations[0] - 1 == len(parents)
    assert parents and all(p is None for p in parents)
    assert len(got) == rounds and snap["plan"][1] == rounds
    assert clock.poll_events >= rounds
    assert clock.loop_stats()[0] == ("loop_poll_events", clock.poll_events)


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_forced_collection_is_taken_out_of_the_stage_it_interrupts(
        generation, ticks, collector):
    spans = Spans()
    spans.on = True
    clock = StageClock(trace=(spans, spans.enabled))
    clock.attach_loop()
    collector.append(clock)
    with clock.stage("plan"):                    # 1
        with clock.stage("read_miss"):           # 2, exit 3
            pass
        gc.collect(generation)                   # gc 4 .. 5
    # plan exit 6
    got = raw(clock)
    assert got["gc"] == (1, 1)
    assert got["read_miss"] == (1, 1)
    assert got["plan"] == (5 - 1 - 1, 1)         # neither child billed
    assert sum(ns for ns, _ in got.values()) == 5     # = plan's wall time
    # a young generation writes no span; the eldest writes cst.gc.2
    opened = [n for what, n in spans.seen if what == "enter"]
    assert opened.count("cst.gc.2") == (generation == 2)
    assert not any(n in ("cst.gc.0", "cst.gc.1", "cst.gc") for n in opened)
    clock.detach_loop()
    gc.collect(generation)                       # no hook: nothing counted
    assert raw(clock)["gc"] == (1, 1)


def test_a_real_collection_pause_is_its_own_stage(collector):
    """The real clock: a generation-2 collection over a heap of cycles
    inside an open stage; the stage's self time leaves it out and the
    self times sum to at most the wall time."""
    clock = StageClock()
    clock.attach_loop()
    collector.append(clock)
    n0 = clock.snapshot()["gc"][1]
    t0 = time.perf_counter_ns()
    with clock.stage("exec"):
        junk = [[] for _ in range(200_000)]
        for x in junk:
            x.append(x)                  # cycles only a collection frees
        del junk, x
        t1 = time.perf_counter_ns()
        gc.collect(2)
        t2 = time.perf_counter_ns()
    wall_ns = time.perf_counter_ns() - t0
    got = raw(clock)
    assert got["gc"][1] == n0 + 1
    assert 0 < got["gc"][0] <= t2 - t1
    assert got["exec"][0] <= wall_ns - got["gc"][0]
    assert got["exec"][0] + got["gc"][0] <= wall_ns


@pytest.mark.parametrize("engine", ["cpu", "device"])
def test_loop_counters_are_in_info_from_boot_and_never_decrease(engine):
    node = device_node()[0] if engine == "device" else Node(node_id=2)
    fields = ["loop_poll_events", "loop_cpu_us", "loop_nvcsw",
              "loop_nivcsw"] + [f"gc_collections_gen{g}" for g in range(3)]
    first = info_of(node)
    assert all(isinstance(first[k], int) and first[k] >= 0 for k in fields)
    assert first["loop_poll_events"] == 0
    assert first["span_loop_poll_n"] == first["span_gc_n"] == 0
    # the thread that built the clock is the loop's until one attaches
    t = time.thread_time()
    while time.thread_time() - t < 0.02:
        pass
    time.sleep(0.01)                      # a voluntary switch
    gc.collect(2)
    second = info_of(node)
    assert all(second[k] >= first[k] for k in fields)
    assert second["loop_cpu_us"] >= first["loop_cpu_us"] + 10_000
    assert second["gc_collections_gen2"] > first["gc_collections_gen2"]
    if os.path.exists(f"/proc/self/task/{threading.get_native_id()}"):
        assert second["loop_nvcsw"] > first["loop_nvcsw"]
    # read from another thread, the loop thread's clocks are the same
    out = {}
    th = threading.Thread(target=lambda: out.update(info_of(node)))
    th.start()
    th.join(5)
    assert out["loop_cpu_us"] >= second["loop_cpu_us"]
    assert out["loop_cpu_us"] < second["loop_cpu_us"] + 10_000


def test_served_loop_splits_its_window_into_stages_poll_and_the_rest(
        tmp_path):
    """A ServerApp on a loop built like bin/server's, one socket
    exchange: Σ stage self times + `loop_poll` stay under the wall time
    of the loop's thread, and so do its CPU time + `loop_poll`."""
    from constdb_tpu.bin.server import loop_factory
    sel = TimedSelector()

    async def main():
        node = Node(node_id=1)
        sel.watch(node.stages)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            t0 = time.perf_counter()
            before = info_of(node)
            chunk = [cmd(b"hset", b"h%d" % (i % 4), b"f%d" % i, b"v")
                     for i in range(24)]
            for _ in range(5):
                c.writer.write(b"".join(encode_msg(m) for m in chunk))
                await c.writer.drain()
                await read_replies(c, bytearray(), len(chunk))
                await asyncio.sleep(0.01)        # the loop waits in poll
            after = info_of(node)
            return before, after, (time.perf_counter() - t0) * 1e6
        finally:
            node.stages.detach_loop()
            await c.close()
            await app.close()

    before, after, wall_us = asyncio.run(main(),
                                         loop_factory=loop_factory(sel))

    def moved(k: str) -> int:
        return after[k] - before[k]
    poll = moved("span_loop_poll_us")
    stages = sum(moved(f"span_{s}_us") for s in STAGES if s != "loop_poll")
    assert poll >= 5 * 10_000 * 0.9 and moved("span_loop_poll_n") >= 10
    assert moved("loop_poll_events") >= 5
    assert stages + poll <= wall_us
    assert moved("loop_cpu_us") + poll <= wall_us * 1.01
    assert moved("span_reply_write_n") >= 5


# ------------------------------------------------------------------- INFO


def test_info_of_a_fresh_node_lists_every_counter_at_zero():
    node, _eng = device_node()
    info = info_of(node)
    want = [f"span_{s}_{k}" for s in STAGES for k in ("us", "n")]
    want += [f"merge_rows_{p}_{f}" for p in ("dev", "host") for f in FAMS]
    want += [f"mirror_rebuilds_cause_{c}" for c in TOUCH_CAUSES]
    want += [f"mirror_patch{k}_{f}" for k in ("es", "_rows")
             for f in JOURNAL_FAMILIES] + ["mirror_patch_overflows"]
    want += LINK_COUNTERS + GATHER_COUNTERS + MICRO_COUNTERS
    want += ["loop_poll_events"] + LIST_COUNTERS + GROW_COUNTERS
    want += KEY_COUNTERS
    assert len(want) == 2 * 24 + 8 + 6 + 7 + 5 + 4 + 3 + 1 + 2 + 5 + 2
    assert STAGES.index("gather") == 1
    assert STAGES[-2:] == ("loop_poll", "gc")
    assert {k: info.get(k) for k in want} == dict.fromkeys(want, 0)
    # the overlapping inclusive totals are gone (ROADMAP D9)
    assert not [k for k in info if k.endswith("_seconds_total")
                or (k.startswith("merge_") and k.endswith("_seconds"))
                or k == "merge_rows_per_sec"]
    # a CPU-engine node has the clock, not the device engine's counters
    cpu = info_of(Node(node_id=2))
    assert all(cpu[f"span_{s}_us"] == 0 for s in STAGES)
    assert all(cpu[k] == 0 for k in LINK_COUNTERS + GATHER_COUNTERS
               + LIST_COUNTERS + KEY_COUNTERS)
    assert "merge_rows_dev_el" not in cpu
    assert not any(k in cpu for k in MICRO_COUNTERS)


def test_node_adopts_the_engines_clock():
    node, eng = device_node()
    assert node.stages is eng.stages
    import jax
    ann = jax.profiler.TraceAnnotation
    assert (eng.stages.annotation, eng.stages.tracing) == \
        (ann, ann.is_enabled)
    assert not eng.stages.tracing()              # no trace runs here
    plain = Node(node_id=2).stages
    assert plain.annotation is None and plain.tracing is None


def test_pipelined_chunk_through_a_socket_moves_the_loop_stages(tmp_path):
    async def main():
        node = Node(node_id=1)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        c = await Client().connect(app.advertised_addr)
        try:
            chunk = [cmd(b"hset", b"h%d" % (i % 4), b"f%d" % i, b"v")
                     for i in range(24)]
            chunk += [cmd(b"hgetall", b"h%d" % i) for i in range(4)]
            t0 = time.perf_counter()
            for _ in range(3):      # the repeats hit the reply cache too
                c.writer.write(b"".join(encode_msg(m) for m in chunk))
                await c.writer.drain()
                await read_replies(c, bytearray(), len(chunk))
            wall_us = (time.perf_counter() - t0) * 1e6
            return info_of(node), wall_us, node.stats
        finally:
            await c.close()
            await app.close()

    info, wall_us, st = asyncio.run(main())
    for s in ("read_take", "intake", "gather", "plan", "read_batch",
              "serve_flush", "reply_write"):
        assert info[f"span_{s}_us"] > 0 and info[f"span_{s}_n"] > 0, s
    # one connection, three reads: three takes of the reader, each a pass
    # of the gather of 28 messages (no hand-over: the take joins them)
    assert info["serve_gather_passes"] == info["serve_gather_conns"] == 3
    assert info["serve_gather_msgs"] == 3 * 28
    assert info["span_gather_n"] == 3 and info["serve_lone_cmds"] == 0
    assert info["span_read_take_n"] >= 3
    assert info["span_read_miss_n"] > 0
    assert info["span_serve_flush_n"] == st.serve_flushes
    total = sum(info[f"span_{s}_us"] for s in STAGES)
    assert total < wall_us
    # fewer than one stage entry per operation
    assert sum(info[f"span_{s}_n"] for s in STAGES) < 3 * 28 + 6


def test_a_peers_stream_moves_the_links_stages_and_counters(tmp_path):
    """Two nodes, one MEET, pipelined writes at each: the pusher's
    `repl_push` and `repl_ops_out`, the puller's `repl_ingest`,
    `repl_flush`, lag and row counters all move; a node with no peer
    never enters them."""
    from cluster_util import close_cluster, converge, make_cluster

    async def main():
        apps = await make_cluster(2, str(tmp_path), serve_batch=512)
        a, b = apps
        try:
            ca = await Client().connect(a.advertised_addr)
            cb = await Client().connect(b.advertised_addr)
            await ca.cmd("meet", b.advertised_addr)
            for c, tag in ((ca, b"a"), (cb, b"b")):
                chunk = [cmd(b"hset", b"h%d" % (i % 4), tag + b"%d" % i,
                             b"v") for i in range(24)]
                for _ in range(3):
                    c.writer.write(b"".join(encode_msg(m) for m in chunk))
                    await c.writer.drain()
                    await read_replies(c, bytearray(), len(chunk))
            await converge(apps, timeout=20)
            await ca.close()
            await cb.close()
            return info_of(a.node), info_of(b.node)
        finally:
            await close_cluster(apps)

    for info in asyncio.run(main()):
        for s in ("repl_ingest", "repl_flush", "repl_push"):
            assert info[f"span_{s}_n"] > 0, s
        assert info["span_repl_ingest_us"] + info["span_repl_flush_us"] > 0
        assert info["repl_ops_out"] >= 72          # its own 72 writes, out
        assert info["repl_apply_lag_n"] >= 72      # the peer's 72, landed
        assert 0 <= info["repl_apply_lag_ms_sum"] < 72 * 20_000
        assert info["merge_rows_repl"] >= 2 * 72   # a key row + an el row
        assert info["merge_rows_serve"] > 0
        assert info["repl_apply_lag_n"] == info["repl_frames_coalesced"]
    lone = info_of(Node(node_id=9))
    assert lone["span_repl_push_n"] == lone["span_repl_ingest_n"] == 0


# ------------------------------------------------------- the device engine


def sadd_round(node, first: int, members: int = 6) -> None:
    """One coalesced run of `members` SADDs of distinct members — one
    element row each — landed as a single micro round."""
    out = bytearray()
    ServeCoalescer(node).run_chunk(
        [cmd(b"sadd", b"s", b"m%d" % (first + i)) for i in range(members)],
        out)
    assert out.count(b":1\r\n") == members


def test_device_engine_counts_rows_by_path_and_rebuilds_by_cause():
    node, eng = device_node(warmup=1)
    sadd_round(node, 0)          # cold plane: host twin
    assert eng.merge_rows_host["el"] == 6 and eng.merge_rows_dev["el"] == 0
    sadd_round(node, 6)          # stable for `warmup` rounds: device
    assert eng.merge_rows_dev["el"] == 6
    assert eng.mirror_rebuilds["el"] == 0        # a first build, no rebuild
    # a lone command takes the exact per-command path, which marks the
    # el plane host-modified: the mirror is stale
    out = bytearray()
    ServeCoalescer(node).run_chunk([cmd(b"sadd", b"s", b"lone")], out)
    assert node.ks.fam_cause["el"] == "client_op"
    sadd_round(node, 12)         # version moved: host twin again
    sadd_round(node, 18)         # stable again: device, after a PATCH of
    info = info_of(node)         # the lone row and the twin's six
    assert info["merge_rows_host_el"] == 12
    assert info["merge_rows_dev_el"] == 12
    assert info["merge_rows_host_env"] == 24     # one key row a command,
    assert info["merge_rows_dev_env"] == 0       # always on the host
    assert info["mirror_rebuilds_el"] == 0       # (PR 31: was 1 rebuild)
    assert info["mirror_patches_el"] == 1
    assert info["mirror_patch_rows_el"] == 1 + 6
    assert info["mirror_patch_overflows"] == 0
    # both device rounds returned their win vector; the first is applied
    # (the lone command's flush: six new members, six winners), the
    # second still waits for the read barrier below
    assert info["micro_win_scatters"] == 2
    assert info["micro_src_scatters"] == 0
    assert info["micro_win_rows"] == 6
    for s in ("serve_flush", "stage_rows", "h2d", "dispatch", "host_twin",
              "mirror_rebuild", "mirror_patch", "state_alloc"):
        assert info[f"span_{s}_n"] > 0, s
    assert info["span_mirror_rebuild_n"] == 2    # first build + one grow
    assert info["span_mirror_patch_n"] == 1
    assert info["span_host_twin_n"] == 4 + 2     # env every round, el twice
    # the lone command flushed before it touched the plane; the read
    # barrier below flushes the two rounds since
    assert info["span_d2h_flush_n"] == 1
    node.ensure_flushed()
    assert info_of(node)["span_d2h_flush_n"] == 2
    assert info_of(node)["micro_win_rows"] == 12
    # rows moved (a GC's cause): the journal is whole, the plane rebuilds
    node.ks.touch("el", cause="gc")
    sadd_round(node, 24)
    sadd_round(node, 30)
    info = info_of(node)
    assert info["mirror_rebuilds_el"] == 1 and info["mirror_patches_el"] == 1
    assert info["mirror_rebuilds_cause_gc"] == 1
    assert sum(info[f"mirror_rebuilds_cause_{c}"]
               for c in TOUCH_CAUSES) == 1
    assert info["span_mirror_rebuild_n"] == 3
    assert node.canonical() is not None


def test_touch_keeps_the_last_cause_and_refuses_an_unknown_one():
    node = Node(node_id=1)
    ks = node.ks
    assert set(ks.fam_cause.values()) == {"reset"}
    ks.touch("el", "env", cause="gc")
    ks.touch("env")
    assert ks.fam_cause["el"] == "gc" and ks.fam_cause["env"] == "client_op"
    ver = dict(ks.fam_ver)
    with pytest.raises(ValueError, match="touch cause"):
        ks.touch("el", cause="because")
    assert ks.fam_ver == ver
    ks.version += 1
    assert set(ks.fam_cause.values()) == {"reset"}


def test_a_device_node_writes_its_stages_into_a_running_trace(
        tmp_path, collector):
    """The engine's pair is jax's: under a real profiler trace (JAX-CPU)
    a per-chunk stage (`plan`) lands in the host plane beside the
    per-flush ones, a generation-2 collection as `cst.gc.2`, a young
    one not at all."""
    import jax
    from jax.profiler import ProfileData
    node, _eng = device_node(warmup=0)
    node.stages.attach_loop()
    collector.append(node.stages)
    sadd_round(node, 0)                  # first build, outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sadd_round(node, 6)
        node.ensure_flushed()
        gc.collect(2)
        gc.collect(0)
    finally:
        jax.profiler.stop_trace()
    sadd_round(node, 12)                 # after the trace: no annotation
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("cst.")]
    assert {"cst.plan", "cst.serve_flush", "cst.d2h_flush",
            "cst.gc.2"} <= set(names)
    assert names.count("cst.plan") == 1 and names.count("cst.gc.2") == 1
    assert not any(n.startswith(("cst.gc.0", "cst.gc.1")) for n in names)


def test_documented_totals_still_read_above_zero():
    """The engine's inclusive `family_secs` (bench.py reads them, ROADMAP
    D1) still read above 0; INFO's overlapping totals are gone, and the
    stage counters cover what they timed: a merge is the engine's
    stages, a flush `d2h_flush`."""
    node, eng = device_node(warmup=0)
    sadd_round(node, 0)
    sadd_round(node, 6)
    node.ensure_flushed()
    info = info_of(node)
    assert eng.family_secs["micro"] > 0 and eng.family_secs["flush"] > 0
    for field in ("merge_seconds_total", "flush_seconds_total",
                  "merge_micro_seconds", "merge_flush_seconds"):
        assert field not in info, field
    assert not hasattr(node.stats, "secs")
    # what merge_seconds_total timed: the engine's stages of a round
    merge_us = sum(info[f"span_{s}_us"] for s in (
        "stage_rows", "h2d", "dispatch", "host_twin", "mirror_rebuild",
        "mirror_patch", "state_alloc"))
    assert merge_us > 0 and info["span_dispatch_n"] >= 1
    # what flush_seconds_total timed: d2h_flush, one entry a flush, and
    # no longer than the inclusive total the engine keeps
    assert info["span_d2h_flush_n"] >= 1 and info["span_d2h_flush_us"] > 0
    assert info["span_d2h_flush_us"] <= eng.family_secs["flush"] * 1e6 + 1
    assert merge_us <= (eng.family_secs["micro"]
                        + eng.family_secs["flush"]) * 1e6 + 1
    assert not hasattr(eng, "stage_secs")        # replaced by stage_rows
    # the legacy whole-round host fallback (steady off) feeds `host`
    from constdb_tpu.engine.tpu import TpuMergeEngine
    off = TpuMergeEngine(resident=True, steady=False)
    n2 = Node(node_id=2, engine=off)
    sadd_round(n2, 0)
    assert off.family_secs["host"] > 0
    assert off.merge_rows_host["el"] == 6 and off.merge_rows_host["env"] == 6
    assert info_of(n2)["span_host_twin_n"] == 1


# ------------------------------------------- the metrics that read them


def layer_specs() -> list:
    """Every per-layer metric BENCHMARK.json names, and the twenty-four the
    stage counters are for (docs/stage_layers/: a `benchmark` PR moves
    them under benchmark/layers/ — see docs/stage_layers/README.md)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    paths = [os.path.join(ROOT, "benchmark", "layers", f"{m['name']}.json")
             for m in manifest["per_layer"]]
    paths += sorted(glob.glob(os.path.join(ROOT, "docs", "stage_layers",
                                           "*.json")))
    specs = []
    for p in paths:
        with open(p) as f:
            specs.append(json.load(f))
    return specs


def counters_of(spec: dict) -> list:
    named = list(spec.get("numerator", [])) + list(spec.get("counters", []))
    named += list(spec.get("part", [])) + list(spec.get("rest", []))
    if "per_counter" in spec:
        named.append(spec["per_counter"])
    return named


def test_every_counter_a_layer_file_names_is_in_info():
    node, _eng = device_node()
    # compile_cache_* appear once the process has a compile cache, as the
    # served node always has (bin/server.py enable_compile_cache)
    from constdb_tpu import conf
    had = conf.COMPILE_CACHE["dir"]
    conf.COMPILE_CACHE["dir"] = had or "/nonexistent"
    try:
        info = info_of(node)
    finally:
        conf.COMPILE_CACHE["dir"] = had
    specs = layer_specs()
    # the five of the replication link, the four of the gather, the five
    # of the list index and the engine (redis-benchmark's cell), the two
    # of keys created under load (ycsb-d); of docs/stage_layers/ fifteen,
    # the event loop's four, the reply sender's two and the reader's three
    assert len(specs) == 10 + 5 + 4 + 5 + 2 + 15 + 4 + 2 + 3
    mine = [s for s in specs if s["workloads"] == ["memtier-default"]]
    assert sorted(s["name"] for s in mine) == [
        "gather_us_per_op.serve", "gathered_ops_per_pass.serve",
        "lone_cmd_share.serve", "reg_rows_dev_share.serve"]
    lists = [s for s in specs
             if s["workloads"] == ["redis-benchmark-default"]]
    assert sorted(s["name"] for s in lists) == [
        "cnt_rows_dev_share.serve", "el_rows_dev_share.serve",
        "list_index_us_per_op.serve", "list_pos_bytes_per_insert.serve",
        "mirror_grows_per_kop.serve"]
    assert sorted(s["name"] for s in specs if s["workloads"] == ["ycsb-d"]
                  ) == ["created_read_flushes_per_kop.serve",
                        "key_create_us_per_op.serve"]
    link = [s for s in specs if s["layer"] == "replication link"]
    assert len(link) == 5 and all(
        s["workloads"] == ["aa-3node-ycsb-a"] for s in link)
    missing = {s["name"]: [c for c in counters_of(s) if c not in info]
               for s in specs}
    assert not {k: v for k, v in missing.items() if v}
    spans = {c for s in specs for c in counters_of(s)
             if c.startswith("span_")}
    assert spans == {f"span_{s}_us" for s in STAGES}   # none left unread


def benchmark_module(name: str):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_overlay_makes_a_manifest_the_contract_accepts(tmp_path):
    """docs/stage_layers/overlay.py on a scratch copy: 24 files beside the
    24, 24 entries at the END of per_layer, nothing else changed — and
    the reason they are not in the checkout's own manifest: a traced
    line without them (the parent commit's) is refused."""
    import importlib.util
    import shutil
    validate = benchmark_module("validate")
    shutil.copytree(os.path.join(ROOT, "benchmark", "layers"),
                    tmp_path / "benchmark" / "layers")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    spec = importlib.util.spec_from_file_location(
        "overlay", os.path.join(ROOT, "docs", "stage_layers", "overlay.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    added = mod.overlay(str(tmp_path))
    assert len(added) == 24 and mod.overlay(str(tmp_path)) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(tmp_path / "BENCHMARK.json") as f:
        after = json.load(f)
    assert validate.check_manifest(after) == []
    n = len(before["per_layer"])
    assert after["per_layer"][:n] == before["per_layer"]
    assert [m["name"] for m in after["per_layer"][n:]] == added
    assert {k: v for k, v in after.items() if k != "per_layer"} == \
        {k: v for k, v in before.items() if k != "per_layer"}
    assert len(os.listdir(tmp_path / "benchmark" / "layers")) == n + 24
    # the parent's traced line: the ten old metrics, none of the new
    line = {"correct": True, "attempted": 10, "failed": 0,
            "metrics": {m["name"]: {"value": 1.0, "unit": m["unit"]}
                        for m in before["per_layer"]
                        if "ycsb-b" in m["workloads"]},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "window_s": 2.0,
                       "busy_s": 1.0},
            "compared": {"reads_wrong": {"value": 0, "limit": 0}}}
    assert validate.check_line(line, before, "ycsb-b", True) == []
    refused = validate.check_line(line, after, "ycsb-b", True)
    assert len(refused) == 24 and all("is missing" in e for e in refused)


def test_stage_layer_specs_read_through_the_benchmarks_readers():
    readers = benchmark_module("readers")
    node, _eng = device_node(warmup=0)
    before = info_of(node)
    for i in range(3):
        sadd_round(node, 6 * i)
    # a lone command makes the mirror stale; the next round patches it
    ServeCoalescer(node).run_chunk([cmd(b"sadd", b"s", b"lone")],
                                   bytearray())
    sadd_round(node, 18, members=5)
    # two planned SMEMBERS misses: the fused scan pass answers both
    ServeCoalescer(node).run_chunk([cmd(b"smembers", b"s")] * 2,
                                   bytearray())
    node.ensure_flushed()
    window = {"info_before": before, "info_after": info_of(node),
              "ops": 24, "kops": 0.024, "seconds": 2.0}
    got = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "docs", "stage_layers",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        assert os.path.basename(path) == spec["name"] + ".json"
        assert spec["workloads"] == (
            CELLS if spec["name"] in LOOP_SPECS | REPLY_SPECS
            else CELLS + ["redis-benchmark-default"]
            if spec["name"] in READ_SPECS else ["ycsb-b"])
        assert spec["moves"] == "served_ops"
        got[spec["name"]] = readers.read(spec, window, None, {})
    # no reply left this node by a socket: the sender's share has nothing
    # to read (test_loop_specs_read_their_counters reads it)
    assert got.pop("reply_pump_share.serve") is None
    # nor did a byte reach it by a socket: the reader's share neither
    assert got.pop("read_pump_share.serve") is None
    assert all(isinstance(v, float) for v in got.values()), got
    per_op = [v for k, v in got.items() if k.endswith("_us_per_op.serve")]
    assert len(per_op) == 11
    assert got["device_merged_row_share.serve"] == 100.0
    assert got["mirror_patch_share.serve"] == 100.0     # 1 patch, 0 rebuilds
    assert got["read_scan_native_share.serve"] == 100.0  # 2 of 2 misses
    # every stage is in exactly one of the eleven per-op metrics (a patch
    # in the engine's) or in the rebuild share, so the eleven add up to
    # the traced share less rebuilds
    traced_us = got["loop_traced_share.serve"] * 2.0 * 1e4
    rebuild_us = got["mirror_rebuild_stall_share.serve"] * 2.0 * 1e4
    gc_us = got["gc_pause_share.serve"] * 2.0 * 1e4
    assert sum(per_op) * 24 == pytest.approx(traced_us - rebuild_us - gc_us)
    # a node without the counters (the parent commit) reads nothing
    bare = dict(window, info_after={}, info_before={})
    with open(os.path.join(ROOT, "docs", "stage_layers",
                           "loop_traced_share.serve.json")) as f:
        assert readers.read(json.load(f), bare, None, {}) is None


@pytest.mark.parametrize("name,expected", [
    ("reply_pump_share.serve", 100 * 2_970 / 3_000),  # 30 transport writes
    ("reply_sender_busy_share.serve", 100 * 0.8 / 2.0),
    ("read_pump_share.serve", 100 * 95_000 / 100_000),  # 5 kB by a link
    ("reader_busy_share.serve", 100 * 0.6 / 2.0),
    ("read_take_us_per_op.serve", 48_000 / 24_000),
    ("loop_poll_share.serve", 100 * 0.5 / 2.0),       # 0.5 s of a 2 s window
    ("loop_cpu_share.serve", 100 * 1.2 / 2.0),
    ("loop_preempt_per_kop.serve", 1000 * 30 / 24_000),
    ("gc_pause_share.serve", 100 * 0.01 / 2.0),
    ("loop_traced_share.serve", 100 * (0.3 + 0.01 + 0.048) / 2.0)])
def test_loop_specs_read_their_counters(name, expected):
    """Each event-loop, reply-sender and reader spec through
    `readers.read` over a window whose deltas are known: 2 s, 24,000
    operations."""
    readers = benchmark_module("readers")
    with open(os.path.join(ROOT, "docs", "stage_layers",
                           f"{name}.json")) as f:
        spec = json.load(f)
    before = {f"span_{s}_us": 1_000 for s in STAGES}
    before.update(loop_cpu_us=5, loop_nivcsw=7, reply_pump_posts=30,
                  reply_transport_writes=0, reply_pump_send_us=100,
                  read_pump_bytes=500, total_net_input_bytes=1_000,
                  read_pump_recv_us=40)
    after = dict(before, span_loop_poll_us=501_000, span_gc_us=11_000,
                 span_plan_us=301_000, loop_cpu_us=1_200_005,
                 loop_nivcsw=37, reply_pump_posts=3_000,
                 reply_transport_writes=30, reply_pump_send_us=800_100,
                 read_pump_bytes=95_500, total_net_input_bytes=101_000,
                 read_pump_recv_us=600_040, span_read_take_us=49_000)
    window = {"info_before": before, "info_after": after, "ops": 24_000,
              "kops": 24.0, "seconds": 2.0}
    assert readers.read(spec, window, None, {}) == pytest.approx(expected)
    # the parent commit's INFO has none of them: nothing is read
    bare = {"info_before": {}, "info_after": {}, "ops": 24_000,
            "kops": 24.0, "seconds": 2.0}
    assert readers.read(spec, bare, None, {}) is None
