"""Stage clocks on the served path (utils/stagetime.py) and the counters
that share their boundaries.

Pinned here:
  * self time: nested stages on one thread add up to the outer
    duration, a child's time is never counted twice, an exception still
    closes and counts the stage, threads never nest into each other;
  * the vocabulary is closed (an undeclared name raises) and only the
    per-flush stages open trace spans, named `cst.<name>[.<tag>]`;
  * INFO lists every `span_*`, `merge_rows_*`, `mirror_rebuilds_cause_*`
    and `mirror_patch*` field from boot, at 0 — `readers._delta`
    (benchmark/readers.py) reads a missing counter as "no metric", and a
    traced line on the chip is refused for a missing metric;
  * a pipelined chunk through a real ServerApp socket moves the loop's
    stages, and their sum stays under the wall time of the exchange;
  * a device engine on JAX-CPU counts merged rows by path, mirror
    rebuilds by cause and mirror patches with their rows (a row-scoped
    write is patched, a GC rebuilds), and the documented inclusive totals
    (`merge_<fam>_seconds`, `merge_seconds_total`,
    `flush_seconds_total`) still read above 0;
  * every counter a per-layer metric names — the nineteen in
    BENCHMARK.json (PR 37's four of the gather and the `reg` rows among
    them) and the fifteen specs of docs/stage_layers/ — is an INFO key of a
    device-engine node, and the existing readers turn each spec into a
    number.
"""

import asyncio
import glob
import json
import os
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
from constdb_tpu.utils.stagetime import ANNOTATED, STAGES, StageClock

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
    spans = StageClock(annotation=lambda name: None)
    with pytest.raises(ValueError, match="over 40"):
        spans.stage("mirror_rebuild", "x" * 30)
    assert all(len(f"cst.{name}.tns_read") <= stagetime.MAX_ANNOTATION
               for name in ANNOTATED)


def test_only_per_flush_stages_open_trace_spans():
    seen = []

    class Span:
        def __init__(self, name: str) -> None:
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc) -> None:
            seen.append(("exit", self.name))

    clock = StageClock(annotation=Span)
    for name in STAGES:
        with clock.stage(name):
            pass
    with pytest.raises(RuntimeError):
        with clock.stage("mirror_rebuild", "el"):
            raise RuntimeError
    names = [n for what, n in seen if what == "enter"]
    assert names == [f"cst.{n}" for n in STAGES if n in ANNOTATED] + \
        ["cst.mirror_rebuild.el"]
    assert seen.count(("exit", "cst.mirror_rebuild.el")) == 1
    assert ANNOTATED == {"serve_flush", "stage_rows", "h2d", "dispatch",
                         "host_twin", "mirror_rebuild", "mirror_patch",
                         "state_alloc", "d2h_flush", "repl_flush"}
    # without an annotation the same stages are counters like the others
    plain = StageClock()
    with plain.stage("d2h_flush", "el"):
        pass
    assert plain.snapshot()["d2h_flush"][1] == 1


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
    assert len(want) == 2 * 19 + 8 + 6 + 7 + 5 + 4 + 3
    assert STAGES.index("gather") == 1 and "gather" not in ANNOTATED
    assert {k: info.get(k) for k in want} == dict.fromkeys(want, 0)
    # a CPU-engine node has the clock, not the device engine's counters
    cpu = info_of(Node(node_id=2))
    assert all(cpu[f"span_{s}_us"] == 0 for s in STAGES)
    assert all(cpu[k] == 0 for k in LINK_COUNTERS + GATHER_COUNTERS)
    assert "merge_rows_dev_el" not in cpu
    assert not any(k in cpu for k in MICRO_COUNTERS)


def test_node_adopts_the_engines_clock():
    node, eng = device_node()
    assert node.stages is eng.stages
    assert eng.stages.annotation is not None     # jax's TraceAnnotation
    assert Node(node_id=2).stages.annotation is None


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
    for s in ("intake", "gather", "plan", "read_batch", "serve_flush",
              "reply_write"):
        assert info[f"span_{s}_us"] > 0 and info[f"span_{s}_n"] > 0, s
    # one connection, three reads: three passes of 28 messages, each a
    # hand-over and a pass of the gather
    assert info["serve_gather_passes"] == info["serve_gather_conns"] == 3
    assert info["serve_gather_msgs"] == 3 * 28
    assert info["span_gather_n"] == 6 and info["serve_lone_cmds"] == 0
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


def test_documented_totals_still_read_above_zero():
    node, eng = device_node(warmup=0)
    sadd_round(node, 0)
    sadd_round(node, 6)
    node.ensure_flushed()
    info = info_of(node)
    assert eng.family_secs["micro"] > 0 and eng.family_secs["flush"] > 0
    for field in ("merge_seconds_total", "flush_seconds_total",
                  "merge_micro_seconds", "merge_flush_seconds"):
        assert info[field] > 0, field
    # inclusive totals contain the stages under them
    assert info["merge_flush_seconds"] * 1e6 >= info["span_d2h_flush_us"]
    assert info["merge_seconds_total"] >= info["merge_micro_seconds"]
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
    """Every per-layer metric BENCHMARK.json names, and the fifteen the
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
    # the five of the replication link, the four of the gather (PR 37)
    assert len(specs) == 10 + 5 + 4 + 15
    mine = [s for s in specs if s["workloads"] == ["memtier-default"]]
    assert sorted(s["name"] for s in mine) == [
        "gather_us_per_op.serve", "gathered_ops_per_pass.serve",
        "lone_cmd_share.serve", "reg_rows_dev_share.serve"]
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
    """docs/stage_layers/overlay.py on a scratch copy: 15 files beside the
    19, 15 entries at the END of per_layer, nothing else changed — and
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
    assert len(added) == 15 and mod.overlay(str(tmp_path)) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(tmp_path / "BENCHMARK.json") as f:
        after = json.load(f)
    assert validate.check_manifest(after) == []
    assert after["per_layer"][:19] == before["per_layer"]
    assert [m["name"] for m in after["per_layer"][19:]] == added
    assert {k: v for k, v in after.items() if k != "per_layer"} == \
        {k: v for k, v in before.items() if k != "per_layer"}
    assert len(os.listdir(tmp_path / "benchmark" / "layers")) == 34
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
    assert len(refused) == 15 and all("is missing" in e for e in refused)


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
        assert spec["workloads"] == ["ycsb-b"]
        assert spec["moves"] == "served_ops"
        got[spec["name"]] = readers.read(spec, window, None, {})
    assert all(isinstance(v, float) for v in got.values()), got
    per_op = [v for k, v in got.items() if k.endswith("_us_per_op.serve")]
    assert len(per_op) == 10
    assert got["device_merged_row_share.serve"] == 100.0
    assert got["mirror_patch_share.serve"] == 100.0     # 1 patch, 0 rebuilds
    assert got["read_scan_native_share.serve"] == 100.0  # 2 of 2 misses
    # every stage is in exactly one of the ten per-op metrics (a patch in
    # the engine's) or in the rebuild share, so the ten add up to the
    # traced share less rebuilds
    traced_us = got["loop_traced_share.serve"] * 2.0 * 1e4
    rebuild_us = got["mirror_rebuild_stall_share.serve"] * 2.0 * 1e4
    assert sum(per_op) * 24 == pytest.approx(traced_us - rebuild_us)
    # a node without the counters (the parent commit) reads nothing
    bare = dict(window, info_after={}, info_before={})
    with open(os.path.join(ROOT, "docs", "stage_layers",
                           "loop_traced_share.serve.json")) as f:
        assert readers.read(json.load(f), bare, None, {}) is None
