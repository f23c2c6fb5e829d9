"""Scenario `served_kv`: one node serves memtier_benchmark's shape — many
closed-loop connections, ONE command in flight each, `SET`s and `GET`s
over a table of registers (datagen_kv.RegisterWorld) — over client
sockets.  Set-up: snapshot from the seed, boot, warm-up with the mix's own
traffic; the window is `--seconds` of it; then the comparison with the
plain key-value reference (reference_kv.check_served_kv) on what the window
answered.  Load workers are loadgen_kv.py; the stand-in is
fake_kv_node.py.

A client that keeps one command in flight forms no run of its own: its
writes reach the merge engine only through the program's loop-pass gather
(server/io.py), whose stage and counters this cell's metrics read.  A
program without it (`gather` in utils/stagetime.STAGES,
`serve_gather_passes` in server/info.py) cannot report them: the scenario
looks BEFORE it builds a snapshot or boots, and fails at once.

With `--trace 1` the node traces `trace_seconds` in the middle of the
window; the rows its device merged there (bytes.py, family `reg`: `env` is
host-authoritative on the micro path and is not counted) are the `SET`s
the workers saw acknowledged inside that slice, times the device's share
of the `reg` rows the node's INFO says it merged there
(`merge_rows_dev_reg` over dev + `merge_rows_host_reg`).
"""

from __future__ import annotations

import importlib.util
import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import datagen              # noqa: E402
import datagen_kv           # noqa: E402
import nodes                # noqa: E402
import reference_kv         # noqa: E402
import traffic              # noqa: E402

NODE = "C"


def _served():
    """Scenario `served`'s warm-up and device read, shared and not copied."""
    spec = importlib.util.spec_from_file_location(
        "scenario_served", os.path.join(HERE, "scenarios", "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def has_gather() -> bool:
    """Does this checkout's program declare the loop-pass gather?  Read
    from its sources' own tables, with no node booted: the stage in
    utils/stagetime.STAGES (a module that imports nothing heavy) and the
    counter in server/info.py's text."""
    from constdb_tpu.utils import stagetime
    if "gather" not in stagetime.STAGES:
        return False
    with open(os.path.join(ROOT, "constdb_tpu", "server", "info.py")) as f:
        return "serve_gather_passes" in f.read()


def _start_workers(run, port: int) -> list:
    mix = run.mix
    n_workers = int(mix["workers"])
    conns = list(range(int(mix["connections"])))
    workers = []
    for w in range(n_workers):
        job = {"port": port, "seed": run.seed, "conns": conns[w::n_workers],
               "config": run.config, "mix": mix,
               "grace_seconds": mix["grace_seconds"]}
        p = subprocess.Popen([sys.executable,
                              os.path.join(HERE, "loadgen_kv.py")],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        p.stdin.write(json.dumps(job).encode() + b"\n")
        p.stdin.flush()
        workers.append(p)
    for p in workers:
        line = p.stdout.readline()
        nodes.check(line == b"ready\n", f"a load worker said {line!r}")
    return workers


def _boot(run, world):
    port = nodes.free_port()
    node = run.config["nodes"][NODE]
    if run.stand_in:
        cfg_path = os.path.join(run.work, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(run.config, f)
        p = subprocess.Popen([sys.executable,
                              os.path.join(HERE, "fake_kv_node.py"),
                              str(port), cfg_path, str(run.seed),
                              run.stand_in])
        run.servers.procs[NODE] = p
    else:
        t = time.monotonic()
        snap = os.path.join(run.work, "table.snapshot")
        size = datagen.write_snapshot(
            world, snap, node["node_id"], NODE, f"127.0.0.1:{port}",
            int(run.config["snapshot_compress_level"]))
        run.log(f"snapshot: {size:,} bytes in {time.monotonic() - t:.1f}s")
        run.servers.boot(NODE, node, port, snap)
    conn = run.servers.wait_listening(NODE, port,
                                      float(run.config["boot_timeout_s"]))
    info = conn.info()
    nodes.check(int(info["keys"]) == world.n,
                f"the node holds {info['keys']} keys of {world.n}")
    nodes.check("boot_snapshot_quarantined" not in info,
                "the node quarantined its boot snapshot")
    if not run.stand_in:
        nodes.check("serve_gather_passes" in info,
                    "the node's INFO has no `serve_gather_passes`")
    if not (run.rehearse or run.stand_in):
        nodes.check(info.get("engine") == "tpu"
                    and info.get("jax_backend") not in (None, "cpu", "none"),
                    f"the node does not run on an accelerator: engine="
                    f"{info.get('engine')} backend={info.get('jax_backend')}")
    return port, conn


def run(run) -> dict:
    mix = run.mix
    nodes.check(run.stand_in or has_gather(),
                "this program has no `gather` stage (utils/stagetime.STAGES) "
                "or no `serve_gather_passes` counter (server/info.py): a "
                "client with one command in flight never reaches its merge "
                "engine, and the cell's metrics have nothing to read")
    served = _served()
    world = datagen_kv.build_world(run.config, run.seed)
    port, conn = _boot(run, world)
    run.log(f"node up: {world.n:,} keys")
    workers = _start_workers(run, port)
    t_warm = time.monotonic() + 0.2
    for p in workers:
        p.stdin.write(b"go %.6f\n" % t_warm)
        p.stdin.flush()
    served._warm_up(run, conn, t_warm)
    t0 = time.monotonic() + 0.25
    t1 = t0 + run.seconds
    for p in workers:
        p.stdin.write(b"end %.6f\n" % t1)
        p.stdin.flush()
    time.sleep(max(0.0, t0 - time.monotonic()))
    info_before = conn.info()
    setup_s = t0 - run.t_process_start
    run.log(f"window opens: setup_s={setup_s:.3f}")
    slice_t, slice_info = None, None
    if run.trace:
        span = min(float(mix["trace_seconds"]), run.seconds / 2)
        time.sleep(max(0.0, t0 + (run.seconds - span) / 2 - time.monotonic()))
        if not run.stand_in:
            run.servers.control(NODE, f"trace-start {run.trace_dir}")
        a = time.monotonic()
        slice_info = [conn.info()]
        time.sleep(max(0.0, a + span - time.monotonic()))
        slice_info.append(conn.info())
        b = time.monotonic()
        if not run.stand_in:
            run.servers.control(NODE, "trace-stop")
        slice_t = (a, b)
    time.sleep(max(0.0, t1 - time.monotonic()))
    info_after = conn.info()
    results = []
    for p in workers:
        results.extend(pickle.load(p.stdout))
        p.wait()
    run.log("window closed, workers in")
    device = served._device(run)
    if run.trace and run.stand_in:
        nodes.stand_in_trace(run.trace_dir)

    # ---- metrics at the clients
    done_in, lat = 0, []
    attempted = failed = 0
    trace_sets = 0
    ops_of = {res["conn"]: traffic.conn_ops(mix, world.n, 1, run.seed,
                                            res["conn"])
              for res in results}
    for res in results:
        t_sent, t_done = res["t_sent"], res["t_done"]
        answered = np.arange(res["sent"]) < res["done"]
        in_window = (t_sent >= t0) & (t_sent <= t1)
        attempted += int(in_window.sum())
        failed += int((in_window & ~answered).sum())
        done_in += int((answered & (t_done >= t0) & (t_done <= t1)).sum())
        ms = np.where(answered, (t_done - t_sent) * 1e3, np.inf)
        lat.append(ms[in_window])
        if slice_t:
            kinds = ops_of[res["conn"]].kinds[:res["sent"]]
            trace_sets += int((answered & (kinds == traffic.UPDATE)
                               & (t_done >= slice_t[0])
                               & (t_done <= slice_t[1])).sum())
    lat = np.concatenate(lat) if lat else np.zeros(0)
    nodes.check(len(lat) > 0, "no operation was sent inside the window")
    values = {"served_ops": done_in / run.seconds,
              "reply_p50_ms": float(np.percentile(lat, 50)),
              "reply_p99_ms": float(np.percentile(lat, 99)),
              "setup_s": setup_s}
    moved = {k: float(info_after[k]) - float(info_before.get(k, 0))
             for k in ("compile_cache_misses", "serve_flushes",
                       "serve_gather_passes", "serve_gather_msgs",
                       "serve_lone_cmds", "dev_rounds_resident",
                       "host_micro_rounds", "merge_rows_dev_reg",
                       "merge_rows_host_reg", "dev_upload_bytes")
             if k in info_after}
    run.log(f"{done_in:,} ops acknowledged in {run.seconds:.0f}s; p50 "
            f"{values['reply_p50_ms']:.2f} ms, p99 "
            f"{values['reply_p99_ms']:.2f} ms; {failed} failed; INFO "
            f"deltas {json.dumps(moved)}")
    def moved_by(key: str) -> float:
        return float(info_after[key]) - float(info_before.get(key, 0))

    stages = [(k[5:-3], moved_by(k) / max(done_in, 1), moved_by(k[:-2] + "n"))
              for k in info_after
              if k.startswith("span_") and k.endswith("_us")]
    run.log("stages, self us per operation acknowledged (and entries): "
            + ", ".join(f"{name} {us:.2f} ({int(n)})"
                        for name, us, n in stages if n))

    # ---- the comparison that decides `correct`
    def readback(keys: list) -> list:
        c = nodes.Conn(port)
        try:
            return c.raw_replies([("GET", world.key(k)) for k in keys])
        finally:
            c.close()

    t = time.monotonic()
    check = reference_kv.check_served_kv(world, mix, run.seed, results,
                                         ops_of, readback)
    check["limits"] = dict(reference_kv.LIMITS)
    run.log(f"comparison with the reference: {time.monotonic() - t:.1f}s")
    conn.close()
    # one SET merges one register row — on the device only where the
    # `reg` family rode a resident round
    device_rows = 0.0
    if slice_info:
        rows = {k: float(slice_info[1].get(k, 0))
                - float(slice_info[0].get(k, 0))
                for k in ("merge_rows_dev_reg", "merge_rows_host_reg")}
        if rows["merge_rows_dev_reg"] > 0:
            device_rows = trace_sets * rows["merge_rows_dev_reg"] \
                / sum(rows.values())
        run.log(f"traced slice: {trace_sets} SETs acknowledged, reg rows "
                f"{json.dumps(rows)}, rows merged on the device "
                f"{device_rows:.1f}")
    window = {"ops": done_in, "kops": done_in / 1e3, "seconds": run.seconds,
              "keys": world.n, "info_before": info_before,
              "info_after": info_after, "client": values,
              "trace_rows": {"reg": device_rows},
              "trace_seconds": slice_t[1] - slice_t[0] if slice_t else 0.0}
    return {"values": values, "attempted": attempted, "failed": failed,
            "check": check, "window": window, "device": device}
