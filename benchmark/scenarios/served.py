"""Scenario `served`: one node serves a closed-loop mix over client
sockets.  Set-up: snapshot from the seed, boot, warm-up with the mix's own
traffic; the window is `--seconds` of it; then the comparison with the
plain reference (reference.check_served) on what the window answered.

With `--trace 1` the node traces `trace_seconds` in the middle of the
window — or, where the mix says `"trace_span": "traffic"`, from the first
operation of the warm-up to the end of the window (a mix that all but
bypasses the device must still catch the few operations that reach it);
the rows the device merged there (bytes.py) are the writes the workers saw
acknowledged inside that slice, times the share of the slice's micro
rounds that the node's INFO says merged on the device
(`dev_rounds_resident` over resident + `host_micro_rounds`): the others
merged on the host twin and gave the kernels nothing to do.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np

import datagen
import nodes
import reference
import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODE = "C"


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
                              os.path.join(HERE, "loadgen.py")],
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
                              os.path.join(HERE, "fake_node.py"), str(port),
                              cfg_path, str(run.seed), run.stand_in])
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
    if not (run.rehearse or run.stand_in):
        nodes.check(info.get("engine") == "tpu"
                    and info.get("jax_backend") not in (None, "cpu", "none"),
                    f"the node does not run on an accelerator: engine="
                    f"{info.get('engine')} backend={info.get('jax_backend')}")
    return port, conn


def _warm_up(run, conn, t_warm: float) -> None:
    """The mix's own traffic until every program it uses is compiled: at
    least `warmup_seconds`, and on until the node's count of compiles has
    stood still for `warmup_quiet_seconds` (a fresh checkout compiles
    here, and that is set-up), `warmup_max_seconds` at the most."""
    mix = run.mix
    least = t_warm + float(mix["warmup_seconds"])
    quiet = float(mix["warmup_quiet_seconds"])
    most = t_warm + float(mix["warmup_max_seconds"])
    misses, since = None, time.monotonic()
    while True:
        time.sleep(0.5)
        now = time.monotonic()
        seen = conn.info().get("compile_cache_misses")
        if seen != misses:
            misses, since = seen, now
        if now >= most or (now >= least and now - since >= quiet):
            run.log(f"warm-up {now - t_warm:.1f}s, compiles so far: "
                    f"{misses}")
            return


def _device(run) -> dict:
    if run.stand_in:
        return {"platform": "none", "kind": "reference stand-in",
                "count": 1, "memory_peak_bytes": 0}
    return run.servers.control(NODE, "device")


def run(run) -> dict:
    mix = run.mix
    world = datagen.build_world(run.config, run.seed)
    port, conn = _boot(run, world)
    run.log(f"node up: {world.n:,} records")
    workers = _start_workers(run, port)
    whole = run.trace and mix.get("trace_span") == "traffic" \
        and not run.stand_in
    slice_info = None
    if whole:
        slice_info = [conn.info()]
        run.servers.control(NODE, f"trace-start {run.trace_dir}")
    t_warm = time.monotonic() + 0.2
    for p in workers:
        p.stdin.write(b"go %.6f\n" % t_warm)
        p.stdin.flush()
    _warm_up(run, conn, t_warm)
    t0 = time.monotonic() + 0.25
    t1 = t0 + run.seconds
    for p in workers:
        p.stdin.write(b"end %.6f\n" % t1)
        p.stdin.flush()
    time.sleep(max(0.0, t0 - time.monotonic()))
    info_before = conn.info()
    setup_s = t0 - run.t_process_start
    run.log(f"window opens: setup_s={setup_s:.3f}")
    slice_t = None
    if whole:
        time.sleep(max(0.0, t1 - time.monotonic()))
        slice_info.append(conn.info())
        slice_t = (t_warm, time.monotonic())
        run.servers.control(NODE, "trace-stop")
    elif run.trace:
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
    device = _device(run)
    if run.trace and run.stand_in:
        nodes.stand_in_trace(run.trace_dir)

    # ---- metrics at the clients
    done_in, lat = 0, []
    attempted = failed = 0
    trace_updates = 0
    # each connection's operations, generated once for the slice count
    # here and the reference's replay below
    ops_of = {res["conn"]: traffic.conn_ops(mix, world.n, world.fieldcount,
                                            run.seed, res["conn"])
              for res in results}
    for res in results:
        depth = res["depth"]
        t_sent = np.repeat(res["t_sent"], depth)[:res["sent"]]
        t_done = res["t_done"]
        answered = np.arange(res["sent"]) < res["done"]
        in_window = (t_sent >= t0) & (t_sent <= t1)
        attempted += int(in_window.sum())
        failed += int((in_window & ~answered).sum())
        done_in += int((answered & (t_done >= t0) & (t_done <= t1)).sum())
        ms = np.where(answered, (t_done - t_sent) * 1e3, np.inf)
        lat.append(ms[in_window])
        if slice_t:
            kinds = ops_of[res["conn"]].kinds[:res["sent"]]
            trace_updates += int((answered & (kinds == traffic.UPDATE)
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
                       "dev_rounds_resident", "host_micro_rounds",
                       "dev_upload_bytes") if k in info_after}
    run.log(f"{done_in:,} ops acknowledged in {run.seconds:.0f}s; p50 "
            f"{values['reply_p50_ms']:.2f} ms, p99 "
            f"{values['reply_p99_ms']:.2f} ms; {failed} failed; INFO "
            f"deltas {json.dumps(moved)}")

    # ---- the comparison that decides `correct`
    def readback(records: list) -> list:
        c = nodes.Conn(port)
        try:
            return c.raw_replies([("HGETALL", world.key(r))
                                  for r in records])
        finally:
            c.close()

    t = time.monotonic()
    check = reference.check_served(world, mix, run.seed, results, ops_of,
                                   readback)
    check["limits"] = dict(reference.LIMITS)
    run.log(f"comparison with the reference: {time.monotonic() - t:.1f}s")
    conn.close()
    # one HSET of one field merges one element row and its key's envelope
    # row — on the device only in a resident round
    device_rows = 0.0
    if slice_info:
        rounds = {k: float(slice_info[1].get(k, 0))
                  - float(slice_info[0].get(k, 0))
                  for k in ("dev_rounds_resident", "host_micro_rounds")}
        if rounds["dev_rounds_resident"] > 0:
            device_rows = trace_updates * rounds["dev_rounds_resident"] \
                / sum(rounds.values())
        run.log(f"traced slice: {trace_updates} writes acknowledged, micro "
                f"rounds {json.dumps(rounds)}, rows merged on the device "
                f"{device_rows:.1f}")
    window = {"ops": done_in, "kops": done_in / 1e3, "seconds": run.seconds,
              "keys": world.n, "info_before": info_before,
              "info_after": info_after, "client": values,
              "trace_rows": {"el": device_rows, "env": device_rows},
              "trace_seconds": slice_t[1] - slice_t[0] if slice_t else 0.0}
    return {"values": values, "attempted": attempted, "failed": failed,
            "check": check, "window": window, "device": device}
