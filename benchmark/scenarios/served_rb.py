"""Scenario `served_rb`: one node, booted empty, serves redis-benchmark's
default run — 50 closed-loop connections, ONE command in flight each, the
default tests in equal shares on one key per test (traffic_rb.py) — over
client sockets.  Set-up: boot, warm-up with the mix's own traffic; the
window is `--seconds` of it; then the comparison with the plain reference
(reference_rb.check_served_rb) on what the window answered.  Load workers
are loadgen_rb.py; the stand-in is fake_rb_node.py.

The list grows all window, so the cell's metrics read the program's list
index (`list_index` in utils/stagetime.STAGES, `list_inserts` in
server/info.py) and its plane grows.  A program without the index cannot
report them: the scenario looks BEFORE it boots, and fails at once.

With `--trace 1` the node traces `trace_seconds` in the middle of the
window; the rows its device merged there (bytes.py) are, by family, what
the node's INFO says it merged on the device in that slice
(`merge_rows_dev_<fam>`): on one hot key a pass folds its SETs, INCRs or
HSETs into one row, so the writes acknowledged there (logged beside them)
overstate the rows.  `env` is host-authoritative on the micro path and is
not counted.
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

import nodes                # noqa: E402
import reference_rb         # noqa: E402
import traffic_rb as T      # noqa: E402

NODE = "C"
# family -> the tests whose writes merge into it (the traced slice's
# acknowledged ones are logged beside the rows the device merged)
FAMILY_TESTS = {"reg": (T.SET,), "cnt": (T.INCR,),
                "el": (T.LPUSH, T.RPUSH, T.SADD, T.HSET, T.SPOP)}


def _served():
    """Scenario `served`'s warm-up and device read, shared and not copied."""
    spec = importlib.util.spec_from_file_location(
        "scenario_served", os.path.join(HERE, "scenarios", "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def has_list_index() -> bool:
    """Does this checkout's program keep a list index?  Read from its
    sources' own tables, with no node booted: the stage in
    utils/stagetime.STAGES and the counter in server/info.py's text."""
    from constdb_tpu.utils import stagetime
    if "list_index" not in stagetime.STAGES:
        return False
    with open(os.path.join(ROOT, "constdb_tpu", "server", "info.py")) as f:
        return "list_inserts" in f.read()


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
                              os.path.join(HERE, "loadgen_rb.py")],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        p.stdin.write(json.dumps(job).encode() + b"\n")
        p.stdin.flush()
        workers.append(p)
    for p in workers:
        line = p.stdout.readline()
        nodes.check(line == b"ready\n", f"a load worker said {line!r}")
    return workers


def _boot(run):
    port = nodes.free_port()
    node = run.config["nodes"][NODE]
    if run.stand_in:
        cfg_path = os.path.join(run.work, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(run.config, f)
        p = subprocess.Popen([sys.executable,
                              os.path.join(HERE, "fake_rb_node.py"),
                              str(port), cfg_path, str(run.seed),
                              run.stand_in])
        run.servers.procs[NODE] = p
    else:
        # booted empty, as redis-benchmark finds a fresh server: the
        # snapshot path holds no file (the node dumps there on its cron)
        run.servers.boot(NODE, node, port,
                         os.path.join(run.work, "empty.snapshot"))
    conn = run.servers.wait_listening(NODE, port,
                                      float(run.config["boot_timeout_s"]))
    info = conn.info()
    nodes.check(int(info["keys"]) == 0,
                f"the node booted with {info['keys']} keys, not empty")
    if not (run.rehearse or run.stand_in):
        nodes.check(info.get("engine") == "tpu"
                    and info.get("jax_backend") not in (None, "cpu", "none"),
                    f"the node does not run on an accelerator: engine="
                    f"{info.get('engine')} backend={info.get('jax_backend')}")
    return port, conn


def run(run) -> dict:
    mix = run.mix
    nodes.check(run.stand_in or has_list_index(),
                "this program has no `list_index` stage "
                "(utils/stagetime.STAGES) or no `list_inserts` counter "
                "(server/info.py): the cell's list metrics have nothing to "
                "read")
    served = _served()
    port, conn = _boot(run)
    run.log("node up, empty")
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
    edge = min(10.0, run.seconds / 4)
    first_s = last_s = 0
    per_5s = np.zeros(int(np.ceil(run.seconds / 5)), dtype=np.int64)
    slice_writes = dict.fromkeys(FAMILY_TESTS, 0)
    for res in results:
        t_sent, t_done = res["t_sent"], res["t_done"]
        answered = np.arange(res["sent"]) < res["done"]
        in_window = (t_sent >= t0) & (t_sent <= t1)
        attempted += int(in_window.sum())
        failed += int((in_window & ~answered).sum())
        done_in += int((answered & (t_done >= t0) & (t_done <= t1)).sum())
        first_s += int((answered & (t_done >= t0)
                        & (t_done < t0 + edge)).sum())
        last_s += int((answered & (t_done > t1 - edge)
                       & (t_done <= t1)).sum())
        at = ((t_done[answered & (t_done >= t0) & (t_done < t1)] - t0)
              // 5).astype(np.int64)
        per_5s += np.bincount(at, minlength=len(per_5s))[:len(per_5s)]
        ms = np.where(answered, (t_done - t_sent) * 1e3, np.inf)
        lat.append(ms[in_window])
        if slice_t:
            kinds = T.conn_ops(mix, run.seed, res["conn"]).kinds[:res["sent"]]
            in_slice = answered & (t_done >= slice_t[0]) \
                & (t_done <= slice_t[1])
            for fam, tests in FAMILY_TESTS.items():
                slice_writes[fam] += int((in_slice
                                          & np.isin(kinds, tests)).sum())
    lat = np.concatenate(lat) if lat else np.zeros(0)
    nodes.check(len(lat) > 0, "no operation was sent inside the window")
    values = {"served_ops": done_in / run.seconds,
              "reply_p50_ms": float(np.percentile(lat, 50)),
              "reply_p99_ms": float(np.percentile(lat, 99)),
              "setup_s": setup_s}

    def moved_by(key: str) -> float:
        return float(info_after.get(key, 0)) - float(info_before.get(key, 0))

    moved = {k: moved_by(k) for k in (
        "compile_cache_misses", "serve_flushes", "serve_barriers",
        "serve_gather_passes", "serve_gather_msgs", "serve_lone_cmds",
        "dev_rounds_resident", "host_micro_rounds", "merge_rows_dev_reg",
        "merge_rows_host_reg", "merge_rows_dev_cnt", "merge_rows_host_cnt",
        "merge_rows_dev_el", "merge_rows_host_el", "mirror_grows_el",
        "mirror_rebuilds_el", "mirror_patches_el", "list_inserts",
        "list_pos_bytes_sum", "dev_upload_bytes") if k in info_after}
    run.log(f"{done_in:,} ops acknowledged in {run.seconds:.0f}s; p50 "
            f"{values['reply_p50_ms']:.2f} ms, p99 "
            f"{values['reply_p99_ms']:.2f} ms; {failed} failed; first "
            f"{edge:.0f}s {first_s / edge:.1f} ops/s, last {edge:.0f}s "
            f"{last_s / edge:.1f} ops/s (each 5 s: {per_5s.tolist()}); INFO "
            f"deltas {json.dumps(moved)}")
    stages = [(k[5:-3], moved_by(k) / max(done_in, 1), moved_by(k[:-2] + "n"))
              for k in info_after
              if k.startswith("span_") and k.endswith("_us")]
    run.log("stages, self us per operation acknowledged (and entries): "
            + ", ".join(f"{name} {us:.2f} ({int(n)})"
                        for name, us, n in stages if n))

    # ---- the comparison that decides `correct`
    def readback(cmds: list) -> list:
        c = nodes.Conn(port)
        try:
            return c.raw_replies(cmds)
        finally:
            c.close()

    t = time.monotonic()
    check = reference_rb.check_served_rb(run.config, mix, run.seed, results,
                                         readback)
    check["limits"] = dict(reference_rb.LIMITS)
    run.log(f"comparison with the reference: {time.monotonic() - t:.1f}s")
    conn.close()
    # the rows the device merged in the slice, by family, as the node
    # counts them: a pass folds a hot key's writes into one row
    rows_dev = {}
    if slice_info:
        for fam in slice_writes:
            k = f"merge_rows_dev_{fam}"
            rows_dev[fam] = max(0.0, float(slice_info[1].get(k, 0))
                                - float(slice_info[0].get(k, 0)))
        run.log(f"traced slice: writes by family {json.dumps(slice_writes)}"
                f", rows merged on the device {json.dumps(rows_dev)}")
    window = {"ops": done_in, "kops": done_in / 1e3, "seconds": run.seconds,
              "keys": 5, "info_before": info_before,
              "info_after": info_after, "client": values,
              "trace_rows": rows_dev,
              "trace_seconds": slice_t[1] - slice_t[0] if slice_t else 0.0}
    return {"values": values, "attempted": attempted, "failed": failed,
            "check": check, "window": window, "device": device}
