"""Scenario `served_d`: one node serves YCSB workload D — reads of the
latest records and inserts of new ones (traffic_d.py) — over client
sockets.  Set-up: snapshot from the seed, boot and warm-up as `served`
(the same 1,000,000-record table); the window is `--seconds` of the mix;
then the comparison with the plain reference (reference_d.check_served_d)
on what the window answered, and a read-back of every acknowledged
insert.  Load workers are loadgen_d.py; the stand-in is fake_d_node.py.

Keys are created all window, so the cell's metrics read the program's
`key_create` stage (utils/stagetime.STAGES) and its
`serve_read_flushes_created` counter (server/info.py).  A program without
them cannot report them: the scenario looks BEFORE it boots, and fails at
once.

The traced span (`window.py`) is the mix's; the rows the device merged
there (bytes.py) are, by family, what the node's INFO says it merged on
the device in the span (`merge_rows_dev_<fam>`: an insert is ten element
rows); `env` is host-authoritative on the micro path and is not counted.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import datagen              # noqa: E402
import nodes                # noqa: E402
import reference_d          # noqa: E402
import traffic_d as T       # noqa: E402
import window               # noqa: E402

NODE = window.NODE
DEVICE_FAMILIES = ("reg", "cnt", "el")


def has_key_create() -> bool:
    """Does this checkout's program stage the keys it creates?  Read from
    its sources' own tables, with no node booted: the stage in
    utils/stagetime.STAGES and the counter in server/info.py's text."""
    from constdb_tpu.utils import stagetime
    if "key_create" not in stagetime.STAGES:
        return False
    with open(os.path.join(ROOT, "constdb_tpu", "server", "info.py")) as f:
        return "serve_read_flushes_created" in f.read()


def _boot(run, world, served):
    if not run.stand_in:
        return served._boot(run, world)
    port = nodes.free_port()
    cfg_path = os.path.join(run.work, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(run.config, f)
    run.servers.procs[NODE] = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "fake_d_node.py"), str(port),
         cfg_path, str(run.seed), run.stand_in])
    return port, run.servers.wait_listening(
        NODE, port, float(run.config["boot_timeout_s"]))


def run(run) -> dict:
    mix = run.mix
    nodes.check(run.stand_in or has_key_create(),
                "this program has no `key_create` stage "
                "(utils/stagetime.STAGES) or no `serve_read_flushes_created` "
                "counter (server/info.py): the cell's key metrics have "
                "nothing to read")
    served = window.served_module()
    world = datagen.build_world(run.config, run.seed)
    port, conn = _boot(run, world, served)
    run.log(f"node up: {world.n:,} records")
    workers = window.start_workers(run, port, "loadgen_d.py")
    win = window.drive(run, conn, workers, served)
    t0, t1, results = win["t0"], win["t1"], win["results"]
    device = served._device(run)
    if run.trace and run.stand_in:
        nodes.stand_in_trace(run.trace_dir)

    # ---- metrics at the clients
    ops_of = {res["conn"]: T.conn_ops(mix, world.n, run.seed, res["conn"])
              for res in results}
    done_in, lat, acked = 0, [], []
    attempted = failed = 0
    for res in results:
        t_sent = np.repeat(res["t_sent"], res["depth"])[:res["sent"]]
        t_done = res["t_done"]
        answered = np.arange(res["sent"]) < res["done"]
        in_window = (t_sent >= t0) & (t_sent <= t1)
        attempted += int(in_window.sum())
        failed += int((in_window & ~answered).sum())
        done_in += int((answered & (t_done >= t0) & (t_done <= t1)).sum())
        lat.append(np.where(answered, (t_done - t_sent) * 1e3,
                            np.inf)[in_window])
        kinds = ops_of[res["conn"]].kinds[:res["sent"]]
        acked.append(t_done[answered & (kinds == T.INSERT)])
    lat = np.concatenate(lat) if lat else np.zeros(0)
    nodes.check(len(lat) > 0, "no operation was sent inside the window")
    values = {"served_ops": done_in / run.seconds,
              "reply_p50_ms": float(np.percentile(lat, 50)),
              "reply_p99_ms": float(np.percentile(lat, 99)),
              "setup_s": win["setup_s"]}
    acked = np.sort(np.concatenate(acked))
    # when the key table passed the next power of two over the table
    cross = 1 << int(world.n).bit_length()
    at = acked[cross - world.n] - t0 if len(acked) > cross - world.n \
        else None
    moved = {k: window.moved(win, k) for k in (
        "compile_cache_misses", "serve_flushes", "serve_keys_created",
        "serve_read_flushes", "serve_read_flushes_created",
        "serve_barriers", "read_cache_hits", "read_cache_misses",
        "dev_rounds_resident", "host_micro_rounds", "merge_rows_dev_el",
        "merge_rows_host_el", "mirror_grows_el", "mirror_grows_env",
        "mirror_grows_reg", "mirror_rebuilds_el", "mirror_rebuilds_env",
        "mirror_patches_el", "span_key_create_us", "span_key_create_n",
        "dev_upload_bytes", "gc_collections_gen2", "span_gc_us",
        "serve_gather_passes", "serve_gather_msgs", "serve_lone_cmds")
        if k in win["info_after"]}
    run.log(f"{done_in:,} ops acknowledged in {run.seconds:.0f}s; p50 "
            f"{values['reply_p50_ms']:.2f} ms, p99 "
            f"{values['reply_p99_ms']:.2f} ms; {failed} failed; "
            f"{len(acked):,} inserts acknowledged in all, "
            f"{int(((acked >= t0) & (acked <= t1)).sum()):,} in the window; "
            f"keys {win['info_before'].get('keys')} -> "
            f"{win['info_after'].get('keys')}; the table passed {cross:,} "
            + (f"at {at:+.1f}s from the window's start" if at is not None
               else "never")
            + f"; INFO deltas {json.dumps(moved)}")
    window.log_stages(run, win, done_in)

    # ---- the comparison that decides `correct`
    def readback(records: list) -> list:
        c = nodes.Conn(port)
        try:
            return c.raw_replies([("HGETALL", world.key(r))
                                  for r in records])
        finally:
            c.close()

    t = time.monotonic()
    check = reference_d.check_served_d(world, mix, run.seed, results, ops_of,
                                       readback)
    check["limits"] = dict(reference_d.LIMITS)
    cmp = check["compared"]
    run.log(f"comparison with the reference: {time.monotonic() - t:.1f}s; "
            f"reads that answered nothing: {cmp['reads_empty']:,} of "
            f"{cmp['reads']:,} compared ({cmp['reads_of_inserts']:,} of "
            "inserted records)")
    conn.close()
    rows_dev = {}
    info = win["slice_info"]
    if info:
        rows_dev = {fam: max(0.0, float(info[1].get(f"merge_rows_dev_{fam}",
                                                    0))
                             - float(info[0].get(f"merge_rows_dev_{fam}", 0)))
                    for fam in DEVICE_FAMILIES}
        run.log(f"traced span: rows merged on the device "
                f"{json.dumps(rows_dev)}")
    slice_t = win["slice_t"]
    window_d = {"ops": done_in, "kops": done_in / 1e3,
                "seconds": run.seconds, "keys": world.n + len(acked),
                "info_before": win["info_before"],
                "info_after": win["info_after"], "client": values,
                "trace_rows": rows_dev,
                "trace_seconds": slice_t[1] - slice_t[0] if slice_t else 0.0}
    return {"values": values, "attempted": attempted, "failed": failed,
            "check": check, "window": window_d, "device": device}
