"""Scenario `mesh`: three active-active replicas of one table serve at once.
The mix's `node` (C, on the chip) takes the closed-loop clients that
scenario `served` drives; each of its `peers` (host nodes) takes a paced
open loop (loadgen_paced.py) of the same operations over the same keys, so
the nodes' writes meet on the hot records.  Every node pushes its writes to
the other two while it serves.

Set-up: the three boot snapshots written side by side (one table; each
file's NODE section names its node and its REPLICAS section lists the other
two with the pull watermark at the table's `max_stamp`, so the links come
up by partial replay — no MEET, no full sync), all three nodes booted,
every link up, workers started, warm-up with traffic on all three.  The
window is `--seconds` of it.  Then **quiesce** (reference_mesh.py's
docstring has the rule), a read-back of the same records from each of the
three nodes, and the comparison with the plain reference
(reference_mesh.check_mesh).

`served_ops`, `reply_p50_ms` and `reply_p99_ms` are taken at the clients
of the mix's node alone: the node on the chip is the system under test and
the peers' load is given.  `attempted` and `failed` count all three nodes'
clients; a paced pipeline never answered counts as failed.

With `--trace 1` the chip node traces `trace_seconds` in mid-window.  The
rows its device merged there are reckoned as scenario `served` reckons
them, from what the harness sent: the writes acknowledged at ANY node
inside the slice (each reaches the chip node, through its clients or over a
link), times the share of the slice's micro rounds that merged on the
device.

A program without the link's stages (`repl_ingest` in
utils/stagetime.STAGES, `span_repl_ingest_us` in INFO) cannot report this
cell's metrics: the scenario looks before it writes a snapshot and again at
boot, and fails at once.

    python scenarios/mesh.py --snapshot '<json>'     one node's snapshot (a
                                                      child of `run`)
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
import nodes                # noqa: E402
import reference_mesh       # noqa: E402
import traffic              # noqa: E402


def _served():
    """Scenario `served`'s warm-up, shared and not copied."""
    spec = importlib.util.spec_from_file_location(
        "scenario_served", os.path.join(HERE, "scenarios", "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- snapshots


def write_snapshot(job: dict) -> int:
    """One node's boot snapshot through the server's own writer, its peers
    listed under REPLICAS at the table's watermark."""
    from constdb_tpu.persist.snapshot import (NodeMeta, ReplicaRecord,
                                              write_snapshot_file)
    world = datagen.build_world(job["config"], job["seed"])
    meta = NodeMeta(node_id=job["node_id"], alias=job["name"],
                    addr=job["addr"], repl_last_uuid=world.max_stamp)
    members = [ReplicaRecord(addr=p["addr"], node_id=p["node_id"],
                             alias=p["name"],
                             add_t=datagen.BASE_MS << datagen.SEQ_BITS,
                             uuid_he_sent=world.max_stamp,
                             uuid_he_acked=world.max_stamp)
               for p in job["peers"]]
    return write_snapshot_file(
        job["path"], meta, members, world.batches(),
        compress_level=int(job["config"]["snapshot_compress_level"]))


def write_snapshots(work: str, config: dict, seed: int, names: list,
                    ports: dict) -> dict:
    """Every node's snapshot, written side by side by child processes.
    -> {name: path}"""
    members = {n: {"name": n, "node_id": config["nodes"][n]["node_id"],
                   "addr": f"127.0.0.1:{ports[n]}"} for n in names}
    procs, paths = [], {}
    for n in names:
        paths[n] = os.path.join(work, f"{n}.snapshot")
        job = dict(members[n], path=paths[n], config=config, seed=seed,
                   peers=[members[p] for p in names if p != n])
        procs.append((n, subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--snapshot",
             json.dumps(job)], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)))
    for n, p in procs:
        out, err = p.communicate(timeout=900)
        nodes.check(p.returncode == 0, f"snapshot of {n} failed: "
                    f"{err.decode(errors='replace')[-800:]}")
    return paths


# ----------------------------------------------------------------- links


def replica_rows(info: dict) -> dict:
    """INFO's `replica<i>` rows -> {peer addr: {field: int or str}}."""
    out = {}
    for k, v in info.items():
        if not (k.startswith("replica") and k[7:].isdigit()):
            continue
        row = dict(part.split("=", 1) for part in v.split(","))
        out[row["addr"]] = {f: int(x) if x.lstrip("-").isdigit() else x
                            for f, x in row.items()}
    return out


def wait_links(conns: dict, timeout: float) -> None:
    """Until every node counts every other as a connected replica."""
    deadline = time.monotonic() + timeout
    while True:
        up = {n: int(c.info().get("connected_replicas", 0))
              for n, c in conns.items()}
        if all(v == len(conns) - 1 for v in up.values()):
            return
        nodes.check(time.monotonic() < deadline,
                    f"links not up after {timeout:.0f}s: connected "
                    f"replicas {up}")
        time.sleep(0.1)


def settled(infos: dict, addrs: dict) -> bool:
    """reference_mesh.py's quiesce rule on one poll of every node's INFO."""
    rows = {n: replica_rows(i) for n, i in infos.items()}
    for a, info_a in infos.items():
        last = int(info_a["repl_log_last_uuid"])
        for b in infos:
            if a == b:
                continue
            mine = rows[a].get(addrs[b])
            his = rows[b].get(addrs[a])
            if mine is None or his is None:
                return False
            if not (mine["i_sent"] == last and mine["i_acked"] >= last
                    and his["he_sent"] >= last
                    and his["he_acked"] == his["he_sent"]):
                return False
    return True


def quiesce(conns: dict, addrs: dict, max_seconds: float) -> tuple:
    """-> (quiesced, seconds it took)."""
    t = time.monotonic()
    last = None
    while True:
        infos = {n: c.info() for n, c in conns.items()}
        logs = {n: i["repl_log_last_uuid"] for n, i in infos.items()}
        if logs == last and settled(infos, addrs):
            return True, time.monotonic() - t
        last = logs
        if time.monotonic() - t > max_seconds:
            return False, time.monotonic() - t
        time.sleep(0.2)


# ------------------------------------------------------------------ boot


def has_link_stages() -> bool:
    """Does this checkout's program declare the link's stages?  Read from
    its stage vocabulary (a module that never imports JAX), before any
    snapshot is written."""
    from constdb_tpu.utils import stagetime
    return "repl_ingest" in stagetime.STAGES


def boot(servers, work: str, config: dict, seed: int, names: list,
         stand_in: str, log) -> tuple:
    """All nodes up, tables whole, links up.  -> (ports, conns)"""
    ports = {n: nodes.free_port() for n in names}
    if stand_in:
        cfg_path = os.path.join(work, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(config, f)
        for n in names:
            peers = ",".join(f"{config['nodes'][p]['node_id']}:{ports[p]}"
                             for p in names if p != n)
            servers.procs[n] = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "fake_mesh.py"),
                 str(ports[n]), cfg_path, str(seed), stand_in,
                 str(config["nodes"][n]["node_id"]), peers])
    else:
        nodes.check(has_link_stages(), "this program has no `repl_ingest` "
                    "stage (utils/stagetime.STAGES): the cell's link "
                    "metrics cannot be read")
        t = time.monotonic()
        paths = write_snapshots(work, config, seed, names, ports)
        log(f"snapshots: {len(paths)} x "
            f"{os.path.getsize(paths[names[0]]):,} bytes in "
            f"{time.monotonic() - t:.1f}s")
        for n in names:
            servers.boot(n, config["nodes"][n], ports[n], paths[n])
    conns = {n: servers.wait_listening(n, ports[n],
                                       float(config["boot_timeout_s"]))
             for n in names}
    for n, c in conns.items():
        info = c.info()
        nodes.check("span_repl_ingest_us" in info,
                    f"node {n}'s INFO has no span_repl_ingest_us")
        nodes.check(int(info["keys"]) == int(config["recordcount"]),
                    f"node {n} holds {info['keys']} keys of "
                    f"{config['recordcount']}")
        nodes.check("boot_snapshot_quarantined" not in info,
                    f"node {n} quarantined its boot snapshot")
    wait_links(conns, float(config["links_timeout_s"]))
    return ports, conns


def start_workers(config: dict, mix: dict, seed: int, ports: dict) -> list:
    """The mix's node gets loadgen.py's closed loops, each peer one paced
    worker.  -> [(node name, process)], all `ready`."""
    layout = reference_mesh.conn_layout(mix)
    workers = []

    def spawn(script: str, job: dict, name: str) -> None:
        p = subprocess.Popen([sys.executable, os.path.join(HERE, script)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        p.stdin.write(json.dumps(job).encode() + b"\n")
        p.stdin.flush()
        workers.append((name, p))

    base = {"seed": seed, "config": config,
            "grace_seconds": mix["grace_seconds"]}
    name, conns = layout[0]
    k = int(mix["workers"])
    for w in range(k):
        spawn("loadgen.py", dict(base, port=ports[name], conns=conns[w::k],
                                 mix=mix), name)
    for name, conns in layout[1:]:
        spawn("loadgen_paced.py",
              dict(base, port=ports[name], conns=conns,
                   mix=reference_mesh.conn_mix(mix, peer=True),
                   rate_ops=mix["peer_rate_ops"]), name)
    for name, p in workers:
        line = p.stdout.readline()
        nodes.check(line == b"ready\n",
                    f"a load worker of {name} said {line!r}")
    return workers


# ------------------------------------------------------------------- run


def run(run) -> dict:
    mix, config = run.mix, run.config
    node = mix["node"]
    names = [node] + list(mix["peers"])
    world = datagen.build_world(config, run.seed)
    ports, conns = boot(run.servers, run.work, config, run.seed, names,
                        run.stand_in, run.log)
    conn = conns[node]
    addrs = {n: f"127.0.0.1:{ports[n]}" for n in names}
    if not (run.rehearse or run.stand_in):
        info = conn.info()
        nodes.check(info.get("engine") == "tpu"
                    and info.get("jax_backend") not in (None, "cpu", "none"),
                    f"node {node} does not run on an accelerator: engine="
                    f"{info.get('engine')} backend={info.get('jax_backend')}")
    run.log(f"{len(names)} nodes up, {world.n:,} records each, every link "
            "connected")
    workers = start_workers(config, mix, run.seed, ports)
    t_warm = time.monotonic() + 0.2
    for _n, p in workers:
        p.stdin.write(b"go %.6f\n" % t_warm)
        p.stdin.flush()
    _served()._warm_up(run, conn, t_warm)
    t0 = time.monotonic() + 0.25
    t1 = t0 + run.seconds
    for _n, p in workers:
        p.stdin.write(b"end %.6f\n" % t1)
        p.stdin.flush()
    time.sleep(max(0.0, t0 - time.monotonic()))
    info_before = conn.info()
    setup_s = t0 - run.t_process_start
    run.log(f"window opens: setup_s={setup_s:.3f}")
    slice_t = slice_info = None
    if run.trace:
        span = min(float(mix["trace_seconds"]), run.seconds / 2)
        time.sleep(max(0.0, t0 + (run.seconds - span) / 2 - time.monotonic()))
        if not run.stand_in:
            run.servers.control(node, f"trace-start {run.trace_dir}")
        a = time.monotonic()
        slice_info = [conn.info()]
        time.sleep(max(0.0, a + span - time.monotonic()))
        slice_info.append(conn.info())
        b = time.monotonic()
        if not run.stand_in:
            run.servers.control(node, "trace-stop")
        slice_t = (a, b)
    time.sleep(max(0.0, t1 - time.monotonic()))
    info_after = conn.info()
    results, at_node = [], {}
    for name, p in workers:
        for res in pickle.load(p.stdout):
            results.append(res)
            at_node[res["conn"]] = name
        p.wait()
    run.log("window closed, workers in")
    quiesced, took = quiesce(conns, addrs, float(mix["quiesce_max_seconds"]))
    run.log(f"quiesce: {'settled' if quiesced else 'NOT settled'} after "
            f"{took:.2f}s")
    infos = {n: c.info() for n, c in conns.items()}
    full_syncs = sum(int(i.get("repl_full_syncs", 0)) for i in infos.values())
    if run.stand_in:
        device = {"platform": "none", "kind": "reference stand-in",
                  "count": 1, "memory_peak_bytes": 0}
        if run.trace:
            nodes.stand_in_trace(run.trace_dir)
    else:
        device = run.servers.control(node, "device")

    # ---- metrics at the clients: the mix's node's for the end-to-end
    # numbers, every node's for attempted / failed
    ops_of, node_of, _names = reference_mesh.ops_for(mix, world, run.seed)
    done_in, lat, late = 0, [], []
    attempted = failed = trace_updates = 0
    for res in results:
        depth = res["depth"]
        t_sent = np.repeat(res["t_sent"], depth)[:res["sent"]]
        t_done = res["t_done"]
        answered = np.arange(res["sent"]) < res["done"]
        in_window = (t_sent >= t0) & (t_sent <= t1)
        attempted += int(in_window.sum())
        failed += int((in_window & ~answered).sum())
        if at_node[res["conn"]] == node:
            done_in += int((answered & (t_done >= t0) & (t_done <= t1)).sum())
            ms = np.where(answered, (t_done - t_sent) * 1e3, np.inf)
            lat.append(ms[in_window])
        else:
            late.append(res["late_ms"])
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
    late = np.concatenate(late) if late else np.zeros(1)
    moved = {k: float(info_after[k]) - float(info_before.get(k, 0))
             for k in ("compile_cache_misses", "serve_flushes",
                       "dev_rounds_resident", "host_micro_rounds",
                       "repl_coalesce_flushes", "repl_frames_coalesced",
                       "repl_ops_out", "repl_window_pauses",
                       "mirror_rebuilds_cause_repl_op",
                       "mirror_patch_overflows") if k in info_after}
    run.log(f"{done_in:,} ops acknowledged at {node} in {run.seconds:.0f}s; "
            f"p50 {values['reply_p50_ms']:.2f} ms, p99 "
            f"{values['reply_p99_ms']:.2f} ms; {failed} failed of "
            f"{attempted:,} at all nodes; peers' pipelines late p50 "
            f"{np.percentile(late, 50):.2f} ms, p99 "
            f"{np.percentile(late, 99):.2f} ms, max {late.max():.1f} ms; "
            f"INFO deltas at {node} {json.dumps(moved)}")

    spans = {k[5:-3]: (float(v) - float(info_before.get(k, 0)),
                       float(info_after[k[:-3] + "_n"])
                       - float(info_before.get(k[:-3] + "_n", 0)))
             for k, v in info_after.items()
             if k.startswith("span_") and k.endswith("_us")}
    run.log(f"stages at {node}, self us per operation it acknowledged (and "
            "entries): " + ", ".join(
                f"{s} {us / max(done_in, 1):.2f} ({int(n)})"
                for s, (us, n) in spans.items() if n))

    # ---- the comparison that decides `correct`
    def readback(n: int, records: list) -> list:
        c = nodes.Conn(ports[names[n]])
        try:
            return c.raw_replies([("HGETALL", world.key(r))
                                  for r in records])
        finally:
            c.close()

    t = time.monotonic()
    check = reference_mesh.check_mesh(
        world, mix, run.seed, results, ops_of, node_of, names,
        float(config["clock_margin_ms"]), int(mix["readback_records"]),
        readback, quiesced, full_syncs)
    check["limits"] = dict(reference_mesh.LIMITS)
    run.log(f"comparison with the reference: {time.monotonic() - t:.1f}s")
    for c in conns.values():
        c.close()
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
        run.log(f"traced slice: {trace_updates} writes acknowledged at all "
                f"nodes, micro rounds at {node} {json.dumps(rounds)}, rows "
                f"merged on the device {device_rows:.1f}")
    window = {"ops": done_in, "kops": done_in / 1e3, "seconds": run.seconds,
              "keys": world.n, "info_before": info_before,
              "info_after": info_after, "client": values,
              "trace_rows": {"el": device_rows, "env": device_rows},
              "trace_seconds": slice_t[1] - slice_t[0] if slice_t else 0.0}
    return {"values": values, "attempted": attempted, "failed": failed,
            "check": check, "window": window, "device": device}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--snapshot":
        print(write_snapshot(json.loads(sys.argv[2])))
    else:
        raise SystemExit("usage: mesh.py --snapshot '<json job>'")
