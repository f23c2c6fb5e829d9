"""The window of a served cell, for a scenario that drives one node as
scenarios/served.py does with load workers of its own
(scenarios/served_d.py): the workers started, `go`, the mix's warm-up
(served.py's), `end`, the trace slice, the workers' records in.

With `--trace 1` the node traces `trace_seconds` in the middle of the
window, or — where the mix says `"trace_span": "traffic"` — from the first
operation of the warm-up to the end of the window; INFO is read at both
ends of what was traced (`slice_info`).
"""

from __future__ import annotations

import importlib.util
import json
import os
import pickle
import subprocess
import sys
import time

import nodes

HERE = os.path.dirname(os.path.abspath(__file__))
NODE = "C"


def served_module():
    """Scenario `served`'s boot, warm-up and device read, shared and not
    copied."""
    spec = importlib.util.spec_from_file_location(
        "scenario_served", os.path.join(HERE, "scenarios", "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def start_workers(run, port: int, script: str, extra=None) -> list:
    """`workers` children of `script`, the mix's connections dealt among
    them; `extra(conns)` adds to a worker's job."""
    mix = run.mix
    n_workers = int(mix["workers"])
    conns = list(range(int(mix["connections"])))
    workers = []
    for w in range(n_workers):
        mine = conns[w::n_workers]
        job = {"port": port, "seed": run.seed, "conns": mine,
               "config": run.config, "mix": mix,
               "grace_seconds": mix["grace_seconds"]}
        job.update(extra(mine) if extra else {})
        p = subprocess.Popen([sys.executable, os.path.join(HERE, script)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        p.stdin.write(json.dumps(job).encode() + b"\n")
        p.stdin.flush()
        workers.append(p)
    for p in workers:
        line = p.stdout.readline()
        nodes.check(line == b"ready\n", f"a load worker said {line!r}")
    return workers


def drive(run, conn, workers: list, served) -> dict:
    """Warm-up, window and trace slice; -> the window's clocks, INFO at
    its ends and at the slice's, and the workers' records."""
    mix = run.mix
    whole = run.trace and mix.get("trace_span") == "traffic" \
        and not run.stand_in
    slice_info = slice_t = None
    if whole:
        slice_info = [conn.info()]
        run.servers.control(NODE, f"trace-start {run.trace_dir}")
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
    return {"t0": t0, "t1": t1, "setup_s": setup_s,
            "info_before": info_before, "info_after": info_after,
            "slice_t": slice_t, "slice_info": slice_info,
            "results": results}


def moved(win: dict, key: str) -> float:
    """A counter's delta over the window."""
    return float(win["info_after"].get(key, 0)) \
        - float(win["info_before"].get(key, 0))


def log_stages(run, win: dict, ops: int) -> None:
    """Each stage's self time an operation acknowledged, and its entries."""
    stages = [(k[5:-3], moved(win, k) / max(ops, 1), moved(win, k[:-2] + "n"))
              for k in win["info_after"]
              if k.startswith("span_") and k.endswith("_us")]
    run.log("stages, self us per operation acknowledged (and entries): "
            + ", ".join(f"{name} {us:.2f} ({int(n)})"
                        for name, us, n in stages if n))
