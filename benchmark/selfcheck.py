#!/usr/bin/env python3
"""The yardstick checked against itself, off the chip (run by hand and in
rehearsal; test_correct.py runs it too):

    python benchmark/selfcheck.py

  1. trace_reduce.py on the recorded chip trace in fixtures/ (PR 25's
     launcher probe: 200,000 records, one connection, 3 s) gives the
     numbers read by hand from its dump;
  2. validate.py accepts a good line and names the fault of each
     malformed one — PR 22's among them (traced, no `busy_s`);
  3. the zipfian generator's head share is the distribution's;
  4. a cell made of a temporary configuration, mix, scenario and layer
     file (plus a temporary manifest) is found and run by name, with no
     edit to any file that is there.
Exit 0 only if all four hold.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import traffic      # noqa: E402
import validate     # noqa: E402

FAILS = []


def expect(cond, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what, flush=True)
    if not cond:
        FAILS.append(what)


def check_reduction() -> None:
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         os.path.join(HERE, "fixtures", "probe_200k.xplane.pb")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    expect(r.returncode == 0, f"fixture trace reduces (rc={r.returncode})")
    if r.returncode:
        print(r.stderr[-1000:])
        return
    out = json.loads(r.stdout.strip().splitlines()[-1])
    expect(out["planes"] == ["/device:TPU:0"], "one device plane found")
    expect(abs(out["busy_s"] - 0.021365663) < 1e-6,
           f"busy_s is the union of XLA Ops ({out['busy_s']})")
    expect(abs(out["window_s"] - 3.559733883) < 1e-6,
           f"window_s spans the trace ({out['window_s']})")
    expect(abs(out["modules"].get("jit_bulk_lww_src", 0) - 0.018350712)
           < 1e-6, "module time has the launch id stripped")
    expect(0 < len(out["device_ops"]) <= 10
           and 0 < len(out["idle_gaps"]) <= 10, "breakdown lists fit")
    # no device plane: an error, not a zero
    import trace_reduce
    host_only = [("/host:CPU", [("main", [("x", 0.0, 5.0)])])]
    try:
        trace_reduce.reduce_planes(host_only)
        expect(False, "a trace with no device plane is refused")
    except ValueError:
        expect(True, "a trace with no device plane is refused")
    idle_dev = [("/device:TPU:0", [("XLA Ops", [("op", 1.0, 0.0)])]),
                ("/host:CPU", [("main", [("x", 0.0, 5.0)])])]
    try:
        trace_reduce.reduce_planes(idle_dev)
        expect(False, "busy_s = 0 is refused")
    except ValueError:
        expect(True, "busy_s = 0 is refused")


def check_validator() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = manifest["workloads"][0]["name"]
    expect(validate.check_manifest(manifest) == [],
           f"BENCHMARK.json keeps the contract's limits "
           f"{validate.check_manifest(manifest)[:2]}")
    for m in validate.expected_metrics(manifest, cell, True):
        expect(os.path.exists(os.path.join(HERE, "layers",
                                           f"{m['name']}.json")),
               f"layer file of {m['name']} is there")
    long_why = copy.deepcopy(manifest)
    long_why["workloads"][0]["why"] = "x" * 201
    expect(validate.check_manifest(long_why) != [],
           "a `why` of 201 characters is refused")
    compared = {"reads_wrong": {"value": 0, "limit": 0}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5 << 30}
    good0 = {"correct": True, "attempted": 9000, "failed": 0,
             "metrics": {m["name"]: {"value": 3.5, "unit": m["unit"]}
                         for m in validate.expected_metrics(
                             manifest, cell, False)},
             "device": dict(device), "compared": compared}
    good1 = {"correct": True, "attempted": 9000, "failed": 0,
             "metrics": {m["name"]: {"value": 3.5, "unit": m["unit"]}
                         for m in validate.expected_metrics(
                             manifest, cell, True)},
             "device": dict(device, window_s=4.1, busy_s=0.2),
             "breakdown": {"device_ops": [["fusion.1", 0.1]],
                           "idle_gaps": [["host:untraced", 0.5]]},
             "compared": compared}
    expect(validate.check_line(good0, manifest, cell, False) == [],
           "a good --trace 0 line passes")
    expect(validate.check_line(good1, manifest, cell, True) == [],
           "a good --trace 1 line passes")

    def bad(line, trace, needle, what):
        errs = validate.check_line(line, manifest, cell, trace)
        expect(any(needle in e for e in errs), f"{what} -> {errs[:1]}")

    pr22 = copy.deepcopy(good1)
    del pr22["device"]["busy_s"]
    bad(pr22, True, "busy_s", "PR 22's line (traced, no busy_s) is refused")
    x = copy.deepcopy(good1)
    x["device"]["busy_s"] = 9.0
    bad(x, True, "at most window_s", "busy_s over window_s is refused")
    x = copy.deepcopy(good1)
    x["device"]["busy_s"] = 0
    bad(x, True, "not above 0", "busy_s of 0 is refused")
    x = copy.deepcopy(good0)
    x["metrics"].pop("setup_s")
    bad(x, False, "setup_s", "a missing end-to-end metric is refused")
    x = copy.deepcopy(good0)
    x["metrics"]["setup_s"]["unit"] = "ms"
    bad(x, False, "unit", "a wrong unit is refused")
    x = copy.deepcopy(good0)
    x["metrics"]["setup_s"] = 12.5
    bad(x, False, "value and unit", "a bare number is refused")
    x = copy.deepcopy(good0)
    x["metrics"]["served_ops"]["value"] = float("nan")
    bad(x, False, "finite", "NaN is refused")
    x = copy.deepcopy(good0)
    x["metrics"]["served_ops"]["value"] = 0
    bad(x, False, "above 0", "an end-to-end 0 is refused")
    x = copy.deepcopy(good1)
    roof = next(n for n in x["metrics"] if "roofline" in n)
    x["metrics"][roof]["value"] = 140.0
    bad(x, True, "105", "a roofline share of 140% is refused")
    x = copy.deepcopy(good0)
    x["device"]["platform"] = "cpu"
    bad(x, False, "no accelerator", "platform cpu is refused on the chip")
    x = copy.deepcopy(good0)
    del x["device"]["memory_peak_bytes"]
    bad(x, False, "memory_peak_bytes", "a device key missing is refused")
    x = copy.deepcopy(good0)
    x["extra"] = x.pop("compared")
    bad(x, False, "last key", "a line without `compared` last is refused")
    x = copy.deepcopy(good1)
    x["breakdown"]["device_ops"] = [["a", 1.0]] * 11
    bad(x, True, "at most 10", "eleven breakdown rows are refused")
    x = copy.deepcopy(good0)
    del x["failed"]
    bad(x, False, "'failed'", "a missing key is refused")
    expect(validate.check_line([1], manifest, cell, False) != [],
           "a line that is no object is refused")


def check_zipfian() -> None:
    n, theta, draws = 125_000, 0.99, 400_000
    rng = np.random.default_rng(5)
    ranks = traffic.draw_ranks(rng, {"kind": "zipfian", "constant": theta},
                               n, draws)
    w = 1.0 / np.arange(1, n + 1) ** theta
    p = w / w.sum()
    head1 = float((ranks == 0).mean())
    head100 = float((ranks < 100).mean())
    expect(abs(head1 - p[0]) < 0.003,
           f"rank 0 share {head1:.4f} against {p[0]:.4f}")
    expect(abs(head100 - p[:100].sum()) < 0.005,
           f"first 100 ranks' share {head100:.4f} against "
           f"{p[:100].sum():.4f}")
    mix = {"connections": 8, "max_ops_per_conn": 50_000, "check_share": 0.25,
           "operations": {"read": 0.5, "update": 0.5},
           "keys": {"kind": "zipfian", "constant": theta}}
    n = 1_000_000
    w = 1.0 / np.arange(1, n + 1) ** theta
    p0 = w[0] / w.sum()
    a, b = (traffic.conn_ops(mix, n, 10, 7, c) for c in (3, 4))
    expect(int(a.kinds.sum()) == 25_000, "update share is exact")
    hot_a, hot_b = (int(np.bincount(o.records).argmax()) for o in (a, b))
    expect(hot_a == hot_b, "two connections share one hottest record: all "
           "draw from one distribution over all records")
    top = float((a.records == hot_a).mean())
    expect(abs(top - p0) < 0.01,
           f"hottest record's share {top:.4f} against {p0:.4f} (YCSB, N=1M)")
    other = traffic.conn_ops(mix, n, 10, 8, 3)
    expect(not np.array_equal(a.kinds, other.kinds)
           and not np.array_equal(a.records, other.records),
           "another seed sends another sequence of kinds and records")

TMP = "selfcheck-tmp"


def check_new_cell() -> None:
    """New files and entries only; every name here is new."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "configs", "ycsb-1node.json")) as f:
        config = json.load(f)
    config["rehearse"] = {"recordcount": 4000}
    with open(os.path.join(HERE, "mixes", "ycsb-b.json")) as f:
        mix = json.load(f)
    mix.pop("trace_span")       # trace a slice in the middle of the window
    mix.update(scenario="selfcheck_tmp", connections=4, workers=1,
               warmup_seconds=1, trace_seconds=1, max_ops_per_conn=100_000,
               operations={"read": 0.8, "update": 0.2})
    layer = {"name": "selfcheck_flushes.serve", "unit": "count",
             "layer": "plan + coalesce", "moves": "served_ops",
             "workloads": [TMP], "reader": "info_delta",
             "counters": ["serve_flushes"]}
    scenario = ("import importlib.util, os\n"
                "_p = os.path.join(os.path.dirname(__file__), 'served.py')\n"
                "_s = importlib.util.spec_from_file_location('served', _p)\n"
                "_m = importlib.util.module_from_spec(_s)\n"
                "_s.loader.exec_module(_m)\n"
                "run = _m.run\n")
    files = {os.path.join(HERE, "configs", f"{TMP}.json"): json.dumps(config),
             os.path.join(HERE, "mixes", f"{TMP}.json"): json.dumps(mix),
             os.path.join(HERE, "layers", f"{layer['name']}.json"):
                 json.dumps(layer),
             os.path.join(HERE, "scenarios", "selfcheck_tmp.py"): scenario}
    manifest["configs"].append({
        "name": TMP, "source": "selfcheck", "reduced": [], "why": "x",
        "file": f"benchmark/configs/{TMP}.json"})
    manifest["workloads"].append({"name": TMP, "config": TMP,
                                  "traffic": TMP, "chips": 1, "why": "x"})
    for m in manifest["end_to_end"]:
        if "ycsb-b" in m.get("workloads", []):
            m["workloads"].append(TMP)
    manifest["per_layer"].append({
        "name": layer["name"], "unit": "count", "better": "lower",
        "source": "program_counter", "layer": layer["layer"],
        "moves": "served_ops", "workloads": [TMP]})
    tmp_manifest = os.path.join(HERE, ".work", f"{TMP}.manifest.json")
    os.makedirs(os.path.dirname(tmp_manifest), exist_ok=True)
    files[tmp_manifest] = json.dumps(manifest)
    try:
        for path, text in files.items():
            with open(path, "w") as f:
                f.write(text)
        for trace in (0, 1):
            r = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 TMP, "--seed", "2147483659", "--seconds", "2", "--trace",
                 str(trace), "--rehearse", "--manifest", tmp_manifest],
                capture_output=True, text=True, timeout=600)
            ok = r.returncode == 0
            line = json.loads(r.stdout.strip().splitlines()[-1]) if ok else {}
            expect(ok and line.get("correct") is True,
                   f"the temporary cell runs, --trace {trace} "
                   f"(rc={r.returncode})")
            if not ok:
                print(r.stderr[-1500:])
            elif trace:
                expect(set(line["metrics"]) == {layer["name"]},
                       "its traced line holds its own layer metric alone")
    finally:
        for path in files:
            if os.path.exists(path):
                os.unlink(path)


def main() -> int:
    check_reduction()
    check_validator()
    check_zipfian()
    check_new_cell()
    print(f"{len(FAILS)} failed")
    return 1 if FAILS else 0


if __name__ == "__main__":
    sys.exit(main())
