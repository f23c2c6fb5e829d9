#!/usr/bin/env python3
"""The benchmark of record: one cell, one run, one line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; its configuration
(`configs/<config>.json`), its traffic mix (`mixes/<traffic>.json`), the
mix's scenario (`scenarios/<scenario>.py`) and each per-layer metric
(`layers/<metric>.json`) are found by name — a new cell is new files and
new entries, never an edit here (README.md).

This process never imports JAX: the chip belongs to the node it boots
(server_proc.py), which also takes the device trace and reads the device's
memory.  The last line of standard output is the result, and it is printed
only if validate.py accepts it for this cell and trace mode; anything else
exits non-zero with the reason and prints no result.

`--rehearse` runs the same code off the chip (`--engine cpu`, the
configuration's `rehearse` overrides): it stamps `platform: cpu` and
proves nothing.  `--stand-in <fault>` puts the plain reference
(fake_node.py) in the program's place: the control and the planted faults
of test_correct.py, exit code 4 whatever `correct` says.  `--manifest`
names another manifest than BENCHMARK.json: selfcheck.py builds one for
its temporary cell.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import subprocess               # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import nodes                    # noqa: E402
import readers                  # noqa: E402
import validate                 # noqa: E402


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise nodes.BenchFailure(
        f"no {what} named {name!r} (have {[e['name'] for e in entries]})")


def load_scenario(name: str):
    path = os.path.join(HERE, "scenarios", f"{name}.py")
    nodes.check(os.path.exists(path), f"no scenario file {path}")
    spec = importlib.util.spec_from_file_location(f"scenario_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a scenario gets: the cell's files, the run's arguments, its
    work directory and its nodes."""

    def __init__(self, args, manifest: dict):
        self.args = args
        self.manifest = manifest
        self.cell = by_name(manifest["workloads"], args.workload, "workload")
        cfg_entry = by_name(manifest["configs"], self.cell["config"],
                            "configuration")
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.mix = load_json(os.path.join(
            HERE, "mixes", f"{self.cell['traffic']}.json"))
        self.rehearse = args.rehearse
        self.stand_in = args.stand_in
        if self.rehearse:
            self.config.update(self.config.get("rehearse", {}))
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.work = os.path.join(HERE, ".work", args.workload)
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        self.trace_dir = os.path.join(self.work, "trace")
        self.servers = nodes.Servers(self.work, self.rehearse)
        self.t_process_start = T_PROCESS_START
        self.log = log

    def metrics_of(self, kind: str) -> list:
        """The manifest's metrics of `kind` that this cell reports."""
        return validate.expected_metrics(self.manifest, self.cell["name"],
                                         kind == "per_layer")


def build_native() -> None:
    from constdb_tpu.utils import native_tables
    native_tables.build_native()      # raises unless cst_ext.so loads
    nodes.check(native_tables.load_ext() is not None,
                "native extension absent")


def reduce_trace(run: Run) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    argv = [sys.executable, os.path.join(HERE, "trace_reduce.py"),
            run.trace_dir]
    if run.rehearse or run.stand_in:
        argv.append("--rehearse")
    r = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=240)
    said = [ln for ln in r.stderr.splitlines()
            if ln.startswith("trace_reduce:")] or [r.stderr.strip()[-800:]]
    nodes.check(r.returncode == 0,
                f"trace reduction failed (rc={r.returncode}): {said[-1]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def result_line(run: Run, outcome: dict, trace: dict | None) -> dict:
    device = dict(outcome["device"])
    if run.trace:
        # the traced window: what the trace's events span, or the
        # scenario's own clock around it where the trace is sparse
        device["window_s"] = max(trace["window_s"],
                                 outcome["window"].get("trace_seconds", 0.0))
        trace = dict(trace, window_s=device["window_s"])
        device["busy_s"] = trace["busy_s"]
        peaks = readers.peaks_of(device["kind"], rehearse=run.rehearse
                                 or bool(run.stand_in))
        metrics = {}
        for m in run.metrics_of("per_layer"):
            spec = load_json(os.path.join(HERE, "layers",
                                          f"{m['name']}.json"))
            value = readers.read(spec, outcome["window"], trace, peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": outcome["values"][m["name"]],
                               "unit": m["unit"]}
                   for m in run.metrics_of("end_to_end")}
    check = outcome["check"]
    line = {"correct": all(check["numbers"][k] <= lim
                           for k, lim in check["limits"].items()),
            "attempted": outcome["attempted"], "failed": outcome["failed"],
            "metrics": metrics, "device": device}
    if run.trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["workload"] = run.cell["name"]
    line["seed"] = run.seed
    line["compared"] = {k: {"value": check["numbers"][k], "limit": lim}
                        for k, lim in check["limits"].items()}
    return line


def main(argv: list) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--stand-in", default="")
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    args = ap.parse_args(argv)
    run = None
    try:
        manifest = load_json(args.manifest)
        run = Run(args, manifest)
        if run.rehearse:
            log("REHEARSAL off the chip: --engine cpu at the "
                "configuration's rehearsal size — proves nothing about "
                "the device")
        build_native()
        scenario = load_scenario(run.mix["scenario"])
        try:
            outcome = scenario.run(run)
        finally:
            run.servers.kill_all()
        trace = reduce_trace(run) if run.trace else None
        line = result_line(run, outcome, trace)
        errors = validate.check_line(line, manifest, run.cell["name"],
                                     run.trace, rehearse=run.rehearse
                                     or bool(run.stand_in))
        nodes.check(not errors, "the result line is not the contract's: "
                    + "; ".join(errors))
    except (nodes.BenchFailure, subprocess.TimeoutExpired, OSError,
            KeyError, ValueError, ImportError) as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        if run is not None:
            run.servers.kill_all()
            for name in sorted(f[:-4] for f in os.listdir(run.work)
                               if f.endswith(".log")):
                log(f"--- {name}.log tail ---\n"
                    f"{run.servers.log_tail(name, 3000)}")
        return 1
    finally:
        if run is not None and not os.environ.get("BENCH_KEEP_WORK"):
            shutil.rmtree(run.work, ignore_errors=True)
    check = outcome["check"]
    if check.get("first"):
        log(f"first difference: {check['first']}")
    log(f"compared: {json.dumps(check.get('compared', {}))}")
    for k, v in line["compared"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 4 if run.stand_in else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
