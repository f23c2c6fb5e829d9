"""The result line against BENCHMARK.json and the contract, for one cell and
one trace mode.  run.py prints a line only if `check_line` returns no
error; selfcheck.py holds it to good and malformed lines (PR 22's among
them: a traced line without `busy_s`).

    python benchmark/validate.py <workload> <0|1> < line.json
"""

from __future__ import annotations

import json
import math
import os
import re
import sys

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
SHARE_LIMIT = 105.0     # a share of a roofline or a peak cannot pass 100%


def _number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v)


def expected_metrics(manifest: dict, workload: str, trace: bool) -> list:
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def check_line(line, manifest: dict, workload: str, trace: bool,
               rehearse: bool = False) -> list:
    """-> list of reasons the line is not the contract's (empty: valid).
    `rehearse`: a run off the chip — per-layer metrics whose counters only
    the device engine has may be missing, and the platform is not held."""
    errors = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for k in KEYS:
        if k not in line:
            errors.append(f"key {k!r} is missing")
    if errors:
        return errors
    if not isinstance(line["correct"], bool):
        errors.append("`correct` is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            errors.append(f"`{k}` is not a count")
    if not errors and line["attempted"] <= 0:
        errors.append("`attempted` is 0: the window drove nothing")
    if not errors and line["failed"] > line["attempted"]:
        errors.append("`failed` exceeds `attempted`")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return errors + ["`metrics` is not an object"]
    want = expected_metrics(manifest, workload, trace)
    if not want:
        errors.append(f"the manifest gives {workload!r} no metric here")
    for m in want:
        got = metrics.get(m["name"])
        if got is None:
            if not (trace and rehearse):
                errors.append(f"metric {m['name']!r} is missing")
            continue
        if not isinstance(got, dict) or "value" not in got \
                or "unit" not in got:
            errors.append(f"metric {m['name']!r} is not value and unit")
            continue
        if not _number(got["value"]):
            errors.append(f"metric {m['name']!r} has no finite number")
            continue
        if got["unit"] != m["unit"]:
            errors.append(f"metric {m['name']!r} has unit {got['unit']!r}, "
                          f"the manifest says {m['unit']!r}")
        if not trace and got["value"] <= 0:
            errors.append(f"end-to-end metric {m['name']!r} is not above 0")
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]) \
                and not 0 < got["value"] <= SHARE_LIMIT:
            errors.append(f"share {m['name']!r} reads {got['value']}: "
                          "not in (0, 105]")
    extra = set(metrics) - {m["name"] for m in want}
    if extra:
        errors.append(f"metrics the manifest does not give this cell in "
                      f"this mode: {sorted(extra)}")
    if trace and not any(m["name"] in metrics for m in want):
        errors.append("no per-layer metric was read")
    device = line["device"]
    if not isinstance(device, dict):
        return errors + ["`device` is not an object"]
    for k in DEVICE_KEYS:
        if k not in device:
            errors.append(f"device.{k} is missing")
    if all(k in device for k in DEVICE_KEYS):
        if not isinstance(device["count"], int) or device["count"] < 1:
            errors.append("device.count is not a count of devices")
        if not _number(device["memory_peak_bytes"]) \
                or device["memory_peak_bytes"] < 0:
            errors.append("device.memory_peak_bytes is not a byte count")
        if not rehearse:
            chips = next(w["chips"] for w in manifest["workloads"]
                         if w["name"] == workload)
            if device["platform"] == "cpu":
                errors.append("device.platform is cpu: no accelerator")
            if device["count"] < chips:
                errors.append(f"device.count {device['count']} is under "
                              f"the cell's {chips} chips")
            if device["memory_peak_bytes"] <= 0:
                errors.append("device.memory_peak_bytes is 0 on a device")
    if trace:
        for k in ("window_s", "busy_s"):
            if not _number(device.get(k)):
                errors.append(f"device.{k} is missing from a traced line")
        if _number(device.get("window_s")) and _number(device.get("busy_s")) \
                and not 0 < device["busy_s"] <= device["window_s"]:
            errors.append(f"device.busy_s {device['busy_s']} is not above 0 "
                          f"and at most window_s {device['window_s']}")
    if "breakdown" in line:
        bd = line["breakdown"]
        for k in ("device_ops", "idle_gaps"):
            rows = bd.get(k) if isinstance(bd, dict) else None
            if not isinstance(rows, list) or len(rows) > 10 or not all(
                    isinstance(r, list) and len(r) == 2
                    and isinstance(r[0], str) and _number(r[1])
                    for r in rows):
                errors.append(f"breakdown.{k} is not at most 10 pairs of "
                              "name and seconds")
    if "compared" not in line or list(line)[-1] != "compared":
        errors.append("`compared` (each number beside its limit) is not "
                      "the line's last key")
    elif not line["compared"] or not all(
            isinstance(v, dict) and _number(v.get("value"))
            and _number(v.get("limit")) for v in line["compared"].values()):
        errors.append("`compared` does not give each number and its limit")
    return errors


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def check_manifest(manifest: dict) -> list:
    """The limits of the contract that a slip of the pen breaks: names,
    units, lengths, bounds, chips, and that every name resolves."""
    errors = []

    def text(what, s):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            errors.append(f"{what} is not 1 to 200 characters on one line")

    cells = [w["name"] for w in manifest["workloads"]]
    configs = [c["name"] for c in manifest["configs"]]
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for n in cells + configs + [m["name"] for m in metrics] + \
            [w["traffic"] for w in manifest["workloads"]]:
        if not NAME.match(n):
            errors.append(f"name {n!r} is outside the contract's alphabet")
    for group in (cells, configs, [m["name"] for m in metrics]):
        if len(set(group)) != len(group):
            errors.append(f"a name appears twice among {group}")
    for w in manifest["workloads"]:
        text(f"why of {w['name']}", w["why"])
        if w["config"] not in configs:
            errors.append(f"cell {w['name']} names no configuration")
        if w["chips"] not in (1, 4):
            errors.append(f"cell {w['name']} asks for {w['chips']} chips")
    for c in manifest["configs"]:
        text(f"why of {c['name']}", c["why"])
        text(f"source of {c['name']}", c["source"])
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            errors.append(f"file of {c['name']} is outside `paths`")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        errors.append("no end-to-end metric is `setup_s`")
    for m in metrics:
        if not UNIT.match(m["unit"]):
            errors.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            errors.append(f"`better` of {m['name']}")
        if m["source"] not in SOURCES:
            errors.append(f"source {m['source']!r} of {m['name']}")
        for w in m.get("workloads", []):
            if w not in cells:
                errors.append(f"{m['name']} lists no cell {w!r}")
    for m in manifest["end_to_end"]:
        if not 0.01 <= m.get("bound", -1) <= 0.25:
            errors.append(f"bound of {m['name']} is outside 1% to 25%")
        if m["source"] not in ("host_clock", "device_trace"):
            errors.append(f"end-to-end {m['name']} has source {m['source']}")
    for m in manifest["per_layer"]:
        text(f"layer of {m['name']}", m["layer"])
        if m["moves"] not in e2e:
            errors.append(f"{m['name']} moves no end-to-end metric")
    for w in cells:
        if len(expected_metrics(manifest, w, False)) < 2:
            errors.append(f"cell {w} reports no end-to-end metric but set-up")
        if not expected_metrics(manifest, w, True):
            errors.append(f"cell {w} reports no per-layer metric")
    if not 1 <= manifest["run_seconds"] <= 51:
        errors.append("run_seconds is outside 1 to 51")
    return errors


def main(argv: list) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    line = json.loads(sys.stdin.read().strip().splitlines()[-1])
    errors = check_manifest(manifest) + check_line(line, manifest, argv[0], bool(int(argv[1])))
    for e in errors:
        print(e, file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
