#!/usr/bin/env python3
"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports.  Run as a child of run.py once the nodes are down
(reading a trace imports JAX, which the parent never does; the child is
held to the CPU and needs no chip):

    python benchmark/trace_reduce.py <trace-dir> [--rehearse] [--dump]

Prints one JSON object: `window_s` (first to last event of the trace),
`busy_s` (union of the intervals in which an operation ran on the device,
averaged over the device planes), `modules` (seconds per XLA module name,
the launch id stripped), `device_ops` and `idle_gaps` (top ten each, for
`breakdown`).  A trace with no device plane, or none busy, is an error:
exit 3 and the reason, never a zero.

What a device is: a plane named `/device:TPU:<n>` (or GPU).  Its line
`XLA Ops` holds one event per executed HLO operation, `XLA Modules` one
per executed program.  `--rehearse` takes, where no device plane exists,
the host plane's XLA executor threads instead and says so in `planes`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_LAUNCH_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list:
    """-> [(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events]))
        out.append((plane.name, lines))
    return out


def union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def module_name(event_name: str) -> str:
    return _LAUNCH_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO line: keep the result's
    name, the opcode and a custom call's target."""
    lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    m = re.search(r"\} ([a-z][\w-]*)\(", rhs) or \
        re.search(r"\] ([a-z][\w-]*)\(", rhs)
    out = lhs.lstrip("%") + (" " + m.group(1) if m else "")
    t = re.search(r'custom_call_target="([^"]+)"', rhs)
    return (out + (" " + t.group(1) if t else ""))[:80]


def _top(pairs: dict, k: int = 10) -> list:
    return [[n, s] for n, s in sorted(pairs.items(),
                                      key=lambda kv: -kv[1])[:k]]


def reduce_planes(planes: list, rehearse: bool = False) -> dict:
    device = [(n, ls) for n, ls in planes
              if n.startswith("/device:") and any(
                  ln == OPS_LINE and evs for ln, evs in ls)]
    host = [(n, ls) for n, ls in planes if n.startswith("/host:")]
    if not device:
        if not rehearse:
            raise ValueError(
                "the trace holds no device plane with operations on it "
                f"(planes: {[n for n, _ in planes]})")
        # rehearsal: XLA's CPU executor threads stand in for the device
        stand_in = [(OPS_LINE, evs) for n, ls in host for ln, evs in ls
                    if ln.startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen"))]
        stand_in = [(ln, [e for e in evs if e[2] > 0]) for ln, evs in stand_in]
        merged = [e for _, evs in stand_in for e in evs]
        if not merged:
            raise ValueError("rehearsal trace holds no XLA executor event")
        device = [("/host:CPU (rehearsal stand-in)",
                   [(OPS_LINE, merged), (MODULES_LINE, merged)])]
    every = [(s, s + d) for _, ls in planes for _, evs in ls
             for _, s, d in evs]
    t0 = min(s for s, _ in every)
    t1 = max(e for _, e in every)
    busy_total = 0.0
    ops, modules, gaps = {}, {}, {}
    host_spans = sorted((s, s + d, name) for _, ls in host for _, evs in ls
                        for name, s, d in evs if d > 0)
    for _, lines in device:
        by_line = dict(lines)
        op_events = by_line.get(OPS_LINE, [])
        busy = union([(s, s + d) for _, s, d in op_events if d > 0])
        busy_total += sum(e - s for s, e in busy)
        for name, _, d in op_events:
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + d * 1e-9
        for name, _, d in by_line.get(MODULES_LINE, []):
            m = module_name(name)
            modules[m] = modules.get(m, 0.0) + d * 1e-9
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        idle = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)), reverse=True)[:40]
        for length, g0, g1 in idle:
            what = _host_during(host_spans, g0, g1)
            gaps[what] = max(gaps.get(what, 0.0), length * 1e-9)
    busy_s = busy_total * 1e-9 / len(device)
    if busy_s <= 0:
        raise ValueError("no operation ran on the device inside the trace")
    return {"window_s": (t1 - t0) * 1e-9, "busy_s": busy_s,
            "modules": modules, "device_ops": _top(ops),
            "idle_gaps": _top(gaps),
            "planes": [n for n, _ in device]}


def _host_during(host_spans: list, g0: float, g1: float) -> str:
    """The host event that covers most of the gap [g0, g1]; the program has
    no spans of its own yet, so this is XLA's runtime at best."""
    best, best_cover = "host:untraced", 0.0
    for s, e, name in host_spans:
        if s >= g1:
            break
        cover = min(e, g1) - max(s, g0)
        if cover > best_cover:
            best, best_cover = "host:" + name[:48], cover
    return best


def dump(planes: list) -> None:
    """What a trace holds, for reading by hand."""
    for name, lines in planes:
        print(f"PLANE {name!r}: {len(lines)} lines")
        for lname, evs in lines:
            total = sum(d for _, _, d in evs) * 1e-9
            print(f"  LINE {lname!r}: {len(evs)} events, {total:.6f}s")
            longest = sorted(evs, key=lambda e: -e[2])[:10]
            for n, s, d in longest:
                print(f"    {d * 1e-9:.6f}s  {n[:100]!r}")


def main(argv: list) -> int:
    trace_dir = argv[0]
    path = trace_dir if trace_dir.endswith(".pb") else find_xplane(trace_dir)
    planes = load(path)
    if "--dump" in argv:
        dump(planes)
    try:
        out = reduce_planes(planes, rehearse="--rehearse" in argv)
    except ValueError as e:
        print(f"trace_reduce: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
