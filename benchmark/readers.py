"""The per-layer readers: the small fixed vocabulary a `layers/<metric>.json`
chooses from by its `reader` key.  A reader that finds nothing to read
returns None and the metric is left out of the line — never a 0 for a
share.  An unknown reader is an error.

    info_ratio            summed deltas of INFO counters (`numerator`) over
                          the delta of another (`per_counter`) or over the
                          window's `ops`, `keys` or `seconds` (`per_window`),
                          times `scale`
    info_delta            summed deltas of INFO counters (`counters`)
    info_share            100 x first list's deltas over both lists' deltas
    client_value          a number the scenario took at the clients (`key`)
    trace_idle_share      100 x (1 - busy_s / window_s) of the traced slice
    trace_roofline_share  100 x (bytes the traced slice's merges need, by
                          the named function of bytes.py, over the device's
                          peak) over the device time of the modules whose
                          names start with one of `module_prefixes`
"""

from __future__ import annotations

import json
import os

import bytes as merge_bytes      # benchmark/bytes.py (shadows no builtin use)

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_of(device_kind: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        if rehearse:
            return {}
        raise ValueError(f"device {device_kind!r} is not in peaks.json "
                         f"(have {sorted(table)})")
    return table[device_kind]


def _delta(window: dict, counters: list):
    before, after = window["info_before"], window["info_after"]
    if any(c not in after for c in counters):
        return None
    return sum(float(after[c]) - float(before.get(c, 0)) for c in counters)


def info_ratio(spec, window, trace, peaks):
    num = _delta(window, spec["numerator"])
    if "per_counter" in spec:
        den = _delta(window, [spec["per_counter"]])
    else:
        den = window.get(spec["per_window"])
    if num is None or not den:
        return None
    return float(spec.get("scale", 1)) * num / den


def info_delta(spec, window, trace, peaks):
    return _delta(window, spec["counters"])


def info_share(spec, window, trace, peaks):
    part = _delta(window, spec["part"])
    rest = _delta(window, spec["rest"])
    if part is None or rest is None or part + rest <= 0:
        return None
    return 100.0 * part / (part + rest)


def client_value(spec, window, trace, peaks):
    return (window.get("client") or {}).get(spec["key"])


def trace_idle_share(spec, window, trace, peaks):
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def trace_roofline_share(spec, window, trace, peaks):
    if not trace or spec["peak"] not in peaks:
        return None
    prefixes = tuple(spec["module_prefixes"])
    seconds = sum(s for name, s in trace["modules"].items()
                  if name.startswith(prefixes))
    rows = window.get("trace_rows") or {}
    needed = getattr(merge_bytes, spec["bytes"])(rows)
    if seconds <= 0 or needed <= 0:
        return None
    return 100.0 * (needed / float(peaks[spec["peak"]])) / seconds


READERS = {f.__name__: f for f in (info_ratio, info_delta, info_share,
                                   client_value,
                                   trace_idle_share, trace_roofline_share)}


def read(spec: dict, window: dict, trace, peaks: dict):
    reader = spec["reader"]
    if reader not in READERS:
        raise ValueError(f"unknown reader {reader!r} (have "
                         f"{sorted(READERS)})")
    return READERS[reader](spec, window, trace, peaks)
