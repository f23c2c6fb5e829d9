#!/usr/bin/env python3
"""Lay the twenty-four stage-clock metrics over a SCRATCH copy of the tree:
their files into <tree>/benchmark/layers/, their `per_layer` entries at
the end of <tree>/BENCHMARK.json (README.md beside this file says why
they are not in the checkout's own manifest yet).

    python3 docs/stage_layers/overlay.py <tree>
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRY_KEYS = ("name", "unit", "better", "source", "layer", "moves",
              "workloads")


def overlay(tree: str) -> list:
    """-> the names added."""
    manifest_path = os.path.join(tree, "BENCHMARK.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    have = {m["name"] for m in manifest["per_layer"]}
    added = []
    for path in sorted(glob.glob(os.path.join(HERE, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["name"] in have:
            continue
        shutil.copy(path, os.path.join(tree, "benchmark", "layers"))
        manifest["per_layer"].append({k: spec[k] for k in ENTRY_KEYS})
        added.append(spec["name"])
    with open(manifest_path, "w") as f:
        json.dump(manifest, f, indent=1)
        f.write("\n")
    return added


if __name__ == "__main__":
    print("\n".join(overlay(sys.argv[1])))
