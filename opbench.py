#!/usr/bin/env python
"""One-shot reproducible op-path benchmark: regenerates OPBENCH.md.

Boots a fresh single node as a subprocess, runs the shipped pipelined
GET/SET/INCR workload (constdb_tpu/bin/test.py bench_ops) with a warmup
pass and reports the MEDIAN of N timed runs per op — the build machines
run concurrent load, so medians are the honest capacity estimate the
round-4 "best of 3 by hand" numbers were not.

    python opbench.py [--requests 200000] [--runs 3] [--pipeline 64]
                      [--conns 4] [--no-native] [--update]

`--update` rewrites OPBENCH.md with the measured table; without it the
table only prints.  `--no-native` strips the C extension from the server
AND client (CONSTDB_NO_NATIVE=1) to measure the pure-Python floor.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import socket
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_port(port: int, timeout: float = 20.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            return
        except OSError:
            time.sleep(0.1)
    raise SystemExit(f"server on port {port} never came up")


def _reset_native_caches() -> None:
    """The native tiers cache their load decision process-wide; A/B passes
    in one process must re-evaluate CONSTDB_NO_NATIVE — otherwise the
    'pure' client pass keeps using the C parser/encoder primed by the
    native pass and the published floor is contaminated."""
    from constdb_tpu.resp import codec
    from constdb_tpu.utils import native_tables
    codec._EXT_CACHE.clear()
    codec._ENC_CACHE.clear()
    native_tables._ext = None


def run(requests: int, runs: int, pipeline: int, conns: int,
        native: bool) -> dict[str, int]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("CONSTDB_NO_NATIVE", None)
    os.environ.pop("CONSTDB_NO_NATIVE", None)
    if not native:
        env["CONSTDB_NO_NATIVE"] = "1"
        os.environ["CONSTDB_NO_NATIVE"] = "1"
    _reset_native_caches()  # the CLIENT side honors the flag too
    port = _free_port()
    srv = subprocess.Popen(
        [sys.executable, "-m", "constdb_tpu.bin.server", "--port", str(port),
         "--node-id", "1", "--engine", "cpu", "--work-dir", "/tmp",
         "--log-level", "warning"],
        env=env, stderr=subprocess.DEVNULL)
    try:
        _wait_port(port)
        from constdb_tpu.bin.test import bench_ops

        addr = f"127.0.0.1:{port}"
        # warmup: primes allocator, code paths, and the key working set
        asyncio.run(bench_ops(addr, max(10_000, requests // 10),
                              pipeline, conns))
        samples: dict[str, list[int]] = {}
        for _ in range(runs):
            got = asyncio.run(bench_ops(addr, requests, pipeline, conns))
            for op, rate in got.items():
                samples.setdefault(op, []).append(rate)
        return {op: int(statistics.median(v)) for op, v in samples.items()}
    finally:
        srv.send_signal(signal.SIGTERM)
        try:
            srv.wait(10)
        except subprocess.TimeoutExpired:
            srv.kill()


TEMPLATE = """# Op-path throughput (client command path)

Regenerate this file with the committed one-shot harness (fixed workload,
warmup pass, median of {runs} runs — see opbench.py):

```
python opbench.py --requests {requests} --runs {runs} --update
```

Measured against a live single node (CPU engine, one asyncio loop) with
the native C RESP parser + encoder on both the server and client side
(native/resp.cpp; interned small-int replies mirror reference
src/resp.rs:12-27):

| op   | requests | pipeline | conns | ops/sec (median of {runs}) |
|------|----------|----------|-------|----------------------------|
| SET  | {requests:,} | {pipeline} | {conns} | {set:,} |
| GET  | {requests:,} | {pipeline} | {conns} | {get:,} |
| INCR | {requests:,} | {pipeline} | {conns} | {incr:,} |

Pure-Python floor on the same machine/run (CONSTDB_NO_NATIVE=1 strips the
extension from server and client):

| op   | ops/sec (median of {runs}) |
|------|----------------------------|
| SET  | {pset:,} |
| GET  | {pget:,} |
| INCR | {pincr:,} |

Where the remaining time goes (cProfile under this load): with parse and
encode in C, the floor is the command dispatch + asyncio socket plumbing
on the single exec loop — the deliberate single-writer trade documented
in SURVEY.md (the reference spends extra cores on parse threads,
reference README.md:12, src/lib.rs:138-142; this build spends C).
Re-check the profile claim with `python opbench.py --profile`.  Encoder
wire bytes are differentially fuzzed against the pure encoder in
tests/test_native_resp.py.

Update this file whenever the op path changes materially.
"""


async def _profile(requests: int, pipeline: int, conns: int) -> None:
    """Server + client in one process under cProfile: shows WHERE the op
    path spends its time (the evidence behind OPBENCH.md's dispatch-floor
    claim)."""
    import cProfile
    import pstats

    from constdb_tpu.bin.test import bench_ops
    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node

    app = await start_node(Node(node_id=1), host="127.0.0.1", port=0,
                           work_dir="/tmp")
    prof = cProfile.Profile()
    prof.enable()
    await bench_ops(app.advertised_addr, requests, pipeline, conns)
    prof.disable()
    await app.close()
    pstats.Stats(prof).sort_stats("tottime").print_stats(16)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200_000)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--pipeline", type=int, default=64)
    ap.add_argument("--conns", type=int, default=4)
    ap.add_argument("--no-native", action="store_true")
    ap.add_argument("--update", action="store_true",
                    help="rewrite OPBENCH.md (runs native AND pure passes)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile the server under load (in-process) and "
                         "print the top self-time entries")
    ns = ap.parse_args()

    if ns.profile:
        asyncio.run(_profile(ns.requests, ns.pipeline, ns.conns))
        return

    if ns.update:
        print("== native (parser + encoder in C) ==")
        nat = run(ns.requests, ns.runs, ns.pipeline, ns.conns, native=True)
        print("== pure python ==")
        pure = run(ns.requests, ns.runs, ns.pipeline, ns.conns, native=False)
        out = TEMPLATE.format(requests=ns.requests, runs=ns.runs,
                              pipeline=ns.pipeline, conns=ns.conns,
                              set=nat["set"], get=nat["get"],
                              incr=nat["incr"], pset=pure["set"],
                              pget=pure["get"], pincr=pure["incr"])
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "OPBENCH.md")
        with open(path, "w") as f:
            f.write(out)
        print(f"wrote {path}")
        for op in ("set", "get", "incr"):
            print(f"  {op:5s}: native {nat[op]:,}  pure {pure[op]:,}  "
                  f"({nat[op] / max(pure[op], 1):.2f}x)")
    else:
        res = run(ns.requests, ns.runs, ns.pipeline, ns.conns,
                  native=not ns.no_native)
        for op, rate in res.items():
            print(f"  {op:5s}: {rate:,} ops/sec (median of {ns.runs})")


if __name__ == "__main__":
    main()
