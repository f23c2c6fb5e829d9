#!/usr/bin/env python3
"""The launcher of every node the benchmark boots.

    python benchmark/server_proc.py <control.sock> [--rehearse] -- <server argv>

Starts a control thread on a UNIX socket, then calls the program's own
`constdb_tpu.bin.server.main(argv)` unchanged.  Only the process that
holds the chip can trace it or read its memory, and the parent (run.py)
must stay off JAX — so these verbs run here, one line in, one line out:

    trace-start <dir>   jax.profiler.start_trace (Python tracer off)
    trace-stop          jax.profiler.stop_trace
    device              {"platform", "kind", "count", "memory_peak_bytes"}

`--rehearse` (a CPU run of `--engine cpu`, which never touches JAX) makes
trace-stop run one tiny jitted op first, so that a rehearsal's trace has
something for trace_reduce.py to find.  It proves nothing about the chip.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device() -> dict:
    import jax
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use",
                                    ms.get("bytes_in_use", 0))))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _trace_start(path: str) -> dict:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # millions of events otherwise
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)
    return {"ok": True}


def _trace_stop(rehearse: bool) -> dict:
    import jax
    if rehearse:
        import jax.numpy as jnp
        jax.jit(lambda x: (x * 2).sum(), inline=False)(
            jnp.ones((256, 256))).block_until_ready()
    jax.profiler.stop_trace()
    return {"ok": True}


def _handle(line: str, rehearse: bool) -> dict:
    verb, _, arg = line.strip().partition(" ")
    if verb == "device":
        return _device()
    if verb == "trace-start":
        return _trace_start(arg)
    if verb == "trace-stop":
        return _trace_stop(rehearse)
    return {"error": f"unknown verb {verb!r}"}


def _control(srv: socket.socket, rehearse: bool) -> None:
    while True:
        conn, _ = srv.accept()
        with conn:
            try:
                line = conn.makefile("r").readline()
                reply = _handle(line, rehearse)
            except Exception as e:   # boundary: report, keep serving
                reply = {"error": f"{type(e).__name__}: {e}"}
            conn.sendall(json.dumps(reply).encode() + b"\n")


def main(argv: list) -> None:
    sock_path = argv[0]
    rest = argv[1:]
    rehearse = rest[0] == "--rehearse"
    if rehearse:
        rest = rest[1:]
    if rest[0] != "--":
        raise SystemExit("usage: server_proc.py <sock> [--rehearse] -- ...")
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    # bound by its base name from its directory: a UNIX socket path holds
    # 107 bytes, a checkout's path may be longer
    cwd = os.getcwd()
    os.chdir(os.path.dirname(sock_path))
    if os.path.exists(os.path.basename(sock_path)):
        os.unlink(os.path.basename(sock_path))   # an earlier node's
    srv.bind(os.path.basename(sock_path))
    os.chdir(cwd)
    srv.listen(4)
    threading.Thread(target=_control, args=(srv, rehearse),
                     daemon=True).start()
    sys.path.insert(0, ROOT)
    from constdb_tpu.bin.server import main as server_main
    server_main(rest[1:])


if __name__ == "__main__":
    main(sys.argv[1:])
