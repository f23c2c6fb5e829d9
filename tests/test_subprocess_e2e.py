"""Black-box cluster smoke: REAL server subprocesses booted from TOML
configs, driven by the shipped test binary over TCP, then a restart that
must warm-boot from the final snapshot.

This is the reference's integration strategy run end-to-end against our
actual binaries (reference bin/test.rs:95-116 spawns servers the same
way), guarding the whole boot → serve → replicate → dump → restore loop.
"""

import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _resp(port, *parts, retries=60):
    for _ in range(retries):
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=2)
            break
        except OSError:
            time.sleep(0.25)
    else:
        raise RuntimeError(f"cannot connect :{port}")
    req = b"*%d\r\n" % len(parts) + b"".join(
        b"$%d\r\n%s\r\n" % (len(p), p) for p in
        (x if isinstance(x, bytes) else str(x).encode() for x in parts))
    s.sendall(req)
    time.sleep(0.15)
    out = s.recv(1 << 16)
    s.close()
    return out


@pytest.mark.slow  # ~6s of real process spawns: over the tier-1 per-test
# budget (scripts/audit_markers.sh); still runs in unfiltered invocations
def test_three_node_cluster_from_toml(tmp_path):
    ports = [_free_port() for _ in range(3)]
    procs = []
    try:
        for i, port in enumerate(ports):
            wd = tmp_path / f"n{i + 1}"
            wd.mkdir()
            cfgp = tmp_path / f"n{i + 1}.toml"
            cfgp.write_text(
                f'node_id = {i + 1}\n'
                f'node_alias = "n{i + 1}"\n'
                f'ip = "127.0.0.1"\n'
                f'port = {port}\n'
                f'work_dir = "{wd}"\n'
                f'engine = "cpu"\n'
                f'snapshot_path = "{wd}/boot.snapshot"\n'
                f'replica_heartbeat_frequency = 1\n'
                f'replica_gossip_frequency = 2\n'
                f'log_level = "info"\n')
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "constdb_tpu.bin.server", str(cfgp)],
                cwd=REPO, env=_env(),
                stdout=open(tmp_path / f"n{i + 1}.log", "ab"),
                stderr=subprocess.STDOUT))
        for port in ports:
            assert b"PONG" in _resp(port, b"ping") or True  # wait until up

        # the shipped black-box harness forms the mesh and asserts
        # convergence with its oracle model
        run = subprocess.run(
            [sys.executable, "-m", "constdb_tpu.bin.test", "--replicas",
             *[f"127.0.0.1:{p}" for p in ports], "--ops", "120"],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=120)
        assert run.returncode == 0, run.stdout + run.stderr

        # a marker write, then restart node 3: SIGTERM dumps, boot restores
        assert b"OK" in _resp(ports[0], b"set", b"marker", b"v1")
        deadline = time.time() + 20
        while b"v1" not in _resp(ports[2], b"get", b"marker"):
            assert time.time() < deadline, "marker did not replicate"
            time.sleep(0.3)
        procs[2].send_signal(signal.SIGTERM)
        procs[2].wait(timeout=20)
        assert os.path.exists(tmp_path / "n3" / "boot.snapshot")
        procs[2] = subprocess.Popen(
            [sys.executable, "-m", "constdb_tpu.bin.server",
             str(tmp_path / "n3.toml")],
            cwd=REPO, env=_env(),
            stdout=open(tmp_path / "n3.log", "ab"),
            stderr=subprocess.STDOUT)
        assert b"v1" in _resp(ports[2], b"get", b"marker"), \
            "warm boot lost the marker"

        # the mesh reconverges: a write on n1 reaches the restarted n3
        assert b"OK" in _resp(ports[0], b"set", b"post", b"v2")
        deadline = time.time() + 30
        while b"v2" not in _resp(ports[2], b"get", b"post"):
            assert time.time() < deadline, "restarted node never reconverged"
            time.sleep(0.4)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
