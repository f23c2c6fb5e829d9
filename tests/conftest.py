"""Test harness configuration.

Tests run on the virtual 8-device CPU platform by default
(`JAX_PLATFORMS=cpu`, `--xla_force_host_platform_device_count=8`, Pallas
kernels in interpret mode), so CRDT semantics, the merge engines, and the
multi-chip sharding (parallel/) are exercised fast and without TPU
hardware.  Set CONSTDB_TEST_TPU=1 to run against the real chip instead
(through the chip tool: one pytest process holds the chip).
"""

import gc
import os
import sys

import pytest

if not os.environ.get("CONSTDB_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "true")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("CONSTDB_TEST_TPU"):
    # on-chip runs pay seconds per kernel compile; the persistent cache
    # makes a second run of the suite in the same call tractable
    from constdb_tpu.conf import enable_compile_cache
    enable_compile_cache()

# Build native/ once per session, HERE — at conftest import, before any
# test module's collection-time skipif looks for the extension — so a
# fresh checkout (the .so files are gitignored) runs the same tier-1 as
# a built tree instead of skipping every native test.
if not os.environ.get("CONSTDB_NO_NATIVE"):
    from constdb_tpu.utils.native_tables import build_native
    try:
        build_native()
    except RuntimeError as e:
        print(f"\nconftest: NATIVE BUILD FAILED — every native-tier test "
              f"will SKIP:\n{e}\n", file=sys.stderr)


# ------------------------------------------------------------ marker audit
# Tier-1 filters `-m 'not slow'`, so a long test that FORGOT the marker
# silently bloats the tier-1 wall until the timeout bites.  scripts/
# audit_markers.sh runs the suite with CONSTDB_MARKER_AUDIT=<report path>:
# every test whose call phase exceeds CONSTDB_MARKER_AUDIT_BUDGET seconds
# (default 5) WITHOUT a `slow` marker lands in the report file, and the
# script fails when it is non-empty.  Inert unless the env var is set.
_AUDIT_PATH = os.environ.get("CONSTDB_MARKER_AUDIT")
if _AUDIT_PATH:
    _AUDIT_BUDGET = float(os.environ.get("CONSTDB_MARKER_AUDIT_BUDGET", "5"))
    _audit_offenders = []

    def pytest_runtest_logreport(report):
        if report.when == "call" and report.duration > _AUDIT_BUDGET \
                and "slow" not in report.keywords:
            _audit_offenders.append(
                f"{report.nodeid} {report.duration:.1f}s")

    def pytest_sessionfinish(session, exitstatus):
        with open(_AUDIT_PATH, "w") as f:
            for line in _audit_offenders:
                f.write(line + "\n")


CPU_MESH_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "JAX_ENABLE_X64": "true",
    "CONSTDB_MESH_RERUN": "1",  # recursion guard for subprocess re-runs
}


def cpu_mesh_subprocess_env() -> dict:
    """Environment for re-running a test module on the virtual CPU mesh."""
    env = dict(os.environ)
    env.update(CPU_MESH_ENV)
    return env


@pytest.fixture(autouse=True)
def _thawed_heap():
    """A node freezes its process's heap once its table has landed
    (utils/stagetime.py StageClock.freeze_heap).  One test process hosts
    many nodes, so every test leaves the heap thawed: what it dropped is
    collected, and no later test inherits its frozen objects."""
    yield
    gc.unfreeze()
