"""chip_smoke.py's own logic, off the chip: at toy size under
`--engine cpu` it passes, and with one deliberately wrong expectation
it exits non-zero — so the smoke cannot pass vacuously.  (What it proves
about the device it proves only on the device: `python chip_smoke.py`
through the chip tool.)"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = ["--engine", "cpu", "--keys", "2000", "--ops", "400", "--sample", "200"]


def _smoke(*extra):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *TOY, *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.slow  # ~10 s of real server boots (scripts/audit_markers.sh)
def test_toy_smoke_passes_and_says_it_proved_nothing():
    r = _smoke()
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["proved_nothing_about_the_device"] is True
    assert "proves nothing about the device" in r.stdout
    assert "C == P on" in r.stdout


@pytest.mark.slow
def test_a_wrong_expectation_fails_the_smoke():
    r = _smoke("--corrupt-expectation")
    assert r.returncode != 0
    assert "FAILED" in r.stderr and "expected" in r.stderr
    assert '"ok"' not in r.stdout


def test_without_an_accelerator_nothing_pretends(tmp_path):
    """The two refusals the default run rests on, each cheap to show:
    a server asked for `--engine tpu` on a CPU backend does not boot
    (so the smoke's C never listens and the run fails), and on the chip
    path the smoke refuses a keyspace no user would call real before
    it starts anything.  Neither prints a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "constdb_tpu.bin.server", "--engine", "tpu",
         "--port", "0", "--work-dir", str(tmp_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert "requires an accelerator" in r.stderr
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--keys", "2000"], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
