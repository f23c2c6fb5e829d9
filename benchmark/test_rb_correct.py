"""The tests the benchmark keeps of the redis-benchmark comparison
(reference_rb.py; `python -m pytest benchmark/test_rb_correct.py -q`: they
need no chip).

The control — the plain reference put in the program's place with the
configuration's guarantee broken (`stale-ack`) — has to come out as not
correct, and so has each planted fault (`drop-write`, on every write verb
or on SADD and SPOP alone, and `alter-answer`);
the unbroken reference has to come out correct, and so has the program off
the chip.  Each drives run.py end to end past its look for a chip
(`--rehearse --stand-in`), at the mix's 50 connections.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run_cell(*extra, seconds="3") -> tuple:
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "redis-benchmark-default", "--seed", "2147483777", "--seconds",
         seconds, "--trace", "0", "--rehearse", *extra], capture_output=True,
        text=True, timeout=600)
    line = json.loads(r.stdout.strip().splitlines()[-1]) if r.stdout.strip() \
        else None
    return r.returncode, line, r.stderr


def test_reference_in_the_programs_place_is_correct():
    rc, line, err = run_cell("--stand-in", "none")
    assert rc == 4 and line["correct"] is True, err[-2000:]
    assert all(v["value"] == 0 for v in line["compared"].values())


def test_control_stale_ack_is_not_correct():
    rc, line, err = run_cell("--stand-in", "stale-ack")
    assert rc == 4 and line["correct"] is False, err[-2000:]
    numbers = line["compared"]
    # each connection's last write never lands
    assert numbers["readback_wrong"]["value"] > 0


@pytest.mark.parametrize("fault", ["drop-write", "alter-answer"])
def test_planted_fault_is_not_correct(fault):
    rc, line, err = run_cell("--stand-in", fault)
    assert rc == 4 and line["correct"] is False, err[-2000:]
    numbers = line["compared"]
    assert numbers["reads_wrong"]["value"] + \
        numbers["readback_wrong"]["value"] > 0


def test_dropped_sadd_and_spop_are_not_correct():
    """SADD and SPOP alone dropped: the balance of the one member is what
    catches it, in the acknowledgements."""
    rc, line, err = run_cell("--stand-in", "drop-write:sadd,spop")
    assert rc == 4 and line["correct"] is False, err[-2000:]
    assert line["compared"]["acks_wrong"]["value"] > 0


def test_program_off_the_chip_is_correct_and_says_cpu():
    rc, line, err = run_cell()
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
