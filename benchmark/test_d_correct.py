"""The tests the benchmark keeps of workload D's comparison (reference_d.py;
`python -m pytest benchmark/test_d_correct.py -q`: they need no chip; run
them serially — the runs share `benchmark/.work/ycsb-d`).

The unbroken reference put in the program's place has to come out
correct; the control (`stale-ack`: an insert acknowledged now, applied at
its connection's next insert) and each planted fault — an acknowledged
insert dropped (`drop-insert`), 9 of a record's 10 fields answered
(`partial`) — have to come out not correct, and so does nothing else than
the number that names the fault; the program off the chip comes out
correct.  Each drives run.py end to end past its look for a chip
(`--rehearse --stand-in`), at the mix's 8 connections.
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
         "ycsb-d", "--seed", "2147483777", "--seconds", seconds, "--trace",
         "0", "--rehearse", *extra], capture_output=True, text=True,
        timeout=600)
    line = json.loads(r.stdout.strip().splitlines()[-1]) if r.stdout.strip() \
        else None
    return r.returncode, line, r.stderr


def test_reference_in_the_programs_place_is_correct():
    rc, line, err = run_cell("--stand-in", "none")
    assert rc == 4 and line["correct"] is True, err[-2000:]
    assert all(v["value"] == 0 for v in line["compared"].values())


@pytest.mark.parametrize("fault, number", [
    ("stale-ack", "readback_wrong"), ("drop-insert", "readback_wrong"),
    ("partial", "reads_partial")])
def test_control_and_planted_faults_are_not_correct(fault, number):
    rc, line, err = run_cell("--stand-in", fault)
    assert rc == 4 and line["correct"] is False, err[-2000:]
    assert line["compared"][number]["value"] > 0


def test_program_off_the_chip_is_correct_and_says_cpu():
    rc, line, err = run_cell()
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
