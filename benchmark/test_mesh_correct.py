"""The tests the benchmark keeps of the mesh cells' comparison (run by
hand: `python -m pytest benchmark/test_mesh_correct.py -q`; they need no
chip and are not part of the repo's tier-1 suite), as test_correct.py
keeps the served cells'.

The unbroken reference served as three forwarding processes
(fake_mesh.py) has to come out correct; the control — a replica that
takes a peer's write by arrival, not by stamp — has to leave the nodes
apart (`converge_wrong` > 0); each planted fault has to come out as not
correct; and the program itself, off the chip, correct with no full
sync.  Each drives run.py end to end past its look for a chip
(`--rehearse --stand-in`).
"""

import pytest

from test_correct import run_cell

CELL = "aa-3node-ycsb-a"


def numbers(line: dict) -> dict:
    return {k: v["value"] for k, v in line["compared"].items()}


def test_reference_as_three_replicas_is_correct():
    rc, line, err = run_cell("--stand-in", "none", workload=CELL,
                             seconds="4")
    assert rc == 4 and line["correct"] is True, err[-2000:]
    assert set(numbers(line).values()) == {0}


def test_control_arrival_wins_leaves_the_nodes_apart():
    rc, line, err = run_cell("--stand-in", "arrival-wins", workload=CELL,
                             seconds="5")
    assert rc == 4 and line["correct"] is False, err[-2000:]
    assert numbers(line)["converge_wrong"] > 0


@pytest.mark.parametrize("fault", ["drop-replicated", "stale-ack"])
def test_planted_fault_is_not_correct(fault):
    rc, line, err = run_cell("--stand-in", fault, workload=CELL,
                             seconds="5")
    assert rc == 4 and line["correct"] is False, err[-2000:]
    got = numbers(line)
    assert got["reads_wrong"] + got["readback_wrong"] + \
        got["converge_wrong"] > 0
    assert got["not_quiesced"] == 0 and got["never_answered"] == 0


def test_program_off_the_chip_is_correct_and_never_full_syncs():
    rc, line, err = run_cell(workload=CELL, seconds="4")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["device"]["platform"] == "cpu"
    assert numbers(line)["full_syncs"] == 0
