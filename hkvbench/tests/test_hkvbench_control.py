"""The check of each cell fails its control (the reference in bfloat16 in
the program's place) and every fault the cell can have, planted in the
timed path."""

import pytest

from hkvbench.tests import tiny

FAULTS = {
    "find": ("control", "half_batch", "altered_answer"),
    "train": ("control", "half_batch", "altered_answer", "unchanged_state"),
    "ingest": ("control", "half_batch", "altered_answer", "unchanged_state"),
}

CASES = [(name, fault) for name in tiny.cells()
         for fault in FAULTS[tiny.cell(name).traffic["entry"]]]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(name, fault):
    line = tiny.measure(name, fault=fault)
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
