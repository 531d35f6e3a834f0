"""Every cell runs end to end at a tiny size on the CPU and comes out
correct, and its result line has the contract's shape."""

import pytest

from hkvbench.tests import tiny


@pytest.mark.parametrize("name", tiny.cells())
def test_cell_runs_and_is_correct(name):
    line = tiny.measure(name)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "checks"
    cell = tiny.cell(name)
    names = {m.name for m in cell.end_to_end}
    assert {"setup_s", "hbm_bytes_per_key"} <= names
    assert set(line["metrics"]) <= names
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


@pytest.mark.parametrize("name", tiny.cells())
def test_cell_is_correct_on_a_second_seed(name):
    line = tiny.measure(name, seed=12345)
    assert line["correct"], line["checks"]
