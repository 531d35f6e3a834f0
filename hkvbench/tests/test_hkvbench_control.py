"""The check of each cell fails its control (the reference in bfloat16 in
the program's place) and every fault its entry declares (`FAULTS`),
planted in the timed path."""

import pytest

from hkvbench import spec
from hkvbench.tests import tiny


def _cases():
    for name in tiny.cells():
        try:
            faults = tiny.entry(name).FAULTS
        except spec.EntryError as e:
            # one case, which fails naming the entry and what it lacks
            yield pytest.param(name, e, id=f"{name}-{e.__class__.__name__}")
            continue
        for fault in faults:
            yield name, fault


@pytest.mark.parametrize("name,fault", list(_cases()))
def test_fault_is_not_correct(name, fault):
    if isinstance(fault, spec.EntryError):
        raise fault
    line = tiny.measure(name, fault=fault)
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
