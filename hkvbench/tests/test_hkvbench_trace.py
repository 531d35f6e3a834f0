"""The trace reduction reads a recorded one-chip trace (a 3 s window of
A.find.uniform on a TPU v5e) to the same numbers every time."""

import gzip
import shutil
from pathlib import Path

import pytest

from hkvbench import trace

DATA = Path(__file__).with_name("data") / "find_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "find.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce_trace(path, wall_s=99.0)


def test_busy_and_window(summary):
    assert summary.chips == 1
    assert summary.busy_s == pytest.approx(3.024116042, abs=1e-9)
    assert summary.window_s == pytest.approx(3.033095763, abs=1e-9)


def test_kernel_time_by_stable_name(summary):
    assert summary.op_s["hkv_find_scan_pipeline"] == pytest.approx(
        2.891429978, abs=1e-9)
    assert "while" not in summary.op_s       # loops hold the kernel calls
    assert sum(summary.op_s.values()) <= summary.busy_s + 1e-9


def test_gaps_named_by_host_spans(summary):
    assert summary.gaps[0][0] == "hkv.block"
    assert summary.gaps[0][1] == pytest.approx(0.002262398, abs=1e-9)
    assert [g[1] for g in summary.gaps] == sorted(
        (g[1] for g in summary.gaps), reverse=True)


def test_union_and_gaps_helpers():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
