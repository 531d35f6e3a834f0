"""What a traced run reads from its window, on recorded TPU v5e traces:
the layer shares and every other per-layer metric of the ingest cell as
the harness reads them, and the idle time by host span.

`ingest_layers.*` is one A.ingest.new batch with the compiled texts of
its three programs (`test_hkvbench_layers.py`); `find_trace` a 3 s
A.find.uniform window (`test_hkvbench_trace.py`)."""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from hkvbench import device, harness, layers, spec, trace

DATA = Path(__file__).with_name("data")
CELL = "A.ingest.new"


def _unpacked(tmp_path_factory, name: str) -> Path:
    path = tmp_path_factory.mktemp("readings") / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def ingest(tmp_path_factory):
    path = _unpacked(tmp_path_factory, "ingest_layers")
    with gzip.open(DATA / "ingest_layers.modules.json.gz", "rt") as f:
        texts = json.load(f)
    return path, layers.reduce(path, texts)


@pytest.fixture(scope="module")
def line(ingest):
    """The per-layer metrics of the recorded batch, read as a traced run
    of the cell reads them (one batch of 2^20 keys)."""
    path, split = ingest
    cell = spec.load(CELL)
    run = harness.Run(cell, 1, 10.0, None, [])
    win = harness.Window(t_open=0.0, t_close=1.0, attempted=1, failed=0,
                         keys=int(cell.traffic["batch"]))
    ctx = harness.MetricCtx(run, win, 0.0, 0, 1, trace.reduce_trace(path, 0.0),
                            device.peaks("TPU v5 lite"), {}, split)
    return harness.read_metrics(cell.per_layer, ctx)


def test_every_per_layer_metric_of_the_cell_reports(line):
    assert set(line) == {m.name for m in spec.load(CELL).per_layer}
    for m in line.values():
        assert isinstance(m["value"], float) and m["unit"] == "%"


def test_layer_shares_are_the_reductions(ingest, line):
    _, split = ingest
    shares = split.shares()
    assert set(shares) == set(layers.SHARES)
    for name, value in shares.items():
        assert line[name]["value"] == value, name
    assert line["closure.self_share"]["value"] == pytest.approx(
        7.941319233, abs=1e-8)


def test_layer_shares_and_kernels_make_up_busy_time(ingest, line):
    _, split = ingest
    kernels = 100.0 * split.seconds["kernels"] / split.busy_s
    total = kernels + sum(line[n]["value"] for n in layers.SHARES)
    # every op on one layer; the rest of busy time lies in loop containers
    assert total == pytest.approx(100.0 * split.op_s / split.busy_s,
                                  abs=1e-9)
    assert total == pytest.approx(100.0, abs=0.01)


def test_an_untraced_window_reads_no_layer_share():
    run = harness.Run(spec.load(CELL), 1, 10.0, None, [])
    win = harness.Window(0.0, 1.0, 1, 0, 1)
    ctx = harness.MetricCtx(run, win, 0.0, 0, 1, None, {}, {}, None)
    assert harness.read_metrics(run.cell.per_layer, ctx) == {}


@pytest.mark.parametrize("name", ["ingest_layers", "find_trace"])
def test_idle_by_span_is_the_idle_window(tmp_path_factory, name):
    s = trace.reduce_trace(_unpacked(tmp_path_factory, name), 0.0)
    assert s.chips == 1
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-9)
    # the line's gaps are the longest ten of the same stretches
    assert len(s.gaps) == 10
    for span, seconds in s.gaps:
        assert seconds <= s.idle_by_span[span] + 1e-12
    assert set(s.idle_by_span) >= {"hkv.block"}
