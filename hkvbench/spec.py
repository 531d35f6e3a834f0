"""What one cell is, read from `BENCHMARK.json` and the files it names.

A cell names a configuration (`configs/<name>.json`, found through the
configuration's `file`) and a traffic mix (`traffic/<name>.json`).  The
mix's `entry` field names the entry module in `entries/<entry>.py`; every metric
named in `BENCHMARK.json` is read by `metrics/<name>.py`.  Adding a cell,
a mix or a metric therefore adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None       # per-layer metrics only
    moves: str | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "hkvbench._by_path." + "_".join(
        path.relative_to(HERE).with_suffix("").parts).replace(".", "_") \
        .replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    metric = lambda m: Metric(m["name"], m["unit"], m["better"], m["source"],
                              m.get("layer"), m.get("moves"))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(metric(m) for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(metric(m) for m in bench["per_layer"]
                        if _applies(m, workload)),
    )


def reader(name: str):
    """`read(ctx)` of metric `name` (metrics/<name>.py)."""
    return load_module(HERE / "metrics" / f"{name}.py").read


def entry(name: str):
    return load_module(HERE / "entries" / f"{name}.py")


def byte_model(op: str):
    return load_module(HERE / "bytes" / f"{op}.py")
