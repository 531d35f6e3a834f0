"""A cell whose entry is new is added as files only: a configuration, a
traffic mix, an entry module with its declarations (`spec.py`), a metric
reader, and additions to BENCHMARK.json.  The benchmark's own tests then
take the cell unedited, and a counter of the entry reaches its metric
reader.  An entry that lacks a declaration fails one case that names the
entry and what it lacks, and is no collection error.

The added cell's files are in `data/added_cell/`, laid out as under
`hkvbench/`; `additions.json` holds the entries appended to
BENCHMARK.json's lists, and under `append_workloads` the cell names
appended to a metric's `workloads`."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
import repro

from hkvbench import harness, spec
from hkvbench.tests import tiny

ADDED = Path(__file__).with_name("data") / "added_cell"
CELL = "A.probe.uniform"
DECLARED = ("setup", "window", "release", "resident", "check", "TINY",
            "FAULTS", "programs")


@pytest.mark.parametrize("name", tiny.cells())
def test_entry_declares_the_contract(name):
    ent = tiny.entry(name)
    missing = [attr for attr in DECLARED if not hasattr(ent, attr)]
    assert not missing, f"entry {ent.name!r} declares no {missing}"
    conf, traffic = ent.TINY
    assert isinstance(conf, dict) and isinstance(traffic, dict)
    assert "control" in ent.FAULTS
    assert set(ent.FAULTS) <= set(harness.FAULTS)


def test_a_missing_declaration_names_the_entry():
    ent = spec.entry("find")
    with pytest.raises(spec.EntryError,
                       match="entry 'find' declares no nothing_here"):
        ent.nothing_here
    assert ent.counters(None, None, None) == {}


def _only_added(old, new) -> bool:
    """Whether `new` is `old` with entries appended to its lists."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and set(old) == set(new)
                and all(_only_added(old[k], new[k]) for k in old))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(_only_added(a, b) for a, b in zip(old, new)))
    return old == new


def _added_copy(root: Path, drop: str | None = None) -> Path:
    """The benchmark with the added cell: its files copied in beside the
    benchmark's, none replaced, and BENCHMARK.json's lists appended to.
    `drop` names a declaration left out of the new entry."""
    shutil.copytree(spec.HERE, root / "hkvbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src in sorted(ADDED.rglob("*")):
        if not src.is_file() or src.name == "additions.json":
            continue
        dst = root / "hkvbench" / src.relative_to(ADDED)
        assert not dst.exists(), f"{dst} would replace a file"
        dst.parent.mkdir(parents=True, exist_ok=True)
        text = src.read_text()
        if drop is not None and dst.parent.name == "entries":
            text, n = re.subn(rf"^{drop} = .*\n", "", text, flags=re.M)
            assert n == 1, drop
        dst.write_text(text)
    old = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench = json.loads(json.dumps(old))
    add = json.loads((ADDED / "additions.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += add[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.get("workloads", []).extend(add["append_workloads"].get(m["name"],
                                                                  ()))
    assert _only_added(old, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def _python(root: Path, *args: str) -> subprocess.CompletedProcess:
    """`python <args>` in the copy, on the CPU, importing its `hkvbench`
    and the program this process imports."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        str(Path(p).parent) for p in repro.__path__))
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def _pytest(root: Path, *args: str) -> tuple[str, str]:
    """Pytest's summary line and its whole output, for the probe's cases."""
    p = _python(root, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                "-k", "probe", *args)
    out = p.stdout + p.stderr
    return p.stdout.strip().splitlines()[-1], out[-4000:]


def test_a_cell_with_a_new_entry_is_added_as_files(tmp_path):
    root = _added_copy(tmp_path)
    summary, out = _pytest(root, "hkvbench/tests/test_hkvbench_cells.py",
                           "hkvbench/tests/test_hkvbench_control.py",
                           "hkvbench/tests/test_hkvbench_layers.py",
                           "hkvbench/tests/test_hkvbench_entry_contract.py")
    # two cell cases, three control cases, the program list, the contract
    assert re.match(r"7 passed, \d+ deselected\b", summary), out
    assert "failed" not in summary and "error" not in summary, out
    p = _python(root, "-c", "import json; from hkvbench.tests import tiny; "
                f"print(json.dumps(tiny.measure({CELL!r}, trace=True)))")
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    batch = spec.load_module(ADDED / "entries" / "probe.py").TINY[1]["batch"]
    assert line["metrics"]["probe.lanes_found"] == {
        "value": float(line["attempted"] * batch), "unit": "lanes"}


def test_an_entry_without_faults_fails_one_case(tmp_path):
    root = _added_copy(tmp_path, drop="FAULTS")
    summary, out = _pytest(root, "hkvbench/tests/test_hkvbench_control.py")
    assert re.match(r"1 failed, 11 deselected\b", summary), out
    assert "error" not in summary, out
    assert "entry 'probe' declares no FAULTS" in out, out
