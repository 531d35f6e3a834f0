"""A run finds its chip or fails: no TPU and an unknown device kind are
errors, never a fall-back; a checkout with only the benchmark's own files
prints no result."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from hkvbench import device, spec


def test_unknown_device_kind_fails():
    with pytest.raises(KeyError):
        device.peaks("TPU v0 imaginary")


def test_known_kind_has_a_source():
    for kind, p in device.PEAKS.items():
        assert p["hbm_bytes_per_s"] > 0 and p["source"], kind


def test_require_fails_without_a_tpu():
    with pytest.raises(device.NoChip):
        device.require(1)


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "hkvbench/run.py", "--workload", "A.find.uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "hkvbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
