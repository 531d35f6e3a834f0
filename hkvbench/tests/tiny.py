"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, and the
harness driven for them without its look for a chip."""

from __future__ import annotations

import dataclasses
import time

import jax

from hkvbench import harness, spec

PEAKS = {"hbm_bytes_per_s": 1e10}


def cell(name: str) -> spec.Cell:
    """Cell `name` at its entry's `TINY` sizes."""
    c = spec.load(name)
    conf, traffic = spec.entry(c.traffic["entry"]).TINY
    return dataclasses.replace(c, config=dict(c.config, **conf),
                               traffic=dict(c.traffic, **traffic))


def measure(name: str, *, seed: int = 2**31 + 5, seconds: float = 0.2,
            fault=None, trace: bool = False) -> dict:
    run = harness.Run(cell(name), seed, seconds, fault, jax.devices()[:1])
    return harness.measure(run, trace=trace, t_start=time.perf_counter(),
                           age0=0.0, peaks=PEAKS)


def entry(name: str) -> spec.Entry:
    return spec.entry(spec.load(name).traffic["entry"])


def cells() -> list:
    import json

    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]
