"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, and the
harness driven for them without its look for a chip."""

from __future__ import annotations

import dataclasses
import time

import jax

from hkvbench import harness, spec

# per traffic entry: the configuration and traffic keys a CPU run shrinks
TINY = {
    "find": ({"capacity": 8 * 128}, {"batch": 256}),
    "train": ({"capacity": 16 * 128},
              {"lookups_per_step": 1024, "id_space": 8192,
               "window_lanes": 256}),
    "ingest": ({"capacity": 8 * 128}, {"batch": 1 << 12}),
}

PEAKS = {"hbm_bytes_per_s": 1e10}


def cell(name: str) -> spec.Cell:
    c = spec.load(name)
    conf, traffic = TINY[c.traffic["entry"]]
    return dataclasses.replace(c, config=dict(c.config, **conf),
                               traffic=dict(c.traffic, **traffic))


def measure(name: str, *, seed: int = 2**31 + 5, seconds: float = 0.2,
            fault=None, trace: bool = False) -> dict:
    run = harness.Run(cell(name), seed, seconds, fault, jax.devices()[:1])
    return harness.measure(run, trace=trace, t_start=time.perf_counter(),
                           age0=0.0, peaks=PEAKS)


def cells() -> list:
    import json

    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]
