"""One run of one cell: set up, measure a window, check, print one line.

    python3 hkvbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start until the window opens: `setup_s`) builds the
cell's table from the seed on the device and warms every program the
window runs, from the persistent compilation cache in `<checkout>/.jax_cache`
(`run.py` points `JAX_COMPILATION_CACHE_DIR` there).
The window then drives the cell's entry for `--seconds`; the batch in
flight when the time is up completes and closes the window.  With
`--trace 1` the window runs under the profiler and the line reports the
cell's per-layer metrics instead of its end-to-end ones.  After the window
the program's state is freed and the entry's answers are compared with the
plain reference (`reference.py`); every number compared is printed with
its limit, on standard error and under `checks` in the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time

from hkvbench import device, spec
from hkvbench import trace as tracemod

FAULTS = ("control", "unchanged_state", "half_batch", "altered_answer")


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    fault: str | None
    devs: list
    # (phase, perf_counter at its end) for each part of set-up
    marks: list = dataclasses.field(default_factory=list)

    def mark(self, phase: str) -> None:
        self.marks.append((phase, time.perf_counter()))


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    attempted: int          # batches or requests dispatched in the window
    failed: int             # of those, the ones that never completed
    keys: int               # keys completed in the window

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


@dataclasses.dataclass(frozen=True)
class Reading:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class MetricCtx:
    run: Run
    window: Window
    setup_s: float
    bytes_in_use: int
    resident_keys: int
    summary: tracemod.Summary | None
    peaks: dict


def process_age_s() -> float:
    """Seconds since this process started (from /proc; 0 where absent)."""
    try:
        import os

        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def setup_parts(marks, t_start: float, age0: float) -> dict:
    """Seconds of each part of set-up, from the interpreter's start: the
    parts add up to `setup_s`."""
    parts, t = {"interpreter": age0}, t_start
    for phase, at in marks:
        parts[phase] = parts.get(phase, 0.0) + (at - t)
        t = at
    return parts


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="plant a fault or the lower-precision control in "
                         "the timed path (checks of the check)")
    return ap.parse_args(argv)


def main(argv, t_start: float, age0: float) -> int:
    args = parse(argv)
    cell = spec.load(args.workload)
    marks = []
    import jax  # noqa: F401

    marks.append(("import", time.perf_counter()))
    try:
        devs = device.require(cell.chips)
    except device.NoChip as e:
        print(f"hkvbench: {e}", file=sys.stderr)
        return 3
    marks.append(("devices", time.perf_counter()))
    run = Run(cell, args.seed, args.seconds, args.fault, devs, marks)
    line = measure(run, trace=bool(args.trace), t_start=t_start, age0=age0,
                   peaks=device.peaks(devs[0].device_kind))
    print(json.dumps(line), flush=True)
    return 0


def measure(run: Run, *, trace: bool, t_start: float, age0: float,
            peaks: dict) -> dict:
    """Set up, measure, check; the result line as a dict."""
    ent = spec.entry(run.cell.traffic["entry"])
    state = ent.setup(run)
    run.mark("warm-up")
    t_open = time.perf_counter()
    setup_s = age0 + (t_open - t_start)
    parts = setup_parts(run.marks, t_start, age0)
    print("hkvbench: set-up " + ", ".join(f"{k} {v:.3f} s"
                                          for k, v in parts.items()),
          file=sys.stderr)
    summary = None
    if trace:
        tdir = spec.ROOT / ".hkvbench" / "trace" / run.cell.name
        with tracemod.Capture(tdir) as cap:
            with tracemod.annotate(tracemod.WINDOW_SPAN):
                win = ent.window(run, state)
        if cap.xplane is None:
            raise RuntimeError("the profiler wrote no trace")
        summary = tracemod.reduce_trace(cap.xplane, cap.wall_s)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        win = ent.window(run, state)
    ent.release(run, state, win)
    resident = ent.resident(run, state)
    in_use = device.memory(run.devs, "bytes_in_use")
    peak = device.memory(run.devs, "peak_bytes_in_use")
    t_check = time.perf_counter()
    readings = ent.check(run, state, win)
    print(f"hkvbench: window {win.seconds:.3f} s, check against the "
          f"reference {time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    mctx = MetricCtx(run, win, setup_s, in_use, resident, summary, peaks)
    metrics = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        value = spec.reader(m.name)(mctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    dev = device.describe(run.devs)
    dev["memory_peak_bytes"] = peak
    line = {"correct": all(r.ok for r in readings),
            "attempted": win.attempted, "failed": win.failed,
            "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {"device_ops": [[k, v] for k, v in ops],
                             "idle_gaps": [[k, v] for k, v in summary.gaps]}
    line["setup_parts"] = parts
    line["checks"] = {r.name: {"value": r.value, "limit": r.limit}
                      for r in readings}
    for r in readings:
        print(f"check {r.name} {r.value} limit {r.limit} "
              f"{'ok' if r.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return line
