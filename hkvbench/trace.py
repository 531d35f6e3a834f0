"""Profiler capture of the measured window, and its reduction to numbers.

`Capture` wraps `jax.profiler` around the window.  `reduce_trace` reads
the `.xplane.pb` with `jax.profiler.ProfileData` and returns a `Summary`:

  busy_s       union of the device-op intervals, averaged over the chips;
  window_s     the traced window's length (the host's window annotation,
               else the capture's wall time);
  op_s         device seconds per op name (numbered suffixes folded),
               loops and calls left out (their ops are listed too);
  gaps         idle stretches between device ops, each named by the
               benchmark's own host annotation (`hkv.*`) covering most
               of it, longest first, cut to `max_gaps`;
  idle_by_span idle seconds of the whole window by that name, uncut
               (the first chip's, as `gaps`).

Device planes are `/device:TPU:<n>`; their op events are on the line named
`XLA Ops`, each named by its HLO instruction's text, in which a Pallas
kernel's instruction carries the kernel's `name=` (e.g.
`hkv_find_scan_pipeline.3 = ... custom-call(...)`).
"""

from __future__ import annotations

import dataclasses
import re
import shutil
import time
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "hkv.window"
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")
_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w.\-]*) = ")
# ops that contain others on the ops line (their children are listed too)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    op_s: dict
    gaps: list            # [(host annotation, seconds)] longest first
    chips: int
    idle_by_span: dict    # host annotation -> idle seconds in the window


def op_name(event) -> str:
    """The stable name of a device op: the HLO instruction's name (the
    event's name is the instruction's text) with numbered suffixes folded,
    so a Pallas kernel reads as its own `name=` (`hkv_find_scan_pipeline`)
    and XLA's ops as their kind (`fusion`, `sort`, `while`)."""
    m = _HLO_NAME.match(event.name)
    return _SUFFIX.sub("", m.group(1) if m else event.name)


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo, hi):
    """Idle stretches of [lo, hi] not covered by `intervals`."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _host_spans(planes):
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hkv."):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def _name_gap(a, b, spans):
    best, cover = "unattributed", 0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best


def reduce_trace(path, wall_s: float, max_gaps: int = 10) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    spans = _host_spans(planes)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    busy, op_s, gaps, chips = 0.0, {}, [], 0
    for plane in planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = [l for l in plane.lines if l.name == OPS_LINE]
        ivs = []
        for line in lines:
            for ev in line.events:
                ivs.append((ev.start_ns, ev.end_ns))
                k = op_name(ev)
                if k not in CONTAINERS:
                    op_s[k] = op_s.get(k, 0.0) + ev.duration_ns * 1e-9
        if not ivs:
            continue
        chips += 1
        busy += _union(ivs) * 1e-9
        if chips == 1:
            lo, hi = (win[0] if win else (min(s for s, _ in ivs),
                                          max(e for _, e in ivs)))
            for a, b in _gaps([(max(s, lo), min(e, hi)) for s, e in ivs
                               if e > lo and s < hi], lo, hi):
                gaps.append((_name_gap(a, b, spans), (b - a) * 1e-9))
    window_s = (win[0][1] - win[0][0]) * 1e-9 if win else wall_s
    idle = {}
    for name, s in gaps:
        idle[name] = idle.get(name, 0.0) + s
    gaps.sort(key=lambda g: -g[1])
    return Summary(busy_s=busy / max(chips, 1), window_s=window_s, op_s=op_s,
                   gaps=gaps[:max_gaps], chips=chips, idle_by_span=idle)


class Capture:
    """`with Capture(dir) as cap:` traces the block; `cap.xplane` is the
    trace file afterwards and `cap.wall_s` the block's wall time."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self.xplane = None
        self.wall_s = 0.0

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.wall_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        found = sorted(self.dir.rglob("*.xplane.pb"))
        self.xplane = found[-1] if found else None
        return False


def annotate(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)

