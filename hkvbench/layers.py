"""Device time of a traced window by program layer.

The program names its layers with `jax.named_scope`, which lands in each
HLO instruction's `op_name` metadata:

  hkv.op.<op>, hkv.emb.<method>   op dispatch (`core/ops.py`, `HKVEmbedding`)
  hkv.closure                     batch closure (`core/merge.py`, `u64.lexsort`)
  hkv.kernel_io                   kernel wrappers (`kernels/ops.py`)

A device trace names an op only by its module (`XLA Modules` line:
`jit_find(<fingerprint>)`) and its HLO instruction (`XLA Ops` line:
`%fusion.30 = ...`).  `reduce` joins each op to the compiled text of its
module and gives it one layer:

  kernels       a Pallas kernel (a `tpu_custom_call`, or the fusion that
                holds one), whatever scope encloses it;
  kernel_io     innermost `hkv.` scope `hkv.kernel_io`;
  closure       innermost `hkv.closure`;
  op            innermost `hkv.op.*` or `hkv.emb.*`;
  client        no `hkv.` scope: the benchmark's own programs.

A fusion counts for its own op_name, which XLA takes from its root
instruction; `Split.mixed_s` is the device time in fusions whose fused
instructions lie in more than one layer.  An instruction the compiler
inserted carries no op_name (copies, async starts and dones, relayouts):
it takes the layer of its consumers (the first that has one, transitively),
else of its operands, else `client`.  A traced module with no text, a
module name held by two programs, and an instruction missing from its
module's text are errors (`LayerError`), never guesses.

The programs a cell's window runs are named by its entry's
`programs(run, state)` (`spec.py`).  `hkvbench/run.py --trace 1` reports
the shares of every traced window.  With a chip, one traced window
reduced by layer:

    python3 hkvbench/layers.py --workload <cell> --seed <n> --seconds <s> [--save <dir>]

prints one JSON line (seconds and share of busy time per layer, the
shares under their metric names, the seconds the post-window lowering
took) and, with `--save`, writes the trace and module texts gzipped.
"""

from __future__ import annotations

if __name__ == "__main__":      # as a script: the run's paths and environment
    import sys
    from pathlib import Path

    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
    from hkvbench import run as _run  # noqa: F401

import bisect
import dataclasses
import re

from hkvbench import trace as tracemod

LAYERS = ("kernels", "kernel_io", "closure", "op", "client")
# per-layer share metric -> layer; kernel time is 100 % minus these
SHARES = {"op.self_share": "op", "closure.self_share": "closure",
          "kernel_io.self_share": "kernel_io",
          "client.device_share": "client"}
MODULES_LINE = "XLA Modules"
_SCOPE = {"hkv.kernel_io": "kernel_io", "hkv.closure": "closure"}
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s[a-z][\w\-]*\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_BODY = re.compile(r'"body": ?"[^"]*"')


class LayerError(RuntimeError):
    pass


def scope_layer(op_name: str | None) -> str | None:
    """The layer of an op_name's innermost `hkv.` scope; `client` for a
    name path with none; None for no name path (compiler-inserted, or a
    parameter's name)."""
    if not op_name or "/" not in op_name:
        return None
    for part in reversed(op_name.split("/")):
        if part in _SCOPE:
            return _SCOPE[part]
        if part.startswith(("hkv.op.", "hkv.emb.")):
            return "op"
        if part.startswith("hkv."):
            raise LayerError(f"unknown program scope {part!r} in {op_name!r}")
    return "client"


@dataclasses.dataclass
class _Instr:
    op_name: str | None
    operands: list
    calls: str | None
    kernel: bool


def parse_module(text: str) -> tuple[str, dict]:
    """(module name, {instruction: (layer, mixed)}) of one compiled
    module's text; `mixed` marks a fusion whose fused instructions lie in
    more than one layer."""
    name = re.match(r"HloModule ([\w.\-]+)", text)
    if name is None:
        raise LayerError("not an HLO module text")
    comps, instrs, comp = {}, {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        args = rest[op.end():] if op else ""
        depth, end = 1, 0
        for end, ch in enumerate(args):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        calls = _CALLS.search(rest)
        on = _OP_NAME.search(rest)
        instrs[m.group(1)] = _Instr(
            on.group(1) if on else None,
            _OPERAND.findall(args[:end]), calls.group(1) if calls else None,
            'custom_call_target="tpu_custom_call"' in rest)
        comp.append(m.group(1))
    for ins in instrs.values():        # a fusion holding a kernel is one
        if ins.calls in comps and any(instrs[i].kernel
                                      for i in comps[ins.calls]):
            ins.kernel = True
    users = {}
    for n, ins in instrs.items():
        for o in ins.operands:
            users.setdefault(o, []).append(n)

    def own(n):
        # the scope of n's op_name, else of the instructions it fuses
        ins = instrs[n]
        layer = scope_layer(ins.op_name)
        if layer is None and ins.calls in comps:
            fused = [scope_layer(instrs[i].op_name) for i in comps[ins.calls]]
            layer = next((l for l in reversed(fused) if l), None)
        return layer

    memo = {}

    def scope_of(n):
        # its own scope, else its consumers', else its operands'
        if n not in memo:
            memo[n] = None          # a cycle resolves to nothing
            layer = own(n)
            for nxt in (users.get(n, ()), instrs[n].operands):
                for u in nxt if layer is None else ():
                    layer = scope_of(u) if u in instrs else None
                    if layer is not None:
                        break
            memo[n] = layer
        return memo[n]

    out = {}
    for n, ins in instrs.items():
        fused = ({scope_layer(instrs[i].op_name) for i in comps[ins.calls]}
                 - {None}) if ins.calls in comps and not ins.kernel else ()
        out[n] = ("kernels" if ins.kernel else scope_of(n) or "client",
                  len(fused) > 1)
    return name.group(1), out


def module_texts(texts) -> dict:
    """{module name: compiled text}, kernel bodies dropped; a name held
    by two different programs is an error."""
    out = {}
    for text in texts:
        text = _BODY.sub('"body": ""', text)
        name = re.match(r"HloModule ([\w.\-]+)", text)
        if name is None:
            raise LayerError("not an HLO module text")
        name = name.group(1)
        if name in out and out[name] != text:
            raise LayerError(f"module name {name!r} is held by two programs")
        out[name] = text
    return out


@dataclasses.dataclass
class Split:
    seconds: dict          # layer -> device seconds (self time)
    busy_s: float          # union of the device-op intervals (all chips)
    op_s: float            # device seconds of every op, containers left out
    mixed_s: float         # seconds in fusions spanning two layers

    def shares(self) -> dict:
        """Each layer's self time over busy time, in %, by metric name."""
        return {k: 100.0 * self.seconds[v] / self.busy_s
                for k, v in SHARES.items()} if self.busy_s > 0 else {}


def instr_seconds(path) -> tuple[dict, float]:
    """Device seconds per (module, instruction) of a trace, loops and
    calls left out as in `trace.reduce_trace`; and the busy time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out, busy = {}, 0.0
    for plane in data.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {l.name: list(l.events) for l in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                      for e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        ivs = []
        for ev in lines.get(tracemod.OPS_LINE, []):
            ivs.append((ev.start_ns, ev.end_ns))
            if tracemod.op_name(ev) in tracemod.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i < 0 or ev.start_ns > mods[i][1]:
                raise LayerError(f"op {ev.name[:80]!r} lies in no module")
            m = tracemod._HLO_NAME.match(ev.name)
            key = (mods[i][2], m.group(1) if m else ev.name)
            out[key] = out.get(key, 0.0) + ev.duration_ns * 1e-9
        busy += tracemod._union(ivs) * 1e-9
    return out, busy


def reduce(path, texts: dict) -> Split:
    """The traced window at `path` by layer, from the compiled texts of
    the modules it ran (`module_texts`)."""
    instr_s, busy = instr_seconds(path)
    parsed = {}
    missing = sorted({m for m, _ in instr_s if m not in texts})
    if missing:
        raise LayerError(f"traced modules with no text: {missing}")
    seconds, mixed = dict.fromkeys(LAYERS, 0.0), 0.0
    for (mod, ins), s in instr_s.items():
        if mod not in parsed:
            parsed[mod] = parse_module(texts[mod])[1]
        if ins not in parsed[mod]:
            raise LayerError(f"instruction {ins!r} is not in {mod}'s text")
        layer, is_mixed = parsed[mod][ins]
        seconds[layer] += s
        mixed += s if is_mixed else 0.0
    return Split(seconds, busy, sum(instr_s.values()), mixed)


def compiled_texts(progs) -> list:
    """The optimized HLO text of each (program, arguments)."""
    return [(fn if args is None else fn.lower(*args).compile()).as_text()
            for fn, args in progs]


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    import shutil
    import sys
    import time
    from pathlib import Path

    from hkvbench import device, harness, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", type=Path, default=None,
                    help="write <cell>.xplane.pb.gz and <cell>.modules.json.gz")
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)
    try:
        devs = device.require(cell.chips)
    except device.NoChip as e:
        print(f"hkvbench.layers: {e}", file=sys.stderr)
        return 3
    run = harness.Run(cell, args.seed, args.seconds, None, devs)
    ent = spec.entry(cell.traffic["entry"])
    st = ent.setup(run)
    tdir = spec.ROOT / ".hkvbench" / "trace" / f"layers.{cell.name}"
    with tracemod.Capture(tdir) as cap:
        with tracemod.annotate(tracemod.WINDOW_SPAN):
            win = ent.window(run, st)
    t0 = time.perf_counter()
    texts = module_texts(compiled_texts(ent.programs(run, st)))
    lower_s = time.perf_counter() - t0
    line = {"workload": cell.name, "seed": args.seed,
            "kv_s_traced": win.keys / win.seconds, "lower_s": lower_s,
            "modules": sorted(texts)}
    summary = tracemod.reduce_trace(cap.xplane, cap.wall_s)
    line.update(busy_s=summary.busy_s, window_s=summary.window_s)
    if args.save is not None:
        args.save.mkdir(parents=True, exist_ok=True)
        with open(cap.xplane, "rb") as f, gzip.open(
                args.save / f"{cell.name}.xplane.pb.gz", "wb") as g:
            g.write(f.read())
        with gzip.open(args.save / f"{cell.name}.modules.json.gz", "wt") as g:
            json.dump(texts, g)
    try:
        split = reduce(cap.xplane, texts)
    except LayerError as e:
        line["error"] = str(e)
    else:
        line.update(layer_s=split.seconds, op_s=split.op_s,
                    mixed_s=split.mixed_s, shares=split.shares(),
                    kernels_share=100.0 * split.seconds["kernels"]
                    / split.busy_s)
    shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 1 if "error" in line else 0


if __name__ == "__main__":
    sys.exit(main())
