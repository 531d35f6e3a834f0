"""Benchmark entry point: one experiment per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run                   # all experiments
  PYTHONPATH=src python -m benchmarks.run exp1 exp4         # subset
  PYTHONPATH=src python -m benchmarks.run exp2 --backend kernel
  PYTHONPATH=src python -m benchmarks.run exp5 exp6_online exp7_maintenance \
      --smoke --json-out runs/bench --timestamp 2026-07-26T00:00:00Z

Output: `name,us_per_call,derived` CSV blocks per experiment on stdout.
`roofline` emits the fused find/update bytes models + distance-to-roofline
against any BENCH_exp2.json in the --json-out dir (dry-run step terms ride
along when runs/dryrun/ artifacts exist).  `exp9_train_apply` measures
end-to-end DLRM train steps/sec under the fused vs composed updater arms
per optimizer variant, with kernel launch/byte deltas.  --backend selects the table-op
implementation for exp2 (DESIGN.md §4); `fused` adds the reader-path
launch-accounting arm on top of the kernel backend.

Trajectory artifacts: with `--json-out DIR`, each experiment additionally
writes `DIR/BENCH_<exp>.json` in the stable `bench-trajectory/v1` schema —
{schema, experiment, title, commit, timestamp, rows[{name, us_per_call,
derived, kv_per_s}]} — so successive CI runs accumulate a comparable perf
trajectory.  The timestamp is PASSED IN (the driver owns the clock; runs
are reproducible byte-for-byte given the same tree), and the commit is
taken from $BENCH_COMMIT or `git rev-parse HEAD`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def _commit() -> str:
    c = os.environ.get("BENCH_COMMIT")
    if c:
        return c
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            check=True,
        ).stdout.strip()
    except Exception:
        return "unknown"


def _pop_flag(args: list, flag: str, *, takes_value: bool = True):
    if flag not in args:
        return None if takes_value else False
    i = args.index(flag)
    if not takes_value:
        del args[i]
        return True
    if i + 1 >= len(args):
        sys.exit(f"error: {flag} requires a value")
    v = args[i + 1]
    del args[i : i + 2]
    return v


def main() -> None:
    args = sys.argv[1:]
    backend = _pop_flag(args, "--backend") or "jnp"
    if backend not in ("auto", "jnp", "kernel", "fused"):
        sys.exit("error: --backend requires one of auto|jnp|kernel|fused")
    json_out = _pop_flag(args, "--json-out")
    timestamp = _pop_flag(args, "--timestamp")
    smoke = _pop_flag(args, "--smoke", takes_value=False)
    if json_out and not timestamp:
        sys.exit("error: --json-out requires --timestamp (the driver passes "
                 "the clock in; artifacts never read one)")
    known = {"exp1", "exp2", "exp3", "exp4", "exp5", "exp6_online",
             "exp7_maintenance", "exp9_train_apply", "roofline"}
    bad = [a for a in args if a not in known]
    if bad:
        sys.exit(f"error: unknown argument(s) {bad}; experiments: {sorted(known)}, "
                 "options: --backend auto|jnp|kernel|fused --smoke "
                 "--json-out DIR --timestamp TS")
    if backend != "jnp" and args and "exp2" not in args:
        sys.exit("error: --backend only applies to exp2; add exp2 to the "
                 "selection or drop the flag")
    if smoke and args and not ({"exp5", "exp6_online",
                                "exp7_maintenance"} & set(args)):
        sys.exit("error: --smoke only applies to exp5/exp6_online/"
                 "exp7_maintenance; add one to the selection or drop "
                 "the flag")
    sel = set(args)
    commit = _commit() if json_out else ""
    from repro.launch import compile_cache

    compile_cache.enable()

    def want(name):
        return not sel or name in sel

    def emit(name, csv):
        if not json_out or csv is None:
            return
        os.makedirs(json_out, exist_ok=True)
        path = os.path.join(json_out, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(csv.to_json(name, commit=commit, timestamp=timestamp),
                      f, indent=1)
        print(f"# wrote {path}")

    if want("exp1"):
        from benchmarks import exp1_load_factor

        emit("exp1", exp1_load_factor.run())
    if want("exp2"):
        from benchmarks import exp2_throughput

        emit("exp2", exp2_throughput.run(backend=backend))
    if want("exp3"):
        from benchmarks import exp3_ablation

        emit("exp3", exp3_ablation.run())
    if want("exp4"):
        from benchmarks import exp4_dual_bucket

        emit("exp4", exp4_dual_bucket.run())
    if want("exp5"):
        from benchmarks import exp5_tiered

        emit("exp5", exp5_tiered.run(smoke=bool(smoke)))
    if want("exp6_online"):
        from benchmarks import exp6_online

        emit("exp6_online", exp6_online.run(smoke=bool(smoke)))
    if want("exp7_maintenance"):
        from benchmarks import exp7_maintenance

        emit("exp7_maintenance", exp7_maintenance.run(smoke=bool(smoke)))
    if want("exp9_train_apply"):
        from benchmarks import exp9_train_apply

        emit("exp9_train_apply", exp9_train_apply.run())
    if want("roofline"):
        from benchmarks import roofline

        # read exp2 artifacts from the SAME --json-out dir when set, so a
        # single invocation's distance rows reflect the run it just wrote
        emit("roofline", roofline.run(bench_dir=json_out or "runs/bench"))


if __name__ == "__main__":
    main()
