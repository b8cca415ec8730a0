"""Measure a cell as the driver does: sets of runs of the same code, each run a
new process with another `--seed`, and per metric the spread (distance between
the quartiles over the median) of each set.

    python benchmark/tools/spread.py <workload> [--sets 2] [--runs 6] [--trace-last]

The parent never imports jax (a chip belongs to one process at a time); it
starts `benchmark/run.py` once per run and reads the last line. Writes
`chiprun_out/spread_<workload>.json` and prints one line per run and a table.
A bound is set to about five times the wider of the sets' spreads, never
under 1 %; the second set's median may not leave the first's by the bound.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def one_run(workload: str, seed: int, seconds: int, trace: int) -> dict | None:
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, cwd=ROOT)
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines:
        print(f"run seed {seed} failed rc={out.returncode}\n"
              + out.stderr[-3000:] + "\n" + out.stdout[-2000:], flush=True)
        return None
    line = json.loads(lines[-1])
    if not line["correct"] or trace:
        for text in lines:
            if text.startswith('{"phase": "detail"') or (
                    trace and text.startswith('{"phase": "trace"')):
                print(text[:6000], flush=True)
    return line


def spread(values) -> float:
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace-last", action="store_true",
                    help="one more run with --trace 1 at the end")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    sets = []
    for s in range(args.sets):
        runs = []
        for r in range(args.runs):
            seed = 100 * (s + 1) + r + 1
            line = one_run(args.workload, seed, seconds, 0)
            if line is None:
                continue
            row = {"seed": seed, "correct": line["correct"],
                   "attempted": line["attempted"], "failed": line["failed"],
                   "peak_gb": line["device"]["memory_peak_bytes"] / 1e9,
                   **{k: v["value"] for k, v in line["metrics"].items()}}
            print(json.dumps(row), flush=True)
            runs.append(row)
        sets.append(runs)
    names = [k for k in sets[0][0] if k not in (
        "seed", "correct", "attempted", "failed", "peak_gb")]
    table = {}
    for name in names:
        # the first run of the first set may have compiled: leave it out of
        # setup_s, as the driver does
        per_set = [[r[name] for r in runs][(1 if name == "setup_s" and i == 0 else 0):]
                   for i, runs in enumerate(sets)]
        table[name] = {
            "medians": [statistics.median(v) for v in per_set],
            "spreads": [spread(v) if len(v) >= 3 else None for v in per_set]}
        print(json.dumps({"metric": name, **table[name]}), flush=True)
    result = {"workload": args.workload, "seconds": seconds, "sets": sets,
              "table": table}
    if args.trace_last:
        result["traced"] = one_run(args.workload, 999, seconds, 1)
        print(json.dumps(result["traced"])[:8000], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"spread_{args.workload}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
