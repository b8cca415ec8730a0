"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. Prints what it runs on first and exits
non-zero, with no result line, unless jax reports the cell's TPU chips. Set-up
(import, weights, compile or cache load, warm-up, ramp) is timed from the
start of this process to the first instant of the window. The last line of
stdout is one JSON object: `correct`, `attempted`, `failed`, `metrics`,
`device`, and with `--trace 1` also `breakdown`. With `--trace 0` the metrics
are the cell's end-to-end metrics, with `--trace 1` its per-layer metrics,
each from its reader under `layer_metrics/`.

Everything else goes to earlier lines and to `benchmark/out/<cell>/`:
per-update rows or per-request records, the reduced trace, the resolved
`auto` choices.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from harness import cell as cells          # noqa: E402
from harness import ops_bytes              # noqa: E402
from harness.window import device_info     # noqa: E402


def run_cell(bench_file: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, out_root: str | None = None,
             t_process_start: float | None = None) -> dict | None:
    """Run one cell and return the final line's object; None (after a message
    on stderr) when the machine is not the cell's. `require_tpu=False` is
    the CPU rehearsal, which tests and scratch scripts steer with a test
    benchmark file of tiny sizes: the command itself has no such switch."""
    cell = cells.load_cell(bench_file, workload)
    import jax

    found = jax.devices()
    print(json.dumps({"phase": "device", "platform": found[0].platform,
                      "kind": found[0].device_kind, "count": len(found),
                      "cell": cell.name, "chips": cell.chips}), flush=True)
    if require_tpu:
        if found[0].platform != "tpu" or len(found) < cell.chips:
            print(f"benchmark: cell {cell.name!r} needs {cell.chips} TPU "
                  f"chip(s); jax found {len(found)} x {found[0].platform!r}. "
                  "No chip, no number.", file=sys.stderr)
            return None
        peak = ops_bytes.peaks(found[0].device_kind)    # KeyError: unknown kind
    else:
        peak = ops_bytes.peaks("TPU v5 lite")   # rehearsal: shares mean nothing

    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    out_dir = os.path.join(out_root or os.path.join(HERE, "out"), cell.name)
    os.makedirs(out_dir, exist_ok=True)
    print(json.dumps({"phase": "start", "compile_cache_dir": cache_dir,
                      "out_dir": out_dir, "seed": seed, "seconds": seconds,
                      "trace": bool(trace)}), flush=True)
    opts = {"seed": seed, "seconds": seconds, "trace": bool(trace),
            "out_dir": out_dir,
            "t_process_start": t_process_start or T_PROCESS_START,
            "traffic_file": cell.traffic_file}
    result = cells.load_driver(cell).run(cell, opts)

    device = device_info(cell.chips)
    run = result.run
    run.update(end_to_end=result.end_to_end, peaks=peak, device=device,
               why_not=result.why_not)
    names = {m["name"]: m for m in cell.end_to_end}
    reported = {k: v for k, v in result.end_to_end.items() if k in names}
    if trace:
        metrics = cells.read_layer_metrics(cell, run, set(reported))
        tr = run.get("trace")
        if tr is not None:
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    else:
        metrics = {k: {"value": float(v), "unit": names[k]["unit"]}
                   for k, v in reported.items()}
    line = {"correct": bool(result.correct), "attempted": int(result.attempted),
            "failed": int(result.failed), "metrics": metrics, "device": device}
    if trace and run.get("trace") is not None:
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump({"line": line, "why_not": result.why_not, "run": run}, f,
                  indent=1, default=str)
    detail = {k: run.get(k) for k in ("auto", "logprobs", "greedy_check",
                                      "statuses", "samples", "update_seconds",
                                      "tokens", "compile", "backlog_end", "child")
              if k in run}
    print(json.dumps({"phase": "detail", "why_not": result.why_not,
                      "end_to_end": result.end_to_end, **detail},
                     default=str), flush=True)
    if trace and run.get("trace") is not None:
        print(json.dumps({"phase": "trace", **run["trace"]}, default=str),
              flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    seconds = args.seconds
    if seconds is None:
        seconds = cells.load_benchmark(bench_file)["run_seconds"]
    line = run_cell(bench_file, args.workload, args.seed, seconds,
                    bool(args.trace))
    if line is None:
        return 2
    if args.trace and "busy_s" not in line["device"]:
        print("benchmark: the traced run found no operation on a device plane",
              file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
