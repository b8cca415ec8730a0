"""The knee of a serve cell, found once, on the chip, outside the benchmark:

    python benchmark/tools/knee_sweep.py <workload> <seconds> <rate> [<rate> ...]

One process, one engine: the cell's set-up as the benchmark makes it, then one
ramp + window + drain per rate, in the order given, the engine idle between
them. Per rate: requests due in the window, completed, failed, backlog
(active + pending) at the window's end, tokens/s, TTFT and TPOT tails. The
knee is the highest rate with no growing backlog and failed = 0; the cell's
mix takes four fifths of it. Writes `chiprun_out/knee_<workload>.json`.
"""

from __future__ import annotations

import json
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from harness import cell as cells            # noqa: E402
from harness.window import TraceWindow       # noqa: E402


def main(argv) -> int:
    workload, seconds, rates = argv[0], float(argv[1]), [float(r) for r in argv[2:]]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: no TPU, no number", file=sys.stderr)
        return 2
    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), workload)
    driver = cells.load_driver(cell)
    out_dir = os.path.join(BENCH, "out", "knee_" + workload)
    os.makedirs(out_dir, exist_ok=True)
    opts = {"seed": 11, "seconds": seconds, "trace": False, "out_dir": out_dir,
            "t_process_start": T0, "traffic_file": cell.traffic_file}
    served = driver.start(cell, opts)
    table = []
    try:
        for i, rate in enumerate(rates):
            opts["seed"] = 11 + i
            m = driver.measure(served, cell, opts, TraceWindow(out_dir, False), rate)
            snaps = m["snapshots"]
            half = len(snaps) // 2
            busy = lambda xs: sum(s["active"] + s["pending"] for s in xs) / max(len(xs), 1)  # noqa: E731
            row = {"rate_rps": rate, "due": len(m["records"]),
                   "completed": m["statuses"].get("ok", 0),
                   "failed": len(m["records"]) - m["statuses"].get("ok", 0),
                   "statuses": m["statuses"], "backlog_end": m["backlog_end"],
                   "in_system_first_half": busy(snaps[:half]),
                   "in_system_second_half": busy(snaps[half:]),
                   "late_p95_ms": sorted((r["sent"] - r["due"]) * 1e3 for r in
                                         m["records"] if "sent" in r)[
                                             int(0.95 * (len(m["records"]) - 1))],
                   **driver.client_metrics(m["records"], seconds)}
            table.append(row)
            print(json.dumps(row), flush=True)
            while served.engine.snapshot()["active"] + served.engine.snapshot()["pending"]:
                time.sleep(0.5)
    finally:
        served.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"knee_{workload}.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
