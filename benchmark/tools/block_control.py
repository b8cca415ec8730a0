"""What the comparison of a `serve_block_ref` cell is worth, read once, on
the chip, outside the benchmark:

    python benchmark/tools/block_control.py <workload> <seed> [<BENCHMARK.json>]
        [--only <control>,<control>]

The cell's set-up as the benchmark makes it (`drivers/serve_block_ref.start`:
the engine, the warm-up, the served answers with the step that unmasked each
token, the float32 reference's replay of every denoise forward and the plain
bf16 path on its share of them: the SOUND reading, each verdict's gaps and
choice shortfalls against `agreement.GAP_SLACK`), then the engine is closed
and the same served tokens are judged as if the configuration described
ANOTHER model (its float32 reference; the calibration stays the sound
reading's: the plain path's own gaps under the sound reference), which must
NOT pass, on both verdicts:

- `causal`: the usual causal mask (a block's tokens do not see each other's
  later ones);
- `shift`: the logits at position i - 1 predict position i;
- `block_1`, `block_8`: another block length than the one generated with;
- `no_head_norm`: q and k without their per-head norms;
- `no_renorm`: the top-8 weights not renormalised;
- `stale_commit`: a program whose commit forward writes nothing, so that
  every later forward reads a block's K/V as its last denoise forward left
  them (the mask id where the block was then still masked). The counters
  cannot see it (`commit_forwards` = `blocks_done` holds); the served
  tokens, conditioned on the committed ones, can;
- `float8`: the plain path with every weight rounded to e4m3 under a scale of
  its own tensor (`greedy_control.to_float8`), the nearest precision below
  the configuration's bf16: ITS argmax and ITS choice of positions, judged
  against the sound reference and the sound plain path's. The weights are
  rounded where they lie and rebuilt from the seed for the reference, so
  this comes last.

A line a reading; exit 0 when every sound reading passes and every control
is refused. Writes `chiprun_out/block_control_<workload>_<seed>.json`. Off the
chip (a rehearsal cell on the CPU) it runs the same and says that it is no
reading.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from harness import agreement             # noqa: E402
from harness import cell as cells         # noqa: E402

greedy_control = cells.load_module(os.path.join(HERE, "greedy_control.py"),
                                   "bench_tool_greedy_control")

# control -> the reference's flags
CONTROLS = {
    "causal": {"causal": True}, "shift": {"shift": True},
    "block_1": {"block_length": 1}, "block_8": {"block_length": 8},
    "no_head_norm": {"qk_norm": False}, "no_renorm": {"renorm": False},
    "stale_commit": {"stale_commit": True},
}


def reading(control: str, verdict: str, ok: bool, d: dict) -> dict:
    """The verdict and how far each statistic is from its limit (1 = on it)."""
    mean, peak = agreement.GAP_SLACK
    over = lambda t, p: (     # noqa: E731
        d[t]["mean_abs"] / (mean * d[p]["mean_abs"] + 1e-3),
        d[t]["max_abs"] / (peak * d[p]["max_abs"] + 5e-2))
    return {"control": control, "verdict": verdict, "ok": ok,
            "gap_over_limit": over("gap", "plain_gap"),
            "choice_over_limit": over("choice_shortfall",
                                      "plain_choice_shortfall"), **d}


def main(argv) -> int:
    only = None
    if "--only" in argv:
        at = argv.index("--only")
        only, argv = set(argv[at + 1].split(",")), argv[:at] + argv[at + 2:]
    workload, seed = argv[0], int(argv[1])
    bench_file = argv[2] if len(argv) > 2 else os.path.join(ROOT, "BENCHMARK.json")
    import jax

    on_chip = jax.devices()[0].platform == "tpu"
    if on_chip:
        from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()
    cell = cells.load_cell(bench_file, workload)
    driver = cells.load_driver(cell)
    out_dir = os.path.join(BENCH, "out", "control_" + workload)
    os.makedirs(out_dir, exist_ok=True)
    opts = {"seed": seed, "seconds": 0.0, "trace": False, "out_dir": out_dir,
            "t_process_start": T0, "traffic_file": cell.traffic_file}
    keep: dict = {}
    served = driver.start(cell, opts, keep)
    sound_ok = served.greedy_ok
    served.close()
    del served
    gc.collect()
    verdicts, rows, asks = keep["verdicts"], keep["rows"], keep["asks"]
    params = keep.pop("params")
    of = lambda name, rs: [r for r, a in zip(rs, asks) if a[4] == name]  # noqa: E731
    lines = [reading("sound", name, *keep["judge"](of(name, rows)))
             for name in ("long", "short")]
    def against_the_sound_plain_path(name, tested, gap, short):
        """The verdict `name` with `tested`'s rows' `gap` / `short` as the
        served tokens', under the SOUND reading's calibration (the plain
        path's own gaps under the sound reference)."""
        return keep["judge"]([{**s, "gap": t[gap], "short": t[short]}
                              for s, t in zip(of(name, rows), of(name, tested))])

    for control, flags in CONTROLS.items():
        if only is not None and control not in only:
            continue
        _, other = verdicts(params, controls=flags, reuse=rows)
        lines += [reading(control, name, *against_the_sound_plain_path(
            name, other, "gap", "short")) for name in ("long", "short")]
    if only is None or "float8" in only:
        # float8: the plain path's own tokens and choices with rounded
        # weights, then the sound weights again (from the seed) for the
        # reference
        params = greedy_control.to_float8(params)
        low = [keep["plain_path"](params, r["fwd"], r["sub"]) for r in rows]
        del params
        gc.collect()
        params = keep["rebuild"]()
        _, rows8 = verdicts(params, reuse=[{"plain": p} for p in low])
        lines += [reading("float8", name, *against_the_sound_plain_path(
            name, rows8, "plain_gap", "plain_short"))
            for name in ("long", "short")]
    for line in lines:
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"block_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    sound = all(ln["ok"] for ln in lines if ln["control"] == "sound") and sound_ok
    refused = all(not ln["ok"] for ln in lines if ln["control"] != "sound")
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
