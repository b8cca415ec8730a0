"""What the limits of a `serve_ref` cell's greedy comparison are worth, read
once, on the chip, outside the benchmark:

    python benchmark/tools/greedy_control.py <workload> <seed> [<BENCHMARK.json>]

The cell's set-up as the benchmark makes it (`drivers/serve_ref.start`: the
engine, the warm-up, the served greedy answers, the float32 reference and the
plain bf16 path at the answers' positions: the SOUND reading, each verdict's
gaps against its limits), then the engine is closed and the same comparison
judges tokens that must NOT pass, teacher-forced on the same served context:

- `lost_page`: the plain path's argmax where each prompt's first page of
  tokens never reached the cache (a block table one page off, a radix hit on
  the wrong node, a key block the read skipped);
- `float8`: the plain path's argmax with every weight rounded to 4 exponent
  and 3 mantissa bits (e4m3) under a scale of its own tensor (max |w| ->
  240), the nearest precision below the configuration's bf16;
  `lax.reduce_precision`, because the TPU compiler removes a convert to
  float8 and back (a first reading came out bit-equal to the plain path, my
  chip run, PR 31). The weights are rounded where they lie, so this comes
  last.

A line a reading: {"control", "verdict", "ok", "mean_over_limit",
"max_over_limit", ...}; a limit is worth its name when every sound reading is
under 1 and a control's is over 1 on `mean` or on `max`. Writes
`chiprun_out/greedy_control_<workload>_<seed>.json`. Off the chip (a
rehearsal cell on the CPU) it runs the same and says that it is no reading.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import numpy as np                            # noqa: E402

from harness import agreement                 # noqa: E402
from harness import cell as cells             # noqa: E402


def reading(control: str, verdict: str, ref, tokens, plain) -> dict:
    """`agreement.follows_greedy` and how far each statistic is from its
    limit (1 = on it)."""
    ok, d = agreement.follows_greedy(ref, tokens, plain)
    mean, peak = agreement.GAP_SLACK
    return {"control": control, "verdict": verdict, "ok": ok,
            "mean_over_limit": d["gap"]["mean_abs"] / (
                mean * d["plain_gap"]["mean_abs"] + 1e-3),
            "max_over_limit": d["gap"]["max_abs"] / (
                peak * d["plain_gap"]["max_abs"] + 5e-2), **d}


def to_float8(params):
    """Every floating leaf to e4m3's precision, scaled so that its largest
    magnitude is 240 (1.875 x 2^7, the format's largest finite value); each
    leaf where it lies."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scale_of(w):
        return jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32))), 1e-30) / 240.0

    def rounded(w, s):
        low = jax.lax.reduce_precision(w.astype(jnp.float32) / s,
                                       exponent_bits=4, mantissa_bits=3)
        return (low * s).astype(w.dtype)

    in_place = jax.jit(rounded, donate_argnums=0)
    return jax.tree.map(
        lambda w: in_place(w, scale_of(w))
        if jnp.issubdtype(w.dtype, jnp.floating) else w, params)


def main(argv) -> int:
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
    page = int(cell.traffic["engine"]["page_size"])
    plain_logits, params = keep.pop("plain_logits"), keep.pop("params")
    lines = [reading("sound", name, v["ref"], v["tokens"], v["plain"])
             for name, v in keep.items()]
    long_ = keep["long"]
    lost, _ = plain_logits(params, [p[page:] for p in long_["batch"]],
                           long_["answers"], long_["n"])
    lines.append(reading("lost_page", "long", long_["ref"],
                         lost.argmax(axis=-1), long_["plain"]))
    del lost
    params = to_float8(params)
    for name, v in keep.items():
        low, _ = plain_logits(params, v["batch"], v["answers"], v["n"])
        lines.append(reading("float8", name, v["ref"], low.argmax(axis=-1),
                             v["plain"]))
    for line in lines:
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"greedy_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    sound = all(ln["ok"] for ln in lines if ln["control"] == "sound") and sound_ok
    refused = all(not ln["ok"] for ln in lines if ln["control"] != "sound")
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
