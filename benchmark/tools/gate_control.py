"""What the greedy comparison of a `serve_reason_ref` cell is worth, read
once, on the chip, outside the benchmark:

    python benchmark/tools/gate_control.py <workload> <seed> [<BENCHMARK.json>]

The cell's set-up as the benchmark makes it (`drivers/serve_reason_ref.start`:
the engine, the warm-up, the served greedy answers, the float32 reference and
the plain bf16 path at the answers' positions: the SOUND reading, each
verdict's gaps against `agreement.follows_greedy`'s limits), then the engine
is closed and the same served tokens are judged as if the configuration
described ANOTHER model (its float32 reference, and its plain bf16 path: the
program's uncached forward under that model's `ModelConfig`), which must NOT
pass, each on the `long` verdict (the rows that pass the window while they
decode; `window_control.py`'s way):

- `no_gate`: attention without its output gate;
- `no_branch_norms`: two norms a layer, the branches joined as they are;
- `rope_everywhere`: rotary embedding on the global layer too;
- `no_window`: every layer global;
- `zero_bias`: the four experts chosen by the scores alone;
- `no_embed_scale`: the embedding as it is stored;
- `float8`: the plain path's argmax with every weight rounded to e4m3 under
  a scale of its own tensor (`greedy_control.to_float8`), the nearest
  precision below the configuration's bf16, against the sound reference, on
  both verdicts. The weights are rounded where they lie, so this comes last.

A line a reading, as `greedy_control.py` prints them; exit 0 when every sound
reading passes and every control is refused. Writes
`chiprun_out/gate_control_<workload>_<seed>.json`. Off the chip (a rehearsal
cell on the CPU) it runs the same and says that it is no reading.
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

from harness import cell as cells             # noqa: E402

greedy_control = cells.load_module(os.path.join(HERE, "greedy_control.py"),
                                   "bench_tool_greedy_control")
reading = greedy_control.reading

# control -> (the reference's flags, the other model's ModelConfig fields)
CONTROLS = {
    "no_gate": ({"gate": False}, {"attention_gate": False}),
    "no_branch_norms": ({"attn_norm": False, "mlp_norm": False},
                        {"branch_norms": False}),
    "rope_everywhere": ({"nope": False}, {"rope_layout": ()}),
    "no_window": ({"window": False},
                  {"sliding_window": 0, "sliding_window_layout": ()}),
    "zero_bias": ({"bias": False}, {"use_expert_bias": False}),
    "no_embed_scale": ({"embed_scale": False}, {"embed_scale": 1.0}),
}


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
    reference_logits = keep.pop("reference_logits")
    plain_logits, params = keep.pop("plain_logits"), keep.pop("params")
    lines = [reading("sound", name, v["ref"], v["tokens"], v["plain"])
             for name, v in keep.items()]
    v = keep["long"]
    args = (v["batch"], v["answers"], v["n"])
    for control, (flags, other_model) in CONTROLS.items():
        other = reference_logits(*args, **flags)
        plain, _ = plain_logits(params, *args, **other_model)
        lines.append(reading(control, "long", other, v["tokens"], plain))
        del other, plain
    params = greedy_control.to_float8(params)
    for name, v in keep.items():
        low, _ = plain_logits(params, v["batch"], v["answers"], v["n"])
        lines.append(reading("float8", name, v["ref"], low.argmax(axis=-1),
                             v["plain"]))
    for line in lines:
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"gate_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    sound = all(ln["ok"] for ln in lines if ln["control"] == "sound") and sound_ok
    refused = all(not ln["ok"] for ln in lines if ln["control"] != "sound")
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
