"""What the greedy comparison of a `serve_state_ref` cell is worth, read
once, on the chip, outside the benchmark:

    python benchmark/tools/state_control.py <workload> <seed> [<BENCHMARK.json>]

The cell's set-up as the benchmark makes it (`drivers/serve_state_ref.start`:
the engine, the warm-up, the served greedy answers of the four verdicts
`long`, `short`, `carry` and `reuse`, the float32 reference and the plain
bf16 path at the answers' positions: the SOUND reading, each verdict's gaps
against `agreement.follows_greedy`'s limits). Then five faults, each of
which must NOT pass:

- `no_bias`, `no_qk_norm`: the same served tokens judged as if the
  configuration described ANOTHER model, one whose router selects by the
  scores alone, one without the per-head q/k norms (that model's float32
  reference, and its plain bf16 path: the program's uncached forward over
  weights with the bias zeroed, the norm weights ones). All four verdicts;
- `state_zeroed_at_every_piece`, `state_not_reset_on_reuse`: the PROGRAM
  with a fault, serving the same prompts again from a new engine over the
  same weights: `core.model._conv_ctx` hands every admission forward
  `fresh` rows (a prefill piece then starts from zeros: the `carry` verdict
  must fail; `long`, whose 256 steps dilute the few tokens that see it, read
  0.85 of its limit on the chip, PR 38) or none (a re-used row then starts
  from what its last occupant left: the `reuse` verdict must fail; `long`
  and `short` run in rows nobody used before and still pass). The jitted
  programs are keyed by the configuration, so the faulty engine's differs
  in a field nothing reads, and it is warmed up like the sound one (64
  requests at once against programs still compiling were reset by the
  gateway's listener);
- `float8`: the plain path's argmax with every weight rounded to e4m3 under
  a scale of its own tensor (`greedy_control.to_float8`), the nearest
  precision below the configuration's bf16, against the sound reference.
  The weights are rounded where they lie, so this comes last.

A line a reading, as `greedy_control.py` prints them; exit 0 when every
sound reading passes and every control is refused where it must be. Writes
`chiprun_out/state_control_<workload>_<seed>.json`. Off the chip (a
rehearsal cell on the CPU) it runs the same and says that it is no reading.
"""

from __future__ import annotations

import dataclasses
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

# fault -> (what `fresh` becomes, the verdicts that must fail)
STATE_FAULTS = {
    "state_zeroed_at_every_piece": ("ones_like", ("carry",)),
    "state_not_reset_on_reuse": ("zeros_like", ("reuse",)),
}
EVERY = ("long", "short", "carry", "reuse")
MUST_FAIL = {"no_bias": EVERY, "no_qk_norm": EVERY, "float8": EVERY,
             **{k: v[1] for k, v in STATE_FAULTS.items()}}


def another_model(params, control: str):
    """The weights of the model the control describes (new top-level dicts;
    every other leaf shared)."""
    import jax.numpy as jnp

    layers = dict(params["layers"])
    if control == "no_bias":
        layers["router"] = {**layers["router"],
                            "bias": jnp.zeros_like(layers["router"]["bias"])}
    else:
        layers.update(q_norm=jnp.ones_like(layers["q_norm"]),
                      k_norm=jnp.ones_like(layers["k_norm"]))
    return {**params, "layers": layers}


def serve_with_fault(driver, cell, opts, fault: str, nth: int, params) -> dict:
    """The cell's comparison from a new engine over the SAME weights (a
    second copy does not fit the chip) whose admission forwards take `fresh`
    as the fault says: `keep`, as `driver.start` fills it."""
    import jax.numpy as jnp

    from drivers import serve_ref
    from drivers.rl_ref import substituted
    from harness import model
    from nanorlhf_tpu.core import model as M

    sound_ctx, sound_cfg = M._conv_ctx, model.model_config
    fresh_to = getattr(jnp, STATE_FAULTS[fault][0])

    def faulty(config, valid=None, fresh=None):
        return sound_ctx(config, valid,
                         None if fresh is None else lambda: fresh_to(fresh()))

    def other_key(config, *args):
        mcfg = sound_cfg(config, *args)
        return dataclasses.replace(
            mcfg, max_position_embeddings=mcfg.max_position_embeddings + nth)

    keep: dict = {}
    with substituted(M, "_conv_ctx", faulty), \
            substituted(model, "model_config", other_key), \
            substituted(serve_ref, "init_weights", lambda *a: params), \
            substituted(driver, "spread", lambda weights, *a: weights):
        served = driver.start(cell, opts, keep)
        served.close()
    del served
    gc.collect()
    return keep


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
    lines = []

    def say(line):      # a line a reading, as it is made
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
        lines.append(line)

    for name, v in keep.items():
        say(reading("sound", name, v["ref"], v["tokens"], v["plain"]))
    for control, flags in (("no_bias", {"bias": False}),
                           ("no_qk_norm", {"qk_norm": False})):
        weights = another_model(params, control)
        for name, v in keep.items():
            args = (v["batch"], v["answers"], v["n"])
            other = reference_logits(*args, **flags)
            plain, _ = plain_logits(weights, *args)
            say(reading(control, name, other, v["tokens"], plain))
            del other, plain
        del weights
    for nth, fault in enumerate(STATE_FAULTS, start=1):
        got = serve_with_fault(driver, cell, opts, fault, nth, params)
        for name, v in got.items():
            if isinstance(v, dict) and "ref" in v:
                say(reading(fault, name, v["ref"], v["tokens"], v["plain"]))
        del got
        gc.collect()
    params = greedy_control.to_float8(params)
    for name, v in keep.items():
        low, _ = plain_logits(params, v["batch"], v["answers"], v["n"])
        say(reading("float8", name, v["ref"], low.argmax(axis=-1), v["plain"]))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"state_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    sound = sound_ok and all(ok for (c, _), ok in by.items() if c == "sound")
    refused = all(not by[(control, verdict)]
                  for control, verdicts in MUST_FAIL.items()
                  for verdict in verdicts)
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
