"""What the greedy comparison of a `serve_ssm_ref` cell is worth, read once,
on the chip, outside the benchmark:

    python benchmark/tools/ssm_control.py <workload> <seed> [<BENCHMARK.json>]
                                          [--only <control>]

The cell's set-up as the benchmark makes it (`drivers/serve_ssm_ref.start`:
the engine, the warm-up, the served greedy answers of the four verdicts
`long`, `short`, `carry` and `reuse`, the float32 reference and the plain
bf16 path at the answers' positions: the SOUND reading, each verdict's gaps
against `agreement.follows_greedy`'s limits, and the two readings of the
recurrent state against the reference's). Then five broken programs,
each of which must NOT pass:

- `no_mixer`, `no_attention`, `no_mup`: the same served tokens judged as if
  the configuration described ANOTHER model: one whose layers have no
  state-space branch, one without the attention branch, one whose input
  projection's five parts go unscaled (that model's float32 reference, and
  its plain bf16 path: the program's uncached forward over weights with the
  branch's output projection zeroed, or under a `ModelConfig` whose
  `ssm_multipliers` are ones). Every verdict must fail;
- `state_not_carried`: the PROGRAM with a fault, serving the same prompts
  again from a new engine over the same weights: `core.model._conv_ctx`
  hands every admission forward `fresh` rows, so a prefill piece starts both
  state leaves from zeros. The `carry` verdict must fail (a last piece of
  one to three tokens sees little else);
- `state_bf16`: the program with the recurrent state KEPT in bfloat16
  (`core.model._state_group` makes the leaf so; the update still computes in
  float32 and rounds what it stores, every token): the nearest precision
  below the float32 the configuration states. The four token verdicts
  CANNOT tell it from float32 at the cell's sizes (they read 0.12-0.38 of
  their limits, as the sound run does, my chip runs, PR 49: the step's
  other bfloat16 values round as coarsely). The driver's reading of the
  state itself does (`state_long`, `state_carry`: `serve_ssm_ref.
  state_reading`, the engine's state of the row against the reference's
  after the same tokens, the first layer's slowest heads under
  `STATE_LIMIT`): BOTH must fail. `serving/state_bytes_per_row` against the
  file's stands beside them, as the driver's `correct` also needs it.

The jitted programs are keyed by the configuration, so a faulty engine's
differs in a field nothing reads, and it is warmed up like the sound one. A
line a reading, as `greedy_control.py` prints them; exit 0 when every sound
reading passes and every control is refused where it must be. Writes
`chiprun_out/ssm_control_<workload>_<seed>.json`. Off the chip (a rehearsal
cell on the CPU) it runs the same and says that it is no reading.
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

EVERY = ("long", "short", "carry", "reuse")
STATE = ("state_long", "state_carry")
BYTES = "state_bytes_per_row"
# control -> (the readings, how many of them must fail: all or any)
MUST_FAIL = {"no_mixer": (EVERY, all), "no_attention": (EVERY, all),
             "no_mup": (EVERY, all), "state_not_carried": (("carry",), all),
             "state_bf16": (STATE, all)}
# control -> (the reference's `without`, the weights' zeroed kernel, the
# plain path's other `ModelConfig` fields)
OTHER_MODELS = {
    "no_mixer": (("mixer",), ("ssm", "out_proj"), {}),
    "no_attention": (("attention",), ("o_proj",), {}),
    "no_mup": (("mup",), None, {"ssm_multipliers": (1.0,) * 5}),
}


def another_model(params, zeroed):
    """The weights of the model the control describes: the branch's output
    projection zeroed (new dicts down to it; every other leaf shared)."""
    import jax.numpy as jnp

    if zeroed is None:
        return params
    layers = dict(params["layers"])
    at = layers
    for name in zeroed[:-1]:
        at[name] = dict(at[name])
        at = at[name]
    at[zeroed[-1]] = {"kernel": jnp.zeros_like(at[zeroed[-1]]["kernel"])}
    return {**params, "layers": layers}


def serve_with_fault(driver, cell, opts, fault: str, nth: int, params) -> dict:
    """The cell's comparison from a new engine over the SAME weights (a
    second copy does not fit the chip) with the program's fault: `keep`, as
    `driver.start` fills it."""
    import jax.numpy as jnp

    from drivers import serve_ref
    from drivers.rl_ref import substituted
    from harness import model
    from nanorlhf_tpu.core import model as M

    sound_ctx, sound_cfg, sound_group = (M._conv_ctx, model.model_config,
                                         M._state_group)

    def never_carried(config, valid=None, fresh=None):
        return sound_ctx(config, valid, None if fresh is None
                         else lambda: jnp.ones_like(fresh()))

    def kept_in_bf16(config, rows, dtype):
        ((tail, S),) = sound_group(config, rows, dtype)
        return ((tail, S.astype(jnp.bfloat16)),)

    def other_key(config, *args):
        mcfg = sound_cfg(config, *args)
        return dataclasses.replace(
            mcfg, max_position_embeddings=mcfg.max_position_embeddings + nth)

    broken = (substituted(M, "_conv_ctx", never_carried)
              if fault == "state_not_carried"
              else substituted(M, "_state_group", kept_in_bf16))
    keep: dict = {}
    with broken, substituted(model, "model_config", other_key), \
            substituted(serve_ref, "init_weights", lambda *a: params), \
            substituted(driver, "spread", lambda weights, *a: weights):
        served = driver.start(cell, opts, keep)
        keep[BYTES] = bytes_reading(driver, cell, served.engine, fault)
        served.close()
    del served
    gc.collect()
    return keep


def state_lines(control: str, state: dict) -> list:
    """`check_greedy`'s readings of the recurrent state against the
    reference's, a line each."""
    return [{"control": control, "verdict": "state_" + name,
             "over_limit": reading["first_layer_slow"] / reading["limit"],
             **reading}
            for name, reading in state.items()]


def bytes_reading(driver, cell, engine, control: str) -> dict:
    """The engine's state bytes a row against the file's: what the driver's
    `correct` needs beside the four token verdicts."""
    got = engine.metrics().get("serving/" + BYTES)
    want = driver.state_bytes_per_row(cell.config)
    return {"control": control, "verdict": BYTES, "ok": got == want,
            "engine": got, "file": want}


def main(argv) -> int:
    only = None
    if "--only" in argv:
        at = argv.index("--only")
        only, argv = argv[at + 1], argv[:at] + argv[at + 2:]
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
    sound_bytes = bytes_reading(driver, cell, served.engine, "sound")
    served.close()
    del served
    gc.collect()
    reference_logits = keep.pop("reference_logits")
    plain_logits, params = keep.pop("plain_logits"), keep.pop("params")
    sound_state = keep.pop("state")
    lines = []

    def say(line):      # a line a reading, as it is made
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
        lines.append(line)

    for name, v in keep.items():
        say(reading("sound", name, v["ref"], v["tokens"], v["plain"]))
    for line in state_lines("sound", sound_state):
        say(line)
    say(sound_bytes)
    for control, (without, zeroed, fields) in OTHER_MODELS.items():
        if only not in (None, control):
            continue
        weights = another_model(params, zeroed)
        for name, v in keep.items():
            args = (v["batch"], v["answers"], v["n"])
            other = reference_logits(*args, without=without)
            plain = plain_logits(weights, *args, **fields)
            say(reading(control, name, other, v["tokens"], plain))
            del other, plain
        del weights
    for nth, fault in enumerate(("state_not_carried", "state_bf16"), start=1):
        if only not in (None, fault):
            continue
        got = serve_with_fault(driver, cell, opts, fault, nth, params)
        for name, v in got.items():
            if isinstance(v, dict) and "ref" in v:
                say(reading(fault, name, v["ref"], v["tokens"], v["plain"]))
        for line in state_lines(fault, got["state"]):
            say(line)
        say(got[BYTES])
        del got
        gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"ssm_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    sound = sound_ok and all(ok for (c, _), ok in by.items() if c == "sound")
    refused = all(some(not by[(control, verdict)] for verdict in verdicts)
                  for control, (verdicts, some) in MUST_FAIL.items()
                  if only in (None, control))
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
