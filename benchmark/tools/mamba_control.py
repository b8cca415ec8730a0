"""What the comparison of a `serve_mamba_ref` cell is worth, read once, on
the chip, outside the benchmark:

    python benchmark/tools/mamba_control.py <workload> <seed> [<BENCHMARK.json>]
                                            [--only <control>[,<control>...]]

The cell's set-up as the benchmark makes it (`drivers/serve_mamba_ref.start`:
the engine, the warm-up, the served greedy answers of the four verdicts
`long`, `short`, `carry` and `reuse`, the float32 reference and the plain
bf16 path at the answers' positions: the SOUND reading, each verdict's gaps
against `agreement.follows_greedy`'s limits, and the two readings of the
recurrent state against the reference's). Then nine broken programs, each of
which must NOT pass (`ssm_control.py`'s two kinds, its helpers by import):

- the same served tokens judged as if the configuration described ANOTHER
  model (that model's float32 reference, and its plain bf16 path: the
  program's uncached forward over changed weights, under a changed
  `ModelConfig`, or with `core.model._gated_norm` in another order):
  `no_attention` (the attention layer's output projection zeroed),
  `no_shared_expert` (its down projection zeroed), `rope` (rotary applied on
  the attention layer: `rope_layout` of ones), `sqrt_scale` (the scale
  1 / sqrt(head_dim) in `attention_multiplier`'s place), `residual_moe` (the
  residual multiplier dropped on the mixture's branch: its down projections
  times 1 / 0.22), `no_renormalise` (the softmax over all 72 logits, the
  chosen ten not renormalised: `norm_topk_prob` False) and
  `norm_before_gate` (the mixer's norm BEFORE its gate). The `long` verdict
  must fail for each;
- `state_not_carried`: the PROGRAM with a fault, serving the same prompts
  again from a new engine over the same weights: every admission forward
  starts both state leaves from zeros. The `carry` verdict must fail;
- `state_bf16`: the program with the recurrent state KEPT in bfloat16, the
  nearest precision below the float32 the configuration states: the driver's
  two readings of the state itself (`state_long`, `state_carry`, under
  `serve_mamba_ref.STATE_LIMIT`) must BOTH fail, and
  `serving/state_bytes_per_row` is then not the file's.

A line a reading; exit 0 when every sound reading passes and every control
is refused where it must be. Writes
`chiprun_out/mamba_control_<workload>_<seed>.json`. Off the chip (a
rehearsal cell on the CPU) it runs the same and says that it is no reading.
"""

from __future__ import annotations

import contextlib
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

ssm_control = cells.load_module(os.path.join(HERE, "ssm_control.py"),
                                "bench_tool_ssm_control")
reading, state_lines = ssm_control.reading, ssm_control.state_lines
BYTES, STATE = ssm_control.BYTES, ssm_control.STATE

# control -> (the reference's `without`, [(the kernel's path under `layers`,
# what it is multiplied by)], the plain path's other `ModelConfig` fields)
OTHER_MODELS = {
    "no_attention": (("attention",), [(("o_proj",), 0.0)], {}),
    "no_shared_expert": (("shared_expert",),
                         [(("shared_expert", "down_proj"), 0.0)], {}),
    "rope": (("rope",), [], "rope_layout"),
    "sqrt_scale": (("attention_multiplier",), [], {"attention_multiplier": 0.0}),
    "residual_moe": (("residual_multiplier_moe",),
                     [(("shared_expert", "down_proj"), "1/residual"),
                      (("experts", "down_proj"), "1/residual")], {}),
    "no_renormalise": (("renormalise",), [], {"norm_topk_prob": False}),
    "norm_before_gate": (("gate_before_norm",), [], {}),
}
FAULTS = ("state_not_carried", "state_bf16")
# control -> the readings that must ALL fail
MUST_FAIL = {**{name: ("long",) for name in OTHER_MODELS},
             "state_not_carried": ("carry",), "state_bf16": STATE}


def another_model(params, changes, residual: float):
    """The weights of the model the control describes: each named kernel
    times its factor (new dicts down to it; every other leaf shared; one
    fused program a kernel: a float32 copy of the experts' 2.3 GB of down
    projections does not fit beside the weights)."""
    import jax
    import jax.numpy as jnp

    if not changes:
        return params
    scaled = jax.jit(lambda w, f: (w.astype(jnp.float32) * f).astype(w.dtype))
    layers = dict(params["layers"])
    for path, factor in changes:
        factor = 1.0 / residual if factor == "1/residual" else factor
        at = layers
        for name in path[:-1]:
            at[name] = dict(at[name])
            at = at[name]
        at[path[-1]] = {"kernel": scaled(at[path[-1]]["kernel"],
                                         jnp.float32(factor))}
    return {**params, "layers": layers}


def norm_then_gate(config, y, z, weight):
    """`core.model._gated_norm` in the other order."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, I = y.shape
    y = y.reshape(B, T, config.ssm_groups, I // config.ssm_groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    return (y.reshape(B, T, I) * weight.astype(f32)
            * jax.nn.silu(z.astype(f32)))


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
    from drivers.rl_ref import substituted
    from nanorlhf_tpu.core import model as M

    cell = cells.load_cell(bench_file, workload)
    driver = cells.load_driver(cell)
    out_dir = os.path.join(BENCH, "out", "control_" + workload)
    os.makedirs(out_dir, exist_ok=True)
    opts = {"seed": seed, "seconds": 0.0, "trace": False, "out_dir": out_dir,
            "t_process_start": T0, "traffic_file": cell.traffic_file}
    keep: dict = {}
    gc.unfreeze()
    served = driver.start(cell, opts, keep)
    sound_ok = served.greedy_ok
    sound_bytes = ssm_control.bytes_reading(driver, cell, served.engine, "sound")
    served.close()
    del served
    gc.unfreeze()
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
    layers = int(cell.config["num_hidden_layers"])
    for control, (without, changes, fields) in OTHER_MODELS.items():
        if only is not None and control not in only:
            continue
        if fields == "rope_layout":
            fields = {"rope_layout": (1,) * layers}
        weights = another_model(params, changes,
                                float(cell.config["residual_multiplier"]))
        other_order = (substituted(M, "_gated_norm", norm_then_gate)
                       if control == "norm_before_gate"
                       else contextlib.nullcontext())
        v = keep["long"]        # (the verdict the control must fail)
        args = (v["batch"], v["answers"], v["n"])
        other = reference_logits(*args, without=without)
        with other_order, driver.plain_experts():
            plain = plain_logits(weights, *args, **fields)
        say(reading(control, "long", other, v["tokens"], plain))
        del other, plain, weights
    for nth, fault in enumerate(FAULTS, start=1):
        if only is not None and fault not in only:
            continue
        gc.unfreeze()
        got = ssm_control.serve_with_fault(driver, cell, opts, fault, nth,
                                           params)
        for name, v in got.items():
            if isinstance(v, dict) and "ref" in v:
                say(reading(fault, name, v["ref"], v["tokens"], v["plain"]))
        for line in state_lines(fault, got["state"]):
            say(line)
        say(got[BYTES])
        del got
        gc.unfreeze()
        gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"mamba_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    sound = sound_ok and all(ok for (c, _), ok in by.items() if c == "sound")
    refused = all(not by[(control, verdict)]
                  for control, verdicts in MUST_FAIL.items()
                  if only is None or control in only for verdict in verdicts)
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
