"""What the comparison of a `serve_loop_ref` cell is worth, read once, on the
chip, outside the benchmark:

    python benchmark/tools/loop_control.py <workload> <seed> [<BENCHMARK.json>] [--only <control>]
    python benchmark/tools/loop_control.py <workload> <seed> [<BENCHMARK.json>] --witness <branch norm>

The cell's set-up as the benchmark makes it (`drivers/serve_loop_ref.start`:
the cached path's logits, the engine, the warm-up, the served greedy answers
of the verdicts `steady` and `full`, the float32 reference and the plain bf16
path at the answers' positions: the SOUND reading, each verdict's gaps against
`agreement.follows_greedy`'s limits). Then six faults, each of which must NOT
pass (a control is refused when ANY of its verdicts fails, as `correct` is):

- `one_pass_fewer`, `no_pass_norm`, `no_branch_norms`: the same served tokens
  judged as if the configuration described ANOTHER model (three passes where
  the file has four;
  the final norm once, after the last pass only; two norms a layer): that
  model's float32 reference (`reference_ouro`'s keywords) and its plain bf16
  path (the program's uncached forward under that model's `ModelConfig`, or
  with the norm that closes a pass laid out of `core/model.py`);
- `shared_slot`, `decode_reads_pass_1`: the PROGRAM with a fault in its
  cache index, serving the same prompts again from a new engine over the
  same weights: `core.model._pass_cache_offset` answers 0 for every pass (the
  passes of a layer share one slot, the paper's "last-step reuse"), or for a
  decode step only (it reads and writes pass 1's slots in every pass while
  the admission wrote all of them). The jitted programs are keyed by the
  configuration, so the faulty engine's differs in a field nothing reads;
- `float8`: the plain path's argmax with every weight rounded to e4m3 under
  a scale of its own tensor (`greedy_control.to_float8`), the nearest
  precision below the configuration's bf16, against the sound reference.
  The weights are rounded where they lie, so this comes last.

`--witness <w>` reads something else, and builds no engine: whether a gap
between the bf16 paths is PRECISION or a fault of the cache. The cell's
weights with `assumed.init.branch_norm` = `w` (1.0: the spread at which a
sound program failed `full` on seed 5, PERF.md PR 55), and one row from a
prompt of `engine.prompt_len` tokens to its `engine.max_new_tokens`-th new
token through the row's PAGES under a table (`serve_loop_ref.check_cached`,
`paged`: the session's layout, the in-place kernel on a TPU, every page of
every cache layer written and read), fed its own argmax, against the
reference: once in the configuration's dtype and once with the SAME weights
and the cache in float32 under "highest". Where the float32 program lies on
the reference (a gap of rounding, no flips beyond ties) the cache's index,
table and masks are right at these sizes, and what the bf16 reading shows is
precision. Exit 0 when it does (`WITNESS_GAP`).

A line a reading, as `greedy_control.py` prints them; exit 0 when every sound
reading passes and every control is refused. Writes
`chiprun_out/loop_control_<workload>_<seed>.json`. Off the chip (a rehearsal
cell on the CPU) it runs the same and says that it is no reading.
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

from drivers.rl_ref import substituted        # noqa: E402
from harness import cell as cells             # noqa: E402

greedy_control = cells.load_module(os.path.join(HERE, "greedy_control.py"),
                                   "bench_tool_greedy_control")
reading = greedy_control.reading

VERDICTS = ("steady", "full")
# control -> (the reference's flags, the other model's ModelConfig fields)
OTHER_MODELS = {
    "one_pass_fewer": ({"passes": -1}, {"loop_passes": -1}),   # (filled in)
    "no_pass_norm": ({"pass_norm": False}, {}),
    "no_branch_norms": ({"attn_norm": False, "mlp_norm": False},
                        {"branch_norms": False}),
}
CACHE_FAULTS = ("shared_slot", "decode_reads_pass_1")
CONTROLS = (*OTHER_MODELS, *CACHE_FAULTS, "float8")


@contextlib.contextmanager
def without_the_norm_between_passes():
    """`core/model.py` with the final norm applied once, by the head, and not
    at the close of each pass."""
    from nanorlhf_tpu.core import model as M

    once = lambda config, params, x: M.rms_norm(     # noqa: E731
        x, params["norm"], config.rms_norm_eps)
    with substituted(M, "_close_pass", lambda config, params, y: y), \
            substituted(M, "_final_norm", once):
        yield


@contextlib.contextmanager
def cache_fault(name: str):
    """`core/model.py` with a fault in a looped model's cache index."""
    from nanorlhf_tpu.core import model as M

    none = lambda config, t: t * 0      # noqa: E731
    if name == "shared_slot":
        with substituted(M, "_pass_cache_offset", none):
            yield
        return
    sound, run = M._pass_cache_offset, M._run_layers

    def decode_reads_pass_1(config, params, x, *args, **kwargs):
        caches = kwargs.get("kv_caches", args[3] if len(args) > 3 else None)
        step = x.shape[1] == 1 and caches is not None
        with substituted(M, "_pass_cache_offset", none if step else sound):
            return run(config, params, x, *args, **kwargs)

    with substituted(M, "_run_layers", decode_reads_pass_1):
        yield


WITNESS_GAP = 1e-3      # nats, mean |logit - reference| of the float32 program.
# Between its two readings at Ouro-2.6B's sizes (my chip run, PR 55): the sound
# float32 program reads 8.0e-6 (branch norms at weight 1; 1.1e-6 at 0.3), the
# nearest precision below, bf16, 0.029-0.164; a wrong slot, page or mask reads
# whole nats (the controls)


def witness(cell, driver, seed: int, branch_norm: float, on_chip: bool) -> int:
    """The two readings of `--witness`, a line each."""
    import jax
    import jax.numpy as jnp

    from harness import model

    cell.config["assumed"]["init"]["branch_norm"] = branch_norm
    mcfg = model.model_config(cell.config)
    params = driver.weights_of(cell, mcfg, seed)
    steps = int(cell.traffic["engine"]["max_new_tokens"])

    def in_float32(tree):
        """The same values, a leaf at a time where it lay (both trees at
        once are the chip's whole memory)."""
        for key, leaf in tree.items():
            if isinstance(leaf, dict):
                in_float32(leaf)
            elif leaf.dtype != jnp.float32:
                tree[key] = leaf.astype(jnp.float32)
                leaf.delete()

    lines = []
    for dtype in (None, jnp.float32):
        if dtype is not None:
            in_float32(params)
        with jax.default_matmul_precision("highest" if dtype else "default"):
            ok, detail = driver.check_cached(params, mcfg, cell, seed,
                                             steps=steps, paged=True)
        lines.append({"control": "witness", "branch_norm": branch_norm,
                      "dtype": str(params["norm"].dtype), "ok": bool(ok),
                      **detail})
    at_reference = lines[1]["tested_vs_float32"]["mean_abs"] < WITNESS_GAP
    for line in lines:
        line.update(workload=cell.name, seed=seed, a_reading=on_chip,
                    float32_at_reference=bool(at_reference))
        print(json.dumps(line), flush=True)
    return 0 if at_reference else 1


def main(argv) -> int:
    only = branch_norm = None
    if "--only" in argv:
        at = argv.index("--only")
        only, argv = argv[at + 1], argv[:at] + argv[at + 2:]
    if "--witness" in argv:
        at = argv.index("--witness")
        branch_norm, argv = float(argv[at + 1]), argv[:at] + argv[at + 2:]
    workload, seed = argv[0], int(argv[1])
    bench_file = argv[2] if len(argv) > 2 else os.path.join(ROOT, "BENCHMARK.json")
    wanted = [c for c in CONTROLS if only in (None, c)]
    import jax

    on_chip = jax.devices()[0].platform == "tpu"
    if on_chip:
        from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()
    cell = cells.load_cell(bench_file, workload)
    driver = cells.load_driver(cell)
    if branch_norm is not None:
        return witness(cell, driver, seed, branch_norm, on_chip)
    out_dir = os.path.join(BENCH, "out", "control_" + workload)
    os.makedirs(out_dir, exist_ok=True)
    opts = {"seed": seed, "seconds": 0.0, "trace": False, "out_dir": out_dir,
            "t_process_start": T0, "traffic_file": cell.traffic_file}

    def served_by(other_model=None, cached=False):
        """What the verdicts of one engine were made of; the engine closed."""
        keep: dict = {}
        served = driver.start(cell, opts, keep, other_model=other_model,
                              cached=cached)
        ok = served.greedy_ok
        served.close()
        del served
        gc.unfreeze()       # (`start` froze what it built: the pool is in it)
        gc.collect()
        return keep, ok

    keep, sound_ok = served_by(cached=True)
    lines = [reading("sound", name, keep[name]["ref"], keep[name]["tokens"],
                     keep[name]["plain"]) for name in VERDICTS]
    lines[0]["setup_ok"] = bool(sound_ok)
    reference_logits, plain_logits = keep["reference_logits"], keep["plain_logits"]
    for control in (c for c in wanted if c in OTHER_MODELS):
        flags, other_model = OTHER_MODELS[control]
        if control == "one_pass_fewer":
            fewer = int(cell.config["total_ut_steps"]) - 1
            flags, other_model = {"passes": fewer}, {"loop_passes": fewer}
        for name in VERDICTS:
            v = keep[name]
            args = (v["batch"], v["answers"], v["n"])
            other = reference_logits(*args, **flags)
            if control == "no_pass_norm":
                with without_the_norm_between_passes():
                    plain = plain_logits(keep["params"], *args)
            else:
                plain = plain_logits(keep["params"], *args, **other_model)
            lines.append(reading(control, name, other, v["tokens"], plain))
            del other, plain
    sound = {name: keep[name] for name in VERDICTS}
    del reference_logits
    position = int(cell.config["max_position_embeddings"])
    for i, control in enumerate(c for c in wanted if c in CACHE_FAULTS):
        del keep, plain_logits      # (the weights, before the next are built)
        gc.collect()
        with cache_fault(control):
            keep, _ = served_by({"max_position_embeddings": position + 1 + i})
        lines += [reading(control, name, keep[name]["ref"], keep[name]["tokens"],
                          keep[name]["plain"]) for name in VERDICTS]
        plain_logits = keep["plain_logits"]
    if "float8" in wanted:
        params = greedy_control.to_float8(keep.pop("params"))
        for name, v in sound.items():
            low = plain_logits(params, v["batch"], v["answers"], v["n"])
            lines.append(reading("float8", name, v["ref"], low.argmax(axis=-1),
                                 v["plain"]))
    for line in lines:
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"loop_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    ok_sound = all(ln["ok"] for ln in lines if ln["control"] == "sound") \
        and sound_ok
    refused = all(any(not ln["ok"] for ln in lines if ln["control"] == c)
                  for c in wanted)
    return 0 if ok_sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
