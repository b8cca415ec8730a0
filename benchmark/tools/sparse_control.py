"""What the comparison of a `serve_sala_ref` cell is worth, read once, on the
chip, outside the benchmark:

    python benchmark/tools/sparse_control.py <workload> <seed> [<BENCHMARK.json>]
                                             [--only <control>]

The cell's set-up as the benchmark makes it (`drivers/serve_sala_ref.start`:
the engine, the warm-up, the served greedy answers of the six verdicts
`long`, `short`, `steady`, `cross`, `carry` and `reuse`, the float32
reference and the
plain bf16 path at the answers' positions: the SOUND reading, each verdict's
gaps against `agreement.follows_greedy`'s limits, the three readings of the
lightning state and the selection's). Then broken programs, each of which
must NOT pass the verdict named for it:

- `newest_64`, `no_group_sum`, `dense_len_ignored`, `no_decay`, `no_r`: the
  same served tokens judged as if the configuration described ANOTHER model:
  one whose sparse layers read the newest `topk` blocks whatever the scores,
  one whose query heads do not sum their scores over a group (the first
  speaks for it), one whose every call selects, one whose lightning state
  never decays, one whose branches join the stream unscaled (that model's
  float32 reference, `without=`; its plain bf16 path: the program's uncached
  forward with `core.sala.select_blocks` replaced, or under a `ModelConfig`
  with `sparse_dense_len` 0, zero log-decays or `scale_depth` 0). `long`
  must fail for the first two and the last two, `cross` for
  `dense_len_ignored` (a prompt under `dense_len` is then read sparse from
  its 4,097th token on; the short rows, under `topk x block_size` tokens,
  choose every block either way);
- `state_not_carried`: the PROGRAM with a fault, serving the same prompts
  again from a new engine over the same weights: `core.model._conv_ctx`
  hands every admission forward `fresh` rows. `carry` must fail, and the
  state's reading after it (`state_carry`, about 1 from the reference's);
- `state_bf16`: the program with the lightning state KEPT in bfloat16
  (`core.model._state_group` makes the leaf so). The token verdicts need not
  tell it; the state's reading after `steady`'s 255 decode steps
  (`state_steady`: the engine's distance from the reference's state over
  the plain cached path's, under `STATE_SLACK`) must fail, and
  `serving/state_bytes_per_row` is not the file's;
- `pooling_shifted`: the selection's own verdict judged as if the program
  pooled its compressed scores one block off (`core.sala.select_blocks`
  over the engine's cached compressed keys moved by a block's worth, the
  same cached keys and queries as the sound reading): `selection` must
  fail.

NOT among them: compressed keys not written at decode. A served request
adds at most `max_new_tokens` = 1,024 tokens, fewer than the local window's
2,048, so a compressed key completed by a decode step lies in a block the
query is forced to read and no selection of the cell consults it; the CPU
tests hold the cache's compressed keys, decode's among them, to the
reference's (tests/test_sala.py).

A line a reading, as `greedy_control.py` prints them; exit 0 when every
sound reading passes and every control is refused where it must be. Writes
`chiprun_out/sparse_control_<workload>_<seed>.json`. Off the chip (a
rehearsal cell on the CPU) it runs the same and says that it is no reading.
"""

from __future__ import annotations

import contextlib
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
ssm_control = cells.load_module(os.path.join(HERE, "ssm_control.py"),
                                "bench_tool_ssm_control")
reading = greedy_control.reading
state_lines, bytes_reading = ssm_control.state_lines, ssm_control.bytes_reading
BYTES = ssm_control.BYTES

STATE = ("state_steady",)
# control -> (the verdicts judged, all of which must fail)
MUST_FAIL = {"newest_64": ("long",), "no_group_sum": ("long",),
             "dense_len_ignored": ("cross",), "no_decay": ("long", "short"),
             "no_r": ("long", "short"),
             "state_not_carried": ("carry", "state_carry"),
             "state_bf16": STATE, "pooling_shifted": ("selection",)}
# control -> (the reference's `without`, the plain path's other fields)
OTHER_MODELS = {
    "newest_64": (("selection",), {}),
    "no_group_sum": (("group_sum",), {}),
    "dense_len_ignored": (("dense_len",), {"sparse_dense_len": 0}),
    "no_decay": (("decay",), {}),
    "no_r": (("scale_depth",), {"scale_depth": 0.0}),
}


def other_program(control: str):
    """The context under which the program's plain path is the control's
    model, where a `ModelConfig` field does not say it."""
    import jax.numpy as jnp
    import numpy as np

    from drivers.rl_ref import substituted
    from nanorlhf_tpu.core import sala
    from nanorlhf_tpu.core.config import ModelConfig

    sound = sala.select_blocks

    def newest(config, q, kc, t):
        k = min(config.sparse_topk,
                -(-kc.shape[2] * config.sparse_kernel_stride
                  // config.sparse_block_size))
        idx = (t // config.sparse_block_size)[:, None, :, None] - jnp.arange(
            k, dtype=jnp.int32)
        idx = jnp.broadcast_to(idx, (q.shape[0], kc.shape[1]) + idx.shape[2:])
        return jnp.maximum(idx, 0), (idx >= 0) & (t >= 0)[:, None, :, None]

    def ungrouped(config, q, kc, t):
        B, H, Tq, hd = q.shape
        KV = kc.shape[1]
        first = jnp.broadcast_to(
            q.reshape(B, KV, H // KV, Tq, hd)[:, :, :1],
            (B, KV, H // KV, Tq, hd)).reshape(q.shape)
        return sound(config, first, kc, t)

    def shifted(config, q, kc, t):
        per = config.sparse_block_size // config.sparse_kernel_stride
        return sound(config, q, jnp.roll(kc, per, axis=2), t)

    if control == "pooling_shifted":
        return substituted(sala, "select_blocks", shifted)
    if control == "newest_64":
        return substituted(sala, "select_blocks", newest)
    if control == "no_group_sum":
        return substituted(sala, "select_blocks", ungrouped)
    if control == "no_decay":
        decays = ModelConfig.lightning_log_decays
        return substituted(
            ModelConfig, "lightning_log_decays",
            lambda self, **kw: np.zeros_like(decays(self, **kw)))
    return contextlib.nullcontext()


def serve_with_fault(driver, cell, opts, fault: str, nth: int, params) -> dict:
    """The cell's comparison from a new engine over the SAME weights with
    the program's fault: `keep`, as `driver.start` fills it."""
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
        ((S,),) = sound_group(config, rows, dtype)
        return ((S.astype(jnp.bfloat16),),)

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
    sound_ok, sound_detail = served.greedy_ok, served.greedy
    sound_bytes = bytes_reading(driver, cell, served.engine, "sound")
    served.close()
    del served
    gc.collect()
    reference_logits = keep.pop("reference_logits")
    plain_logits, params = keep.pop("plain_logits"), keep.pop("params")
    sound_state, selection = keep.pop("state"), keep.pop("selection")
    lines = []

    def say(line):      # a line a reading, as it is made
        line.update(workload=workload, seed=seed, a_reading=on_chip)
        print(json.dumps(line), flush=True)
        lines.append(line)

    for name, v in keep.items():
        say(reading("sound", name, v["ref"], v["tokens"], v["plain"]))
    for line in state_lines("sound", sound_state):
        say(line)
    say({"control": "sound", "verdict": "selection",
         **sound_detail["selection"]})
    say(sound_bytes)
    for nth, (control, (without, fields)) in enumerate(OTHER_MODELS.items(),
                                                       start=10):
        if only not in (None, control):
            continue
        for name in MUST_FAIL[control]:
            v = keep[name]
            args = (v["batch"], v["answers"], v["n"], v["longest"])
            other = reference_logits(*args, without=without)
            with other_program(control):    # (a program of its own key)
                plain = plain_logits(
                    params, *args, rope_theta=1e4 + nth, **fields)
            say(reading(control, name, other, v["tokens"], plain))
            del other, plain
    if only in (None, "pooling_shifted"):
        from harness import model

        with other_program("pooling_shifted"):
            say({"control": "pooling_shifted", "verdict": "selection",
                 **driver.selection_reading(
                     params, model.model_config(cell.config), cell,
                     selection["ids"], int(cell.traffic["pad_token_id"]),
                     selection["last"], selection["kc"])})
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
                           f"sparse_control_{workload}_{seed}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    sound = sound_ok and all(ok for (c, _), ok in by.items() if c == "sound")
    refused = all(not by[(control, verdict)]
                  for control, verdicts in MUST_FAIL.items()
                  if only in (None, control) for verdict in verdicts)
    return 0 if sound and refused else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
