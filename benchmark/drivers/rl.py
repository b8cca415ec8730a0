"""Driver for mixes of kind `rl`: one closed-loop RL job.

Builds the configuration's model from its file (harness/model.py), the
launcher's own `RLConfig` (`entrypoints/grpo.build_config()`) with the mix's
sizes, a prompt corpus from the seed (harness/trafficgen.py), and runs
`RLTrainer.train(num_updates=1)` in a loop: one warm-up update (set-up: it
compiles or loads every program), then whole updates until the next would
not fit into `--seconds`. Each update ends in `block_until_ready` inside the
trainer's phases; the harness clocks each call and annotates it
(`bench.update`) in the profiler's trace.

`tokens_per_s` = response tokens generated in the window's whole updates over
the wall seconds of those updates (rollout + reward + scoring + update +
hand-off), whole cell, not divided by chips.

`correct` (checked in set-up or after the window, never inside it): loss,
gradient norm and KL finite on every window update; KL at step 1 within
KL_STEP1_TOL; no program new to the process inside the window; every row ran
its full response or ends in EOS; and per-token logprobs of a seeded sample
of the first batch from the `auto` path against harness/reference.py in
float32 at `highest` precision, held to the measured-in-run rule of
harness/agreement.py.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import time

import numpy as np

from harness import agreement, model, trafficgen
from harness.window import (Meter, RunResult, TraceWindow, annotate,
                            memory_by_device)


def rl_config(cell, opts, mesh_cfg):
    """`entrypoints.grpo.build_config()` with the mix's sizes; everything
    else (KL, clip, lr, LoRA targets, top-p, monolithic contiguous rollout)
    stays as the launcher has it."""
    from nanorlhf_tpu.entrypoints.grpo import build_config

    mix, assumed = cell.traffic, cell.config["assumed"]
    cfg = build_config()
    cfg.seed = int(opts["seed"])
    cfg.sft_model_path = cell.config_name
    cfg.train_dataset_name = "benchmark:" + cell.traffic_name
    cfg.response_length = int(mix["response_length"])
    cfg.temperature = float(mix["temperature"])
    cfg.sample_n = int(mix["sample_n"])
    cfg.per_device_train_batch_size = int(mix["per_device_train_batch_size"])
    cfg.gradient_accumulation_steps = int(mix["gradient_accumulation_steps"])
    cfg.num_mini_batches = int(mix["num_mini_batches"])
    cfg.rollout_page_size = int(mix.get("rollout_page_size", 0))
    cfg.rollout_decode_rows = int(mix.get("rollout_decode_rows", 0))
    cfg.use_lora = True
    cfg.lora_r = int(assumed["lora"]["r"])
    cfg.lora_alpha = int(assumed["lora"]["alpha"])
    cfg.mesh = mesh_cfg
    cfg.total_episodes = int(mix["prompts"]) * 100_000   # never reached
    cfg.save_steps = 10 ** 9                             # beyond the run
    cfg.num_printed_samples = 0
    cfg.output_dir = os.path.join(opts["out_dir"], "trainer")
    shutil.rmtree(cfg.output_dir, ignore_errors=True)    # metrics.jsonl appends
    return cfg


class CountingReward:
    """The rule reward (costs nothing: the share of distinct words in the
    response) that also counts what was generated: the pads at a response's
    end are what it did not generate, and a row that stops early must stop
    on an EOS. (One trailing pad without an EOS is let through: the policy
    can sample the pad id itself as its last token.)"""

    def __init__(self, response_length: int, pad_token: str):
        self.response_length, self.pad_token = response_length, pad_token
        self.tokens, self.bad_rows = [], 0

    def __call__(self, prompts_and_responses, eos_token):
        scores, tokens = [], 0
        for s in prompts_and_responses:
            words = s.split()[-self.response_length:]
            n = len(words)
            while n and words[n - 1] == self.pad_token:
                n -= 1
            tokens += n
            if len(words) - n >= 2 and (n == 0 or words[n - 1] != eos_token):
                self.bad_rows += 1
            scores.append(len(set(words)) / max(len(words), 1))
        self.tokens.append(tokens)
        return np.asarray(scores, np.float32)


def auto_choices(trainer, context: int) -> dict:
    """Which implementation each `auto` resolves to at this run's shapes,
    asked of the functions the model asks (as chip_smoke does)."""
    from nanorlhf_tpu.core.model import use_decode_kernel, use_flash
    from nanorlhf_tpu.ops.fused_logprob import _resolve_impl
    from nanorlhf_tpu.trainer.trainer import fused_logprob_impl

    cfg, impl = trainer.cfg, trainer.mcfg.attention_impl
    total = context + cfg.response_length
    pick = lambda pallas: "pallas" if pallas else "xla"  # noqa: E731
    return {"prefill_attention": pick(use_flash(impl, context)),
            "decode_attention": pick(use_decode_kernel(impl, total)),
            "score_update_attention": pick(use_flash(impl, total)),
            "fused_logprob": _resolve_impl(
                fused_logprob_impl(cfg, trainer.mcfg), False)}


def check_logprobs(trainer, cell, qr, context: int) -> tuple:
    """The first batch's sample through three paths on the current policy:
    `auto` (the trainer's own policy scorer), plain bf16 (XLA attention +
    lax logprob scan) and the float32 reference."""
    import jax
    import jax.numpy as jnp

    from harness import reference
    from nanorlhf_tpu.trainer.trainer import fused_response_logprobs

    trainer.ref_params = trainer.opt_state = None   # the reference needs room
    gc.collect()
    pad, scale = trainer.tokenizer.pad_token_id, trainer.lora_scale
    temperature = trainer.cfg.temperature
    qr = jnp.asarray(qr)
    tested = np.asarray(trainer._policy_score_fn()(trainer.params, qr, context))
    plain_mcfg = dataclasses.replace(trainer.mcfg, attention_impl="xla")
    plain_cfg = dataclasses.replace(trainer.cfg, fused_logprob_impl="lax")
    plain = np.asarray(jax.jit(lambda p, x: fused_response_logprobs(
        p, plain_mcfg, x, x[:, context:], pad, context, plain_cfg,
        lora_scale=scale))(trainer.params, qr))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, x: reference.response_logprobs(
            p, cell.config, x, context, pad, temperature, scale))(
                trainer.params, qr))
    real = np.asarray(qr)[:, context:] != pad
    return agreement.bf16_agreement(tested, plain, ref, real)


def run(cell, opts) -> RunResult:
    import jax

    from nanorlhf_tpu.data import ToyTokenizer
    from nanorlhf_tpu.data.datasets import PromptDataset
    from nanorlhf_tpu.trainer import RLTrainer

    mix, seed, seconds = cell.traffic, int(opts["seed"]), float(opts["seconds"])
    meter = Meter()
    mark0 = meter.mark()
    mesh_cfg, mesh = model.mesh_of(cell.config, cell.chips)
    mcfg = model.model_config(cell.config)
    params = model.init_weights(mcfg, seed, model.dtype_of(cell.config), mesh)
    tokenizer = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    corpus = trafficgen.rl_prompts(mix, seed, mcfg.vocab_size,
                                   tokenizer.pad_token_id)
    dataset = PromptDataset(corpus, tokenizer.pad_token_id)
    cfg = rl_config(cell, opts, mesh_cfg)
    reward = CountingReward(cfg.response_length, tokenizer.pad_token)
    in_use = {"weights": memory_by_device(cell.chips, "bytes_in_use")}
    trainer = RLTrainer(cfg, mcfg, tokenizer, params, dataset, reward, mesh=mesh)
    del params
    in_use["trainer"] = memory_by_device(cell.chips, "bytes_in_use")
    in_use["trainer_peak"] = memory_by_device(cell.chips)
    assert cfg.batch_size == int(mix["prompts"]), (
        f"mix says {mix['prompts']} prompts, the batch hierarchy gives "
        f"{cfg.batch_size}")

    first = {}
    score = trainer._score_chunk_fn()

    def spy(p, ref_p, qr, ctx):
        if not first:
            first.update(qr=np.asarray(qr)[: int(mix["reference_rows"])], ctx=ctx)
        return score(p, ref_p, qr, ctx)

    trainer._score_fn_cached = spy

    def one_update(note: str) -> float:
        t = time.perf_counter()
        with annotate(note, step=trainer.state["global_step"] + 1):
            trainer.train(num_updates=1)
        return time.perf_counter() - t

    # ---- set-up: the warm-up update compiles or loads every program -----
    warm_s = one_update("bench.warmup_update")
    choices = auto_choices(trainer, first["ctx"])
    mark1 = meter.mark()
    print(json.dumps({"phase": "setup", "warmup_update_s": warm_s,
                      "auto": choices, "corpus_digest": trafficgen.digest(corpus),
                      "bytes_in_use": in_use,
                      "peak_by_device": memory_by_device(cell.chips),
                      **Meter.delta(mark0, mark1)}), flush=True)
    tracer = TraceWindow(opts["out_dir"], bool(opts["trace"]))
    reward.tokens.clear()

    # ---- the window: whole updates, never overshooting --seconds --------
    # (the clock that decides is the updates' own seconds, so a traced run,
    # whose profiler takes seconds to start and stop, holds the same updates)
    setup_s = time.time() - opts["t_process_start"]
    durations, last = [], 0.0
    while True:
        if durations and sum(durations) + 1.02 * last > seconds:
            break
        traced = tracer.enabled and len(durations) == 1
        if traced:
            tracer.start()
        last = one_update("bench.update")
        if traced:
            tracer.stop()
        durations.append(last)
    if tracer.enabled and len(durations) == 1:     # a window of one update
        tracer.start()
        one_update("bench.update")                  # traced, not counted
        tracer.stop()
    wall = sum(durations)
    mark2 = meter.mark()
    tokens = list(reward.tokens[: len(durations)])

    # ---- after the window ----------------------------------------------
    with open(os.path.join(cfg.output_dir, "metrics.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if "episode" in r]
    warm_row, rows = rows[0], rows[1: 1 + len(durations)]
    why_not, failed = [], 0
    keys = {"loss": "loss/policy_avg_new", "grad_norm": "policy/grad_norm_new",
            "kl": "objective/kl_old"}
    for r in rows:
        if not all(math.isfinite(r[k]) for k in keys.values()):
            failed += 1
    if failed:
        why_not.append(f"{failed} updates with a non-finite loss, grad norm or KL")
    if len(rows) != len(durations):
        why_not.append(f"{len(durations)} updates, {len(rows)} metric rows")
    if abs(warm_row[keys["kl"]]) >= agreement.KL_STEP1_TOL:
        why_not.append(f"step-1 KL {warm_row[keys['kl']]}")
    window_compile = Meter.delta(mark1, mark2)
    if window_compile["compiles"]:
        why_not.append(f"{window_compile['compiles']} programs new to the "
                       "process inside the window")
    if reward.bad_rows:
        why_not.append(f"{reward.bad_rows} rows stopped short without an EOS")
    if len(durations) < int(mix["min_updates"]):
        why_not.append(f"only {len(durations)} whole updates fit "
                       f"(mix wants {mix['min_updates']})")
    trace = tracer.reduce()
    ok, numerics = check_logprobs(trainer, cell, first["qr"], first["ctx"])
    if not ok:
        why_not.append(f"auto path further from float32 than bf16 explains: "
                       f"{numerics}")
    trainer.close()

    artefacts = {
        "kind": "rl", "cell": cell.name, "seed": seed, "chips": cell.chips,
        "config": cell.config, "traffic": mix, "context": first["ctx"],
        "rows": rows, "warm_row": warm_row, "update_seconds": durations,
        "tokens": tokens, "auto": choices, "logprobs": numerics,
        "compile": {"setup": Meter.delta(mark0, mark1),
                    "window": window_compile},
        "trace": trace, "device_kind": jax.devices()[0].device_kind,
        "peak_by_device": memory_by_device(cell.chips),
    }
    return RunResult(
        correct=not why_not, attempted=len(durations), failed=failed,
        end_to_end={"tokens_per_s": sum(tokens) / wall, "setup_s": setup_s},
        run=artefacts, why_not=why_not)
