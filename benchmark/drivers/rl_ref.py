"""Driver for mixes of kind `rl_ref`: `drivers/rl.py`'s closed-loop RL job,
its numerics held to the float32 reference the CONFIGURATION names.

`rl.py` imports `harness/reference.py` (the dense Qwen2 forward) by name.
This driver runs the same loop (every function is `rl.py`'s, by import) with
one substitution: `check_logprobs` takes the module under `harness/` that
the configuration file's `reference` key names (`reference_olmoe` for
OLMoE), with the same three signatures. So a further architecture brings a
reference and a configuration file, not a driver.

A mix may say `"eos_unreachable": true`: the weights' output column of the
tokenizer's EOS id (and of its pad id) is then zeroed, so its logit is 0 where the launcher's
nucleus sampling keeps the 64 largest of V logits: no row can stop early and
every update generates the same tokens, as a 152k-row vocabulary gives the
dense cells for free (one EOS in 150,000 tokens). With OLMoE's 50k rows a
random policy ended ~0.65 rows an update early, and `tokens_per_s`, which
counts a stopped row by its real tokens, swung with the seed by 0.44 %
(spread of six runs, my chip run, PR 27) against the 0.5 % a cell is admitted
at, while the updates' seconds spread by 0.03 %.

Two more rules, both about expert models (a configuration without
`num_experts` skips them):

- it fails at once, with a non-zero exit and before any weights are built,
  when the program's `ModelConfig` does not carry the file's `num_experts`:
  a program that drops the key would otherwise run a dense model of the
  expert's width under the configuration's name for the whole window;
- `correct` also needs `moe/dropped_tokens == 0` on every window row, and
  the run's artefacts gain `moe_trace`, the device trace's self time by
  `moe.*` scope (harness/moe_trace.py), for `expert_layer_share`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import os
import sys

import numpy as np

from drivers import rl
from harness import agreement, model

INIT_WEIGHTS = model.init_weights


def check_logprobs(trainer, cell, qr, context: int) -> tuple:
    """`rl.check_logprobs` with the configuration's reference: the first
    batch's sample through `auto` (the trainer's own policy scorer), plain
    bf16 (XLA attention + lax logprob scan) and the float32 reference."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.trainer.trainer import fused_response_logprobs

    reference = importlib.import_module("harness." + cell.config["reference"])
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


def refuse_a_program_without_the_experts(cell) -> None:
    want = cell.config.get("num_experts")
    if not want:
        return
    got = getattr(model.model_config(cell.config), "num_experts", None)
    if got != want:
        print(f"benchmark: configuration {cell.config_name!r} has num_experts "
              f"{want}, the program's ModelConfig carries {got!r}: this "
              "program would run another model under the configuration's "
              "name. Nothing was built.", file=sys.stderr)
        raise SystemExit(4)


def init_weights_without_eos(mcfg, seed: int, dtype, mesh=None):
    """`harness/model.init_weights`, then the output columns of the EOS id
    and of the pad id zeroed (the rows of the embedding where the head is
    tied): a sampled pad at a row's end would count as not generated too."""
    import jax.numpy as jnp

    from nanorlhf_tpu.data import ToyTokenizer

    params = INIT_WEIGHTS(mcfg, seed, dtype, mesh)
    tokenizer = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    ids = jnp.asarray([tokenizer.eos_token_id, tokenizer.pad_token_id])
    if "lm_head" in params:
        params["lm_head"] = params["lm_head"].at[:, ids].set(0)
    else:
        params["embed_tokens"] = params["embed_tokens"].at[ids].set(0)
    return params


@contextlib.contextmanager
def substituted(module, name: str, value):
    """`module.name` is `value` inside the block: `rl.run` looks both of its
    helpers up when it calls them."""
    saved = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, saved)


def run(cell, opts):
    refuse_a_program_without_the_experts(cell)
    with contextlib.ExitStack() as stack:
        stack.enter_context(substituted(rl, "check_logprobs", check_logprobs))
        if cell.traffic.get("eos_unreachable"):
            stack.enter_context(substituted(
                model, "init_weights", init_weights_without_eos))
        result = rl.run(cell, opts)
    run_ = result.run
    run_["kind"] = "rl_ref"
    if cell.config.get("num_experts"):
        dropped = [r.get("moe/dropped_tokens") for r in run_["rows"]]
        if any(d != 0 for d in dropped):
            result.why_not.append(f"moe/dropped_tokens per window row: {dropped}")
            result.correct = False
        if run_.get("trace") is not None:
            from harness import moe_trace, xplane

            path = xplane.newest_xplane(os.path.join(opts["out_dir"], "trace"))
            run_["moe_trace"] = moe_trace.scope_seconds(path) if path else None
    return result
