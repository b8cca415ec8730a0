"""Shared launcher plumbing: model/tokenizer/dataset/reward resolution.

The reference keeps "ALL setting is on the file you run" (`README.md:34`) —
each launcher is a config literal plus loading code. These helpers keep the
launchers that thin while handling the environments a TPU build actually
meets: real HF checkpoints when present on disk, a fully offline demo mode
(random-init model + toy tokenizer + synthetic prompts) otherwise, so every
launcher runs end-to-end even with zero egress.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.core.params import load_hf_checkpoint
from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset, load_tokenizer
from nanorlhf_tpu.parallel import make_mesh, param_sharding_rules
from nanorlhf_tpu.rewards import make_rule_reward
from nanorlhf_tpu.rewards.builders import make_torch_rm_reward
from nanorlhf_tpu.trainer import RLConfig, RLTrainer


def resolve_model(sft_model_path: str, seed: int = 0, attention_impl: str = "auto",
                  mesh=None):
    """(ModelConfig, params, tokenizer): HF checkpoint dir → load it; else an
    offline demo model (1.5B-shaped unless the path says 'tiny', 'llama' or
    'olmoe').

    `mesh`: the offline model is initialised straight into the trainer's
    sharding (one jitted init with `out_shardings`), so no device ever holds
    the whole tree. Without it everything lands on the first device — on a
    four-chip host that one chip then peaks at twice the model while the
    others hold a quarter each."""
    if sft_model_path and os.path.isdir(sft_model_path):
        config, params = load_hf_checkpoint(sft_model_path)
        tokenizer = load_tokenizer(sft_model_path)
    else:
        print(f"[offline demo] '{sft_model_path}' not found locally — "
              "random-init model + toy tokenizer")
        path = (sft_model_path or "").lower()
        llama = "llama" in path  # Llama-family geometry (no attention biases)
        if "tiny" in path and "olmoe" in path:
            config = ModelConfig.olmoe_tiny(vocab_size=4096)
        elif "tiny" in path:
            config = ModelConfig.qwen2_tiny(vocab_size=4096)
            if llama:  # e.g. "TinyLlama-...": tiny shape, llama family
                config = dataclasses.replace(
                    config, attention_bias=False, rope_theta=500_000.0
                )
        elif llama:
            config = ModelConfig.llama3_2_1b()
        elif "olmoe" in path:
            config = ModelConfig.olmoe_1b_7b()
        else:
            config = ModelConfig.qwen2_1_5b()
        tokenizer = ToyTokenizer(vocab_size=min(4096, config.vocab_size))
        init = partial(init_params, config, dtype=jnp.bfloat16)
        key = jax.random.PRNGKey(seed)
        shardings = None if mesh is None else jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            param_sharding_rules(jax.eval_shape(init, key)))
        params = jax.jit(init, out_shardings=shardings)(key)
    if attention_impl != config.attention_impl:
        config = dataclasses.replace(config, attention_impl=attention_impl)
    return config, params, tokenizer


def resolve_dataset(cfg: RLConfig, tokenizer, max_prompt_len: int = 256):
    """hh-rlhf when the datasets cache has it; synthetic corpus otherwise."""
    name = cfg.train_dataset_name
    cache = cfg.dataset_cache_dir
    try:
        return load_prompt_dataset(name, tokenizer, split=cfg.train_dataset_split,
                                   max_prompt_len=max_prompt_len,
                                   cache_dir=cache)
    except Exception as e:  # zero-egress / no local cache
        print(f"[offline demo] dataset '{name}' unavailable ({type(e).__name__}) — "
              "synthetic prompts")
        return load_prompt_dataset("synthetic:512", tokenizer,
                                   max_prompt_len=max_prompt_len)


def resolve_rm_reward(reward_model_path: str, batch_size: int = 16):
    """Torch host-side RM when its checkpoint exists (deberta path,
    `GRPO/grpo.py:159-198`); otherwise a rule-based stand-in so the loop
    still runs offline."""
    if reward_model_path and os.path.isdir(reward_model_path):
        return make_torch_rm_reward(reward_model_path, batch_size)
    print(f"[offline demo] reward model '{reward_model_path}' not found — "
          "rule-based stand-in reward")

    def fn(s: str, eos_token: str) -> float:
        has_eos = 1.0 if eos_token in s else 0.0
        words = s.split()
        return has_eos + 0.05 * min(len(set(words)) / max(len(words), 1), 1.0)

    return make_rule_reward(fn)


def init_multihost_logged() -> dict:
    """Multi-host bring-up FIRST (before anything touches the backend):
    no-op on a single host; on a pod it joins jax.distributed so
    jax.devices() is the global mesh (parallel/distributed.py). Logs the
    per-process device counts when running multi-process. Shared by
    common.run and the r1 launcher. Also the single place every launcher
    passes through before compiling anything, so the persistent compile
    cache is enabled here, and the one line that says what the run is on is
    printed here: jax falls back to the CPU with only a warning when no
    platform is pinned and the TPU fails to initialise, and kernels then
    run interpreted (ops/attention._interpret_default) — the log must show
    it."""
    from nanorlhf_tpu.parallel import initialize_multihost
    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()

    dist = initialize_multihost()
    dev = jax.devices()
    print(f"[device] platform={dev[0].platform} kind={dev[0].device_kind} "
          f"count={len(dev)} compile_cache={cache_dir}")
    if dist["process_count"] > 1:
        print(f"[multihost] process {dist['process_index']}/"
              f"{dist['process_count']}: {dist['local_device_count']} local "
              f"/ {dist['global_device_count']} global devices")
    return dist


def run(cfg: RLConfig, value_params_fn=None, post_build=None):
    """Build everything and train — the tail of every launcher.

    `value_params_fn(mcfg, params) -> tree` builds the value model from the
    freshly resolved policy (PPO). `post_build(trainer, dataset, reward_func)`
    runs before training (PPO's value-initializer phase).
    """
    init_multihost_logged()
    # rollout_devices>0 makes the trainer split the devices into its own
    # train and rollout meshes; otherwise the mesh is built here, so that
    # the model can be initialised into it
    mesh = make_mesh(cfg.mesh) if cfg.rollout_devices == 0 else None
    mcfg, params, tokenizer = resolve_model(
        cfg.sft_model_path, cfg.seed, attention_impl=cfg.attention_impl,
        mesh=mesh,
    )
    dataset = resolve_dataset(cfg, tokenizer)
    reward_func = resolve_rm_reward(cfg.reward_model_path)
    value_params = value_params_fn(mcfg, params) if value_params_fn else None
    trainer = RLTrainer(
        cfg, mcfg, tokenizer, params, dataset, reward_func,
        value_params=value_params, mesh=mesh,
    )
    # the trainer copied what it keeps (policy, reference, value): holding
    # the intake tree through train() would cost a third model in HBM
    del params, value_params
    if post_build is not None:
        post_build(trainer, dataset, reward_func)
    from nanorlhf_tpu.resilience import Preempted

    try:
        return trainer.train()
    except Preempted as e:
        # SIGTERM during training: the loop already flushed the in-flight
        # async save and committed an emergency checkpoint — exit cleanly
        # (resume_from_checkpoint picks the run back up) instead of dumping
        # a stack trace into the preemption logs
        print(f"[preemption] {e} — exiting cleanly; resume with "
              "resume_from_checkpoint()")
        return trainer.state
    finally:
        trainer.close()
        if cfg.telemetry:
            # close() just (re)wrote the span trace — point the operator at
            # it (docs/OBSERVABILITY.md)
            trace = os.path.join(cfg.telemetry_dir or cfg.output_dir,
                                 "trace.json")
            print(f"[telemetry] span trace: {trace} — load at "
                  "https://ui.perfetto.dev")
