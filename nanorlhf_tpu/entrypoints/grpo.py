"""GRPO launcher — config parity with `/root/reference/GRPO/grpo.py:86-155`.

All settings live in this file (reference convention, `README.md:34`).
Run: python -m nanorlhf_tpu.entrypoints.grpo
"""

from nanorlhf_tpu.entrypoints.common import run
from nanorlhf_tpu.trainer import AlgoName, RLConfig


def build_config(sequence_parallel: int = 1,
                 rollout_staleness: int | None = None,
                 rollout_devices: int = 0,
                 rollout_workers: int = 1,
                 rollout_spec_k: int = 0,
                 status_port: int = 0,
                 env_name: str = "",
                 env_max_turns: int = 1) -> RLConfig:
    """`sequence_parallel > 1` routes the chunked logprob pass and the jitted
    update through ring attention with the sequence dim sharded over an sp
    mesh axis (response_length must divide by it).

    `rollout_staleness` (not None) turns on the async rollout orchestrator
    (docs/ORCHESTRATOR.md) at that max_staleness, with sampler logprob
    capture so the truncated-IS off-policy correction has the behavior
    logprobs it needs; pair with `rollout_devices > 0` to give generation
    its own device group so it truly never waits on the train step.

    `rollout_workers > 1` generalizes the pipeline into the elastic rollout
    fleet (docs/FLEET.md): N independent, preemptible workers under leased
    work with reassignment/quarantine fault tolerance. Implies the
    orchestrator; staleness defaults to the worker count (the gate bounds
    in-flight leases, so fewer stale steps would idle workers). With
    `rollout_devices > 0` the reserved group is split into per-worker
    meshes (rollout_devices must divide by rollout_workers).

    `rollout_spec_k > 0` turns on draft-free speculative rollout decode
    (sampler/speculative.py, distribution-exact); composes with every knob
    above.

    `status_port != 0` serves the live run-health endpoints /metrics ·
    /healthz · /statusz on that port (-1 = ephemeral; docs/OBSERVABILITY.md
    §5). Health scoring itself is on regardless — this only exposes it
    over HTTP.

    `env_name` runs rollouts through a vectorized environment
    (docs/ENVIRONMENTS.md): "single_turn" wraps the reward callable
    (bit-identical to the default pipeline); "python_tool" with
    `env_max_turns > 1` runs fenced ```python blocks as mid-episode tools
    over the paged scheduler — multi-turn forces the paged continuous-
    batching layout and turns off the knobs the episode driver replaces
    (orchestrator, spec decode, logprob capture)."""
    cfg = RLConfig(
        algo=AlgoName.GRPO,
        exp_name="grpo-v1",
        sft_model_path="Qwen/Qwen2.5-1.5B-Instruct",
        reward_model_path="OpenAssistant/reward-model-deberta-v3-large-v2",
        output_dir="output/grpo-v1",
        # reference defaults (`GRPO/grpo.py:108-155`)
        kl_coef=0.01,
        cliprange=0.2,
        temperature=0.9,
        learning_rate=6e-6,
        warmup_steps=0,
        min_lr_rate=0.1,
        response_length=1500,
        per_device_train_batch_size=4,
        gradient_accumulation_steps=8,
        num_mini_batches=16,
        num_ppo_epochs=1,
        total_episodes=250000,
        whiten_rewards=False,
        advantage_whiten=False,   # GRPO has its own group baseline
        sample_n=4,               # grpo_sample_N (`grpo.py:106`)
        use_lora=True,
        lora_r=64,
        lora_alpha=16,
        gradient_checkpointing=True,
        missing_eos_penalty=None,
        save_steps=1,
        save_total_limit=8,
        metric_for_best_model="eval_objective/rlhf_reward_old",
        greater_is_better=True,
        load_best_model_at_end=True,
        stop_token="eos",
    )
    if sequence_parallel > 1:
        from nanorlhf_tpu.parallel import MeshConfig

        cfg.mesh = MeshConfig(data=-1, sp=sequence_parallel)
    if rollout_staleness is not None:
        cfg.rollout_orchestrator = True
        cfg.max_staleness = rollout_staleness
        cfg.sampler_logprob_capture = True  # behavior logprobs for the IS fix
    if rollout_workers > 1:
        cfg.rollout_orchestrator = True
        cfg.rollout_workers = rollout_workers
        cfg.sampler_logprob_capture = True
        if rollout_staleness is None:
            # N workers need N leases in flight to all stay busy
            cfg.max_staleness = rollout_workers
    if rollout_devices > 0:
        cfg.rollout_devices = rollout_devices
    cfg.rollout_spec_k = rollout_spec_k
    cfg.status_port = status_port
    if env_name:
        cfg.env_name = env_name
        cfg.env_max_turns = env_max_turns
        if env_max_turns > 1:
            # the episode driver owns the rollout phase: paged continuous
            # batching on, and the knobs it replaces off
            if cfg.rollout_page_size <= 0:
                cfg.rollout_page_size = 128
            if cfg.rollout_decode_rows <= 0:
                cfg.rollout_decode_rows = cfg.batch_size * cfg.sample_n // 2
            cfg.rollout_orchestrator = False
            cfg.rollout_workers = 1
            cfg.sampler_logprob_capture = False
            cfg.rollout_spec_k = 0
            # half the budget per turn, the rest for the tool observations
            cfg.env_turn_tokens = cfg.response_length // (2 * env_max_turns)
            cfg.env_obs_budget = min(
                256,
                (cfg.response_length - cfg.env_turn_tokens * env_max_turns)
                // max(1, env_max_turns - 1))
    return cfg


if __name__ == "__main__":
    run(build_config())
