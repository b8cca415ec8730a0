"""Measure sampler-logprob-capture drift on real hardware (ROADMAP 5b).

`sampler_logprob_capture=True` reuses the sampler's per-token logprobs as the
rollout-policy logprobs, halving the scoring forwards. Decode-vs-scoring
numerics (KV-cache decode path vs the padded scoring forward, bf16) make the
epoch-1 importance ratio deviate from exactly 1; the trainer logs that
residual as `sampler_capture/ratio_drift_new` = mean |exp(score_lp −
captured_lp) − 1| over response tokens. This harness runs a few flagship-
shaped updates with capture ON and reports the measured drift so the default
can be flipped (or the reason not to recorded) — VERDICT r3 #7.

Run on the chip as the only jax process (`chiprun -- python
tools/capture_drift.py`). Env knobs: DRIFT_UPDATES (2),
DRIFT_RESPONSE (256), DRIFT_PROMPTS (16), DRIFT_MODEL (1_5b | tiny).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()  # warm-start repeat runs

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    updates = int(os.environ.get("DRIFT_UPDATES", 2))
    resp = int(os.environ.get("DRIFT_RESPONSE", 256))
    prompts = int(os.environ.get("DRIFT_PROMPTS", 16))
    model = os.environ.get("DRIFT_MODEL", "1_5b")

    mcfg = (ModelConfig.qwen2_1_5b() if model == "1_5b"
            else ModelConfig.qwen2_tiny(vocab_size=4096))
    tok = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.bfloat16)
    ds = load_prompt_dataset(f"synthetic:{prompts * 2}", tok, max_prompt_len=64)

    def reward(p, eos):
        return np.asarray([1.0 if eos in s else 0.0 for s in p], np.float32)

    run_dir = "/tmp/nanorlhf_capture_drift"
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=run_dir, response_length=resp,
        temperature=0.9, sample_n=4, per_device_train_batch_size=prompts,
        gradient_accumulation_steps=1, num_mini_batches=1,
        total_episodes=updates * prompts * 4, use_lora=True,
        gradient_checkpointing=True, mesh=MeshConfig(1, 1, 1), save_steps=0,
        report_to="jsonl", logging_steps=1,
        sampler_logprob_capture=True,
    )
    t = RLTrainer(cfg, mcfg, tok, params, ds, reward)
    t.train(num_updates=updates)

    rows = [json.loads(l) for l in open(os.path.join(run_dir, "metrics.jsonl"))]
    drifts = [r["sampler_capture/ratio_drift_new"] for r in rows
              if "sampler_capture/ratio_drift_new" in r]
    print(json.dumps({
        "metric": "sampler_capture_ratio_drift",
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
        "model": model, "response_length": resp,
        "per_update": [round(d, 6) for d in drifts],
        "mean": round(float(np.mean(drifts)), 6) if drifts else None,
        "max": round(float(np.max(drifts)), 6) if drifts else None,
    }), flush=True)


if __name__ == "__main__":
    main()
