"""Silicon learning-curve artifact: sparse GRPO (the r1-zero path) climbing a
shaped math-format reward from scratch.

The reference's learning evidence is a rising reward curve
(`/root/reference/README.md:36-37`, `docs/perf.png`) and MATH-500 accuracy
improving from a base model (`examples/r1-v0/README.md:9-14`). This
environment has zero egress and no pretrained checkpoint on disk, so a binary
boxed-answer reward on a random-init policy would be flat (no gradient
signal). Instead this harness runs the SAME r1 machinery — SparseGRPOTrainer,
bucket packing, de-padding, group advantages — on a synthetic arithmetic
corpus with a SHAPED reward a from-scratch policy can climb within ~30
updates:

    reward = digit_density                  (fraction of response tokens that
                                             are digits — dense signal from
                                             the first rollout)
           + 0.5 · has_boxed_format         (emits `\\boxed{...}`)
           + 1.0 · boxed_answer_correct     (grader-verified exact answer)
           + 0.25 · stopped_with_eos

The committed artifact is the metrics series (objective/scores rising), the
repo's answer to the reference's reward-curve evidence at a scale the
hardware budget allows. Run on the TPU (default env) or CPU
(`PYTHONPATH= JAX_PLATFORMS=cpu LEARN_MODEL=tiny`).

A second phase (`LEARN_BINARY_UPDATES > 0`) then SWAPS the reward to the
r1-style BINARY one — 1.0 iff the boxed answer is exactly right, else 0.0,
nothing in between (`examples/r1-v0/grpo_r1.py` reward contract) — and
keeps training the same policy. This is the regime the reference's 1.5B
evidence lives in: most GRPO groups score identically (all-wrong or
all-right) and carry zero advantage, so the sparse filter starves; the
phase records skip counts and whether binary accuracy still climbs from
the shaped-phase policy. A from-scratch policy straight into binary would
be flat forever (never emits \\boxed), which is why the shaped phase runs
first — the curriculum makes the binary regime reachable on this
hardware budget.

Env knobs: LEARN_UPDATES (30), LEARN_BINARY_UPDATES (0), LEARN_MODEL
(small8m | tiny | 1_5b), LEARN_PROMPTS (32 per update), LEARN_RESPONSE
(64), LEARN_LR (8e-3), LEARN_TEMP (1.0 — hotter keeps exploration alive
past the format plateau; the entropy collapse at 8e-3/1.0 freezes the
policy before it ever answers correctly), LEARN_OUT (docs/artifacts). LR note: from-scratch models
need orders more than the fine-tuning 6e-6, but too hot COLLAPSES the
policy — identical samples → zero group advantages → the sparse filter
skips the update. Measured on CPU: tiny (0.1M) wants 2e-2 (3e-4 is flat
noise); small8m (2.9M) at 2e-2 collapses (33/40 updates skipped), at 8e-3
climbs cleanly 0.15 → 0.66 over 40 updates with zero skips. Default 8e-3.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def model_config(name: str):
    from nanorlhf_tpu.core import ModelConfig

    if name == "tiny":
        return ModelConfig.qwen2_tiny(vocab_size=512)
    if name == "1_5b":
        # flagship GEOMETRY (hidden/layers/heads of Qwen2-1.5B) at the toy
        # 512 vocab — the silicon learning-curve shape. Vocab must stay 512:
        # the digit-token share of the toy tokenizer sets the reward's base
        # rate, and at real-vocab sizes the from-scratch digit density is so
        # low every group ties at zero and the sparse filter starves.
        return dataclasses.replace(ModelConfig.qwen2_1_5b(), vocab_size=512)
    # ~4M-param decoder: an order beyond the 336k-param toy of
    # tests/test_learning.py, small enough that ~40 updates fit one chip
    # call (or ~20 min of single-core CPU). Vocab stays 512: the toy
    # tokenizer's digit-token share sets the reward's base rate, and at
    # 4096 the digit density is so low that most GRPO groups score
    # identically zero and the sparse filter skips the update.
    return dataclasses.replace(
        ModelConfig.qwen2_tiny(vocab_size=512),
        hidden_size=256,
        intermediate_size=688,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=2,
    )


_BOXED = re.compile(r"\\boxed\{([^{}]*)\}")


def _expected_answer(s: str, answers_by_prompt: dict):
    """Ground truth for the prompt embedded in decoded sample `s` (first
    prompt-substring match wins) — the ONE matching rule both rewards share,
    so decode-round-trip edge fixes can't diverge the two phases."""
    for p, a in answers_by_prompt.items():
        if p in s:
            return a
    return None


def make_reward(answers_by_prompt: dict):
    """Shaped r1-style reward (see module docstring). `answers_by_prompt`
    maps the prompt text (sans padding) to the ground-truth answer string."""

    def reward(pmt_and_responses, eos_token):
        out = []
        for s in pmt_and_responses:
            # split prompt/response at the generation marker the toy chat
            # template ends with; fall back to scoring the whole string
            resp = s.split("<assistant>")[-1]
            toks = resp.replace(eos_token, " ").split()
            digits = sum(1 for t in toks if t.strip().isdigit())
            r = digits / max(len(toks), 1)
            m = _BOXED.search(resp)
            if m:
                r += 0.5
                want = _expected_answer(s, answers_by_prompt)
                if want is not None and m.group(1).strip() == want:
                    r += 1.0
            if eos_token in s:
                r += 0.25
            out.append(r)
        return np.asarray(out, np.float32)

    return reward


def make_binary_reward(answers_by_prompt: dict):
    """r1-contract binary reward: 1.0 iff the \\boxed answer is exactly the
    ground truth, else 0.0 — no format shaping, no partial credit. The
    sparse-filter starvation regime (all-same groups carry zero advantage)."""

    def reward(pmt_and_responses, eos_token):
        out = []
        for s in pmt_and_responses:
            m = _BOXED.search(s.split("<assistant>")[-1])
            want = _expected_answer(s, answers_by_prompt) if m else None
            out.append(1.0 if (want is not None
                               and m.group(1).strip() == want) else 0.0)
        return np.asarray(out, np.float32)

    return reward


def build_corpus(tok, n: int, seed: int, max_operand: int = 50):
    """Arithmetic prompts through the toy chat template + their answers.
    Addends are drawn from 1..max_operand-1 (EXCLUSIVE upper bound,
    LEARN_MAX_OPERAND; floored at 2 so the range is never empty): small
    operands make answers single tokens, so from-scratch exploration can
    actually hit correctness — the knob that decides whether the binary
    phase has any signal to find."""
    rng = np.random.default_rng(seed)
    max_operand = max(2, max_operand)
    texts, answers = [], {}
    for _ in range(n):
        a = int(rng.integers(1, max_operand))
        b = int(rng.integers(1, max_operand))
        q = f"What is {a} plus {b}? Put the answer in \\boxed{{}}."
        texts.append(q)
        answers[q] = str(a + b)
    return texts, answers


def main():
    import signal

    # a run bounded with coreutils `timeout` gets SIGTERM — convert it to
    # an exception so the artifact still gets written from whatever updates
    # completed (a killed run losing its whole curve is the worst outcome).
    def _on_term(signum, frame):
        raise KeyboardInterrupt("SIGTERM")

    signal.signal(signal.SIGTERM, _on_term)

    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()  # warm-start repeat runs

    from nanorlhf_tpu.core import init_params
    from nanorlhf_tpu.data import ToyTokenizer, PromptDataset
    from nanorlhf_tpu.data.datasets import encode_texts, _left_pad
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig
    from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer

    updates = int(os.environ.get("LEARN_UPDATES", 30))
    binary_updates = int(os.environ.get("LEARN_BINARY_UPDATES", 0))
    model = os.environ.get("LEARN_MODEL", "small8m")
    prompts = int(os.environ.get("LEARN_PROMPTS", 32))
    resp = int(os.environ.get("LEARN_RESPONSE", 64))
    out_dir = os.environ.get("LEARN_OUT", "docs/artifacts")

    mcfg = model_config(model)
    tok = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.bfloat16)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))

    texts, answers = build_corpus(
        tok, 256, seed=0,
        max_operand=int(os.environ.get("LEARN_MAX_OPERAND", 50)),
    )
    templated = [
        tok.apply_chat_template([{"role": "user", "content": t}],
                                tokenize=False, add_generation_prompt=True)
        for t in texts
    ]
    ids = encode_texts(tok, templated, max_prompt_len=32)
    dataset = PromptDataset(_left_pad(ids, tok.pad_token_id), tok.pad_token_id)

    # pid-unique fresh run dir: the metrics logger APPENDS, and a fixed
    # path would let a concurrent or stale invocation pollute the committed
    # artifact (observed: two overlapped runs interleaved one jsonl)
    import shutil

    run_dir = f"/tmp/nanorlhf_learning_run.{os.getpid()}"
    shutil.rmtree(run_dir, ignore_errors=True)
    cfg = RLConfig(
        algo=AlgoName.GRPO,
        exp_name="learning-curve",
        output_dir=run_dir,
        response_length=resp,
        temperature=float(os.environ.get("LEARN_TEMP", 1.0)),
        top_p=0.95,
        rollout_top_k=0,                 # r1 default: exact nucleus
        sample_n=4,
        kl_coef=0.0,                     # r1: no KL (`grpo_r1.py:138`)
        learning_rate=float(os.environ.get("LEARN_LR", 8e-3)),
        # LEARN_PROMPTS is the GLOBAL prompts-per-update; the mesh takes
        # every visible device on its data axis (1 on one chip, 8 on the
        # virtual CPU test mesh)
        per_device_train_batch_size=max(1, prompts // len(jax.devices())),
        gradient_accumulation_steps=1,
        num_mini_batches=1,
        total_episodes=(updates + binary_updates)
        * max(1, prompts // len(jax.devices())) * len(jax.devices()) * 4,
        use_lora=False,                  # full FT: random init has no base
        gradient_checkpointing=True,
        mesh=MeshConfig(-1, 1, 1),
        save_steps=0,
        report_to="jsonl",
        logging_steps=1,
    )
    trainer = SparseGRPOTrainer(cfg, mcfg, tok, params, dataset,
                                make_reward(answers))
    interrupted = None
    shaped_steps = None
    shaped_skips = 0
    binary_stats = None
    try:
        state = trainer.train(num_updates=updates)
        shaped_steps = state["global_step"]
        shaped_skips = state["rollouts"] - shaped_steps
        if binary_updates > 0:
            # PHASE 2: same policy, same trainer — only the reward becomes
            # the r1 binary contract. The sparse filter now sees all-same
            # groups (zero advantage) whenever a prompt is uniformly
            # failed/solved; skipped updates consume a rollout without
            # stepping, which is exactly the starvation the 1.5B regime
            # exhibits.
            trainer.reward_func = make_binary_reward(answers)
            state = trainer.train(num_updates=binary_updates)
    except KeyboardInterrupt as e:
        interrupted = str(e) or "interrupted"
        state = trainer.state
        print(f"\n[learning_run] interrupted ({interrupted}) — writing the "
              f"artifact from {state['global_step']} completed updates")
        if shaped_steps is None:  # died in phase 1
            shaped_steps = state["global_step"]
            shaped_skips = state["rollouts"] - shaped_steps
            binary_updates = 0
    shaped_rollouts = shaped_steps + shaped_skips
    if binary_updates > 0:
        # derive ATTEMPTED from the rollout counter, not the env knob — an
        # interrupt mid-phase-2 would otherwise record attempts that never
        # ran, making the committed skip-rate internally inconsistent
        binary_attempted = state["rollouts"] - shaped_rollouts
        binary_stats = {
            "updates_attempted": binary_attempted,
            "updates_stepped": state["global_step"] - shaped_steps,
            "updates_skipped_by_sparse_filter": (
                (state["rollouts"] - state["global_step"]) - shaped_skips
            ),
        }

    # tolerate a torn trailing line: the SIGTERM→KeyboardInterrupt can land
    # inside the logger's write, and the recovery path must not lose the
    # whole curve to one malformed row
    rows = []
    for line in open(os.path.join(run_dir, "metrics.jsonl")):
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    series = [
        {
            "step": r["step"],
            "score": round(r.get("eval_objective/scores_old", 0.0), 4),
            "entropy": round(r.get("policy/entropy_avg_new", 0.0), 3),
            # response-length growth — the reference's len.png evidence
            "resp_len": round(r.get("eval_response_length", 0.0), 2),
            # steps logged after the swap carry the binary phase marker
            "phase": "binary" if r["step"] > shaped_steps else "shaped",
        }
        for r in rows
        if "eval_objective/scores_old" in r
    ]
    os.makedirs(out_dir, exist_ok=True)
    shaped_series = [s for s in series if s["phase"] == "shaped"]
    bin_series = [s for s in series if s["phase"] == "binary"]
    # skip rows (sparse_skip/*, logged by the trainer when every group ties):
    # raw_score_mean distinguishes starved-at-zero (uniformly failed) from
    # starved-solved (uniformly correct) — both carry zero group advantage
    skip_raw = [
        {"rollout": r["sparse_skip/rollout_index"],
         "raw_score_mean": round(r["sparse_skip/raw_score_mean"], 4)}
        for r in rows if "sparse_skip/raw_score_mean" in r
    ]
    # rollout_index is the 1-based CONSUMED count (RolloutStream sets
    # rollouts = index + 1), so the last shaped-phase skip carries exactly
    # shaped_rollouts — strictly-greater keeps its shaped-scale score out
    # of the binary average
    bin_skip_raw = [s for s in skip_raw if s["rollout"] > shaped_rollouts]
    first = np.mean([s["score"] for s in shaped_series[:3]]) if shaped_series else 0.0
    last = np.mean([s["score"] for s in shaped_series[-3:]]) if shaped_series else 0.0
    artifact = {
        "what": "sparse-GRPO (r1 path) reward curve, shaped math-format "
                "reward, from-scratch policy"
                + (" + binary-reward phase (r1 contract)" if binary_stats
                   else ""),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": model,
        "n_params": n_params,
        "updates": state["global_step"],
        "episodes": state["episode"],
        "reward_first3_avg": round(float(first), 4),
        "reward_last3_avg": round(float(last), 4),
        "series": series,
    }
    if binary_stats:
        b_first = np.mean([s["score"] for s in bin_series[:3]]) if bin_series else 0.0
        b_last = np.mean([s["score"] for s in bin_series[-3:]]) if bin_series else 0.0
        binary_stats["binary_first3_avg"] = round(float(b_first), 4)
        binary_stats["binary_last3_avg"] = round(float(b_last), 4)
        if bin_skip_raw:
            means = [s["raw_score_mean"] for s in bin_skip_raw]
            avg = float(np.mean(means))
            binary_stats["skipped_raw_score_mean_avg"] = round(avg, 4)
            # a skipped batch only guarantees PER-GROUP ties, not batch
            # uniformity — a mid-range mean is some groups all-solved and
            # others all-failed, its own regime
            binary_stats["starvation_mode"] = (
                "uniformly_failed" if avg < 0.05
                else "uniformly_solved" if avg > 0.95
                else "mixed_groups"
            )
        artifact["binary_phase"] = binary_stats
    if interrupted:
        artifact["interrupted"] = interrupted
    path = os.path.join(out_dir, "learning_curve_r5.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"\nwrote {path}: shaped reward {first:.3f} -> {last:.3f} over "
          f"{shaped_steps} updates ({n_params/1e6:.1f}M params, "
          f"{jax.default_backend()})"
          + (f"; binary phase {binary_stats}" if binary_stats else ""))


if __name__ == "__main__":
    main()
