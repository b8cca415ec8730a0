"""End-to-end trainer smoke tests on the 8-device CPU mesh.

Covers the BASELINE.json smoke config shape (REINFORCE, rule-based reward,
CPU-runnable) plus one pass of every other algorithm — the integration net
the reference never had (SURVEY.md §4).
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params, init_score_head
from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
from nanorlhf_tpu.parallel import MeshConfig
from nanorlhf_tpu.trainer import RLConfig, AlgoName, RLTrainer


def rule_reward(pmt_and_responses, eos_token):
    """Rule-based reward: likes responses that end (contain EOS) and are short."""
    out = []
    for s in pmt_and_responses:
        has_eos = 1.0 if eos_token in s else 0.0
        out.append(has_eos - 0.01 * len(s.split()))
    return np.asarray(out, dtype=np.float32)


def make_trainer(algo: AlgoName, tmp_path, trainer_cls=RLTrainer, **overrides):
    tok = ToyTokenizer(vocab_size=256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=256)
    key = jax.random.PRNGKey(0)
    params = init_params(mcfg, key, jnp.float32)
    cfg = RLConfig(
        algo=algo,
        output_dir=str(tmp_path / algo.value),
        response_length=8,
        temperature=1.0,
        sample_n=2,
        total_episodes=32,
        per_device_train_batch_size=1,
        gradient_accumulation_steps=2,
        num_mini_batches=2,
        num_ppo_epochs=1,
        learning_rate=1e-4,
        kl_coef=0.05,
        use_lora=True,
        lora_r=4,
        lora_alpha=8,
        gradient_checkpointing=False,
        mesh=MeshConfig(2, 2, 2),
        save_steps=1,
        report_to="jsonl",
    )
    for k, v in overrides.items():
        setattr(cfg, k, v)
    dataset = load_prompt_dataset("synthetic:64", tok, max_prompt_len=12)
    value_params = None
    if algo == AlgoName.PPO:
        value_params = init_params(mcfg, jax.random.PRNGKey(2), jnp.float32)
        value_params.pop("lm_head", None)
        value_params["score"] = init_score_head(mcfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return trainer_cls(
        cfg, mcfg, tok, params, dataset, rule_reward, value_params=value_params
    )


def test_reinforce_smoke(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, advantage_whiten=True)
    # batch = 1*2*2 * world(4) = 16 → 2 updates for 32 episodes
    state = tr.train()
    assert state["global_step"] == 2
    assert (tmp_path / "reinforce" / "metrics.jsonl").exists()
    # a cache of one block: the rollout's one loop reads all of it
    rows = [json.loads(line)
            for line in open(tmp_path / "reinforce" / "metrics.jsonl")]
    assert [r["rollout/attn_read_frac"] for r in rows if "episode" in r] == [
        1.0, 1.0]
    # ... and on the CPU its cache is contiguous (ISSUE 52)
    assert [r["rollout/kv_in_place"] for r in rows if "episode" in r] == [0, 0]
    # ... and 16 rows sample their candidates by selection (ISSUE 60)
    assert [r["rollout/sample_pick"] for r in rows if "episode" in r] == [1, 1]
    assert (tmp_path / "reinforce" / "checkpoint-2").exists()


def test_rollout_context_depadding(tmp_path):
    """Batches of short prompts train at a menu-rounded context, not the
    dataset-wide max (r1 de-padding applied to the main trainer)."""
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=16)
    # dataset padded to width 12; all synthetic prompts are much shorter than
    # a padded-out width, so force a wide dataset pad to observe the strip
    wide = np.full((64, 32), tr.tokenizer.pad_token_id, np.int32)
    wide[:, -6:] = tr.dataset.input_ids[:, -6:]
    tr.dataset.input_ids = wide
    tr._iter = tr.dataset.loader(tr.cfg.batch_size, tr.cfg.seed)
    seen = {}
    orig = tr._score_chunk_fn()

    def spy(params, ref_params, qr, context_length):
        seen["ctx"] = context_length
        return orig(params, ref_params, qr, context_length)

    tr._score_fn_cached = spy
    tr.train(num_updates=1)
    assert seen["ctx"] <= 16, f"context not de-padded: {seen['ctx']}"


def test_multiple_ppo_epochs_go_off_policy(tmp_path):
    """num_ppo_epochs=2: the second epoch re-fits on stale rollouts, so the
    importance ratio must move off 1 (the clipping machinery is live) while
    the run stays finite — the off-policy capability the reference's losses
    exist for (`REINFORCE/reinforce_trainer.py:637` comment)."""
    import json

    tr = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=16,
                      num_ppo_epochs=2, learning_rate=5e-3)
    tr.train()
    lines = [
        json.loads(l)
        for l in open(tmp_path / "grpo" / "metrics.jsonl")
        if "samples" not in l
    ]
    m = lines[-1]
    # averaged over both epochs the ratio reflects epoch-2 drift
    assert np.isfinite(m["val/ratio_new"])
    assert m["policy/approxkl_avg_new"] > 0, "second epoch produced no drift"
    assert np.isfinite(m["loss/policy_avg_new"])


@pytest.mark.parametrize(
    "algo", [AlgoName.GRPO, AlgoName.RLOO, AlgoName.RAFT, AlgoName.REMAX, AlgoName.PPO]
)
def test_all_algos_one_update(tmp_path, algo):
    tr = make_trainer(algo, tmp_path, total_episodes=16)
    state = tr.train()
    assert state["global_step"] == 1
    import json

    lines = [
        json.loads(l)
        for l in open(tmp_path / algo.value / "metrics.jsonl")
        if "samples" not in l
    ]
    m = lines[-1]
    assert np.isfinite(m["loss/policy_avg_new"])
    assert np.isfinite(m["eval_objective/rlhf_reward_old"])
    if algo == AlgoName.PPO:
        assert "loss/value_avg_new" in m

    # metric-surface fidelity (docs/METRICS.md): every reference key present
    # with per-algo semantics
    for key in (
        "objective/kl_old", "objective/kl_rollout_old", "objective/entropy_old",
        "objective/non_score_reward_old", "eval_objective/scores_old",
        "policy/approxkl_avg_new", "policy/clipfrac_avg_new",
        "policy/entropy_avg_new", "loss/policy_avg_new", "val/ratio_new",
        "val/ratio_var_new", "val/num_eos_tokens_old", "lr", "eps", "episode",
    ):
        assert key in m, f"missing metric {key}"
        assert np.isfinite(m[key]), f"non-finite metric {key}"
    assert m["policy/entropy_avg_new"] > 0, "true entropy must be positive"
    assert m["lr"] > 0
    if algo == AlgoName.GRPO:
        # GRPO: KL in-loss -> non_score_reward identically 0 (reference
        # hard-codes it, `grpo_trainer.py:730`)
        assert m["objective/non_score_reward_old"] == 0.0
    else:
        # KL-in-reward: non_score_reward is the measured KL penalty — exactly
        # -kl_coef x the rollout token-sum KL (both reduce the same masked
        # tensor). At update 1 both are 0 (LoRA b=0 -> policy == ref), so the
        # identity is the meaningful check, not nonzero-ness.
        assert m["objective/non_score_reward_old"] == pytest.approx(
            -tr.cfg.kl_coef * m["objective/kl_rollout_old"], abs=1e-6
        )


def test_pad_chunk_prime_totals():
    """A prime rollout count no longer degenerates the chunked logprob pass
    to chunk=1 (VERDICT r1 weak #6): fixed-size chunks with a padded tail,
    results sliced back — numerics unchanged."""
    from nanorlhf_tpu.trainer.trainer import pad_chunk

    total, chunk = 97, 16
    data = np.arange(total * 3, dtype=np.float32).reshape(total, 3)
    out = []
    n_calls = 0
    for i in range(0, total, chunk):
        n_real = min(chunk, total - i)
        rows = pad_chunk(data[i : i + chunk], chunk)
        assert rows.shape[0] == chunk  # ONE jit shape for every call
        out.append(rows[:n_real])
        n_calls += 1
    np.testing.assert_array_equal(np.concatenate(out), data)
    assert n_calls == 7  # ceil(97/16), not 97


def test_ppo_value_lora_shrinks_optimizer_state(tmp_path):
    """Value-model LoRA (`PPO/ppo.py:301-332`): the Adam state for the value
    tree covers only adapters + score + embed, and the value backbone never
    drifts during PPO updates."""
    tr_full = make_trainer(AlgoName.PPO, tmp_path, total_episodes=16,
                           value_use_lora=False)
    tr_lora = make_trainer(AlgoName.PPO, tmp_path / "l", total_episodes=16,
                           value_use_lora=True, value_lora_r=4,
                           value_lora_alpha=8)

    def trainable_value_elems(tr):
        trainable, _ = tr._partition(tr._train_tree(tr.params, tr.value_params))
        return sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(trainable["value"])
            if x is not None
        )

    # LoRA: backbone layers frozen, only adapters + score + embed trainable —
    # strictly fewer optimizer-tracked elements than full fine-tuning
    assert trainable_value_elems(tr_lora) < trainable_value_elems(tr_full)

    backbone_before = [
        np.asarray(x).copy() for x in jax.tree.leaves(tr_lora.value_params["layers"])
    ]
    tr_lora.train(num_updates=1)
    for a, b in zip(backbone_before, jax.tree.leaves(tr_lora.value_params["layers"])):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert "lora" in tr_lora.value_params


def test_sampler_logprob_capture_grpo(tmp_path):
    """Opt-in capture path: one GRPO update trains with sampler-captured
    logprobs (policy scoring pass skipped); the epoch-1 ratio stays ~1 and
    the drift guard metric is emitted."""
    import json

    tr = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=16,
                      sampler_logprob_capture=True)
    state = tr.train()
    assert state["global_step"] == 1
    lines = [
        json.loads(l)
        for l in open(tmp_path / "grpo" / "metrics.jsonl")
        if "samples" not in l
    ]
    m = lines[-1]
    assert "sampler_capture/ratio_drift_new" in m
    # f32 tiny model: decode and scoring numerics agree to float noise
    assert m["sampler_capture/ratio_drift_new"] < 1e-2
    assert np.isfinite(m["loss/policy_avg_new"])


def test_rollout_top_k_reaches_sampler(tmp_path, monkeypatch):
    """RLConfig.rollout_top_k / rollout_approx_top_k flow into the
    SamplingParams the rollout uses — the r1-zero launcher relies on
    top_k=0 giving the exact untruncated nucleus (VERDICT r3 #6)."""
    import nanorlhf_tpu.trainer.trainer as trainer_mod

    seen = []
    real_generate = trainer_mod.generate

    def spy_generate(params, config, ids, mask, key, sampling, **kw):
        seen.append(sampling)
        return real_generate(params, config, ids, mask, key, sampling, **kw)

    monkeypatch.setattr(trainer_mod, "generate", spy_generate)
    trainer = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=16,
                           rollout_top_k=0, rollout_approx_top_k=False)
    trainer.train(num_updates=1)
    assert seen and seen[0].top_k == 0 and seen[0].approx_top_k is False

    # the SPARSE trainer (the r1-zero path the top_k=0 default targets)
    # rolls out through the same body of the one loop, so the same knobs
    # reach its sampler (code-review r4: its own SamplingParams once fell
    # back to the k=64 pre-trim)
    from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer

    seen_sparse = []

    def spy_sparse(params, config, ids, mask, key, sampling, **kw):
        seen_sparse.append(sampling)
        return real_generate(params, config, ids, mask, key, sampling, **kw)

    monkeypatch.setattr(trainer_mod, "generate", spy_sparse)
    tok = ToyTokenizer(vocab_size=256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=256)
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path / "sparse"),
        response_length=8, temperature=1.0, sample_n=2, total_episodes=32,
        per_device_train_batch_size=4, gradient_accumulation_steps=1,
        num_mini_batches=1, use_lora=False, gradient_checkpointing=False,
        mesh=MeshConfig(-1, 1, 1), save_steps=0, report_to="none",
        rollout_top_k=0, rollout_approx_top_k=False,
    )
    st = SparseGRPOTrainer(
        cfg, mcfg, tok, init_params(mcfg, jax.random.PRNGKey(1), jnp.float32),
        load_prompt_dataset("synthetic:64", tok, max_prompt_len=12),
        rule_reward,
    )
    st.train(num_updates=1)
    assert seen_sparse and seen_sparse[0].top_k == 0
    assert seen_sparse[0].approx_top_k is False

    from nanorlhf_tpu.entrypoints.grpo_r1 import build_config

    assert build_config().rollout_top_k == 0


def test_ref_free_mode_kl0(tmp_path):
    """kl_coef == 0 auto-drops the reference model (r1-zero parity — the
    reference loads NO ref model on that path, `grpo_r1.py:138`): no ref
    weight copy, no ref half of the scoring pass, and the training
    trajectory is BIT-IDENTICAL to a forced-ref run, because ref logprobs
    only ever enter terms multiplied by kl_coef. score_ref_logprobs=True
    forces ref scoring (e.g. to monitor KL drift at coef 0)."""
    t_free = make_trainer(AlgoName.GRPO, tmp_path, kl_coef=0.0,
                          output_dir=str(tmp_path / "free"))
    assert t_free.ref_params is None        # no 2nd weight copy in HBM
    t_free.train(num_updates=2)

    t_full = make_trainer(AlgoName.GRPO, tmp_path, kl_coef=0.0,
                          score_ref_logprobs=True,
                          output_dir=str(tmp_path / "full"))
    assert t_full.ref_params is not None
    t_full.train(num_updates=2)

    for a, b in zip(jax.tree.leaves(t_free.params),
                    jax.tree.leaves(t_full.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # KL metrics read 0 (no reference model exists; the GRPO update-pass
    # refkl stand-in would otherwise report KL-to-old-policy)
    import json

    rows = [json.loads(l) for l in open(tmp_path / "free" / "metrics.jsonl")
            if "objective/kl_old" in l]
    assert rows and all(r["objective/kl_old"] == 0.0 for r in rows)
    assert all(r["objective/kl_rollout_old"] == 0.0 for r in rows)

    # capture + ref-free: the scoring pass disappears entirely — still runs
    t_cap = make_trainer(AlgoName.GRPO, tmp_path, kl_coef=0.0,
                         sampler_logprob_capture=True,
                         output_dir=str(tmp_path / "cap"))
    t_cap.train(num_updates=1)

    # dropping the ref while its KL coefficient is live is rejected — it
    # would silently swap the configured objective
    with pytest.raises(ValueError, match="score_ref_logprobs"):
        make_trainer(AlgoName.GRPO, tmp_path, kl_coef=0.01,
                     score_ref_logprobs=False,
                     output_dir=str(tmp_path / "bad"))

    # PPO value-init with a None ref (ref-free): the ref forward is skipped
    # and the returned tree still regresses
    from nanorlhf_tpu.core import init_score_head
    from nanorlhf_tpu.trainer.value_init import (
        ValueInitConfig, finetune_value_model)

    tok = ToyTokenizer(vocab_size=256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=256)
    pol = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    val = init_params(mcfg, jax.random.PRNGKey(1), jnp.float32)
    val.pop("lm_head", None)
    val["score"] = init_score_head(mcfg, jax.random.PRNGKey(2),
                                   dtype=jnp.float32)
    prompts = load_prompt_dataset("synthetic:8", tok,
                                  max_prompt_len=8).input_ids
    out = finetune_value_model(
        val, pol, None, rule_reward, np.asarray(prompts), tok, mcfg,
        response_length=8, temperature=1.0, kl_coef=0.0, gamma=1.0,
        vcfg=ValueInitConfig(train_data_size=8, num_train_epochs=1,
                             per_device_train_batch_size=4),
    )
    assert "score" in out
