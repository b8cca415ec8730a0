"""Async rollout orchestrator (nanorlhf_tpu/orchestrator/):

- bounded-staleness queue semantics under a slow-consumer fake trainer
  (wait policy never exceeds the bound; drop policy counts evictions);
- staleness-0 orchestrated training reproduces the synchronous trainer;
- truncated-IS GRPO at staleness 1 matches on-policy training when the
  policy is unchanged (learning_rate=0 → behavior == current policy);
- queue state survives checkpoint/resume with identical token streams;
- with disaggregated meshes, a pipelined max_staleness=2 run reports a
  strictly higher rollout/train overlap fraction than rollout_ahead under
  the bench's repeated train(num_updates=1) invocation pattern.
"""

import json
import time

import numpy as np
import pytest

import jax

from nanorlhf_tpu.orchestrator import (
    BoundedStalenessQueue,
    OverlapMeter,
    RolloutOrchestrator,
)
from nanorlhf_tpu.trainer import AlgoName

from test_trainer_smoke import make_trainer


def _metric_rows(outdir):
    rows = []
    with open(outdir / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if "episode" in row:
                rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# queue / producer semantics (no model — fake dispatch)
# ---------------------------------------------------------------------------


def test_staleness_bound_enforced_slow_consumer():
    """Wait policy: a fast producer against a slow consumer never dispatches
    (nor delivers) a sample beyond the staleness bound."""
    dispatched = []

    def dispatch(index, tree):
        dispatched.append((index, tree["v"]))
        return {"index": index}

    orch = RolloutOrchestrator(
        dispatch_fn=dispatch, initial_params={"v": 0}, max_staleness=2,
        policy="wait",
    )
    try:
        consumed = []
        for step in range(5):
            s = orch.get()
            consumed.append(orch.version - s.version)
            time.sleep(0.05)  # slow consumer: the producer races ahead
            orch.publish({"v": orch.version + 1})
        # consumed staleness within the bound, and dispatch-time lead
        # (index ahead of the published version) never exceeded it either
        assert all(st <= 2 for st in consumed), consumed
        assert all(idx - v <= 2 for idx, v in dispatched), dispatched
        # the producer really pipelined (ran ahead of the consumer)
        assert max(idx for idx, _ in dispatched) >= 2
        assert orch.queue.dropped == 0
        hist = orch.queue.staleness_counts
        assert sum(hist.values()) == len(consumed)
        assert set(hist) <= {0, 1, 2}
    finally:
        orch.close()


def test_drop_policy_counts_drops_and_keeps_bound():
    """Drop policy: production is gated exactly like "wait" (a producer
    allowed to run ahead would burn the data/PRNG cursor on samples
    destined for the floor — a real bug caught by the verify drive);
    queued samples that go over-stale anyway — publishes without consumes
    — are discarded at get(), counted, and never delivered."""

    def dispatch(index, tree):
        return {"index": index}

    orch = RolloutOrchestrator(
        dispatch_fn=dispatch, initial_params={}, max_staleness=1,
        policy="drop",
    )
    try:
        deadline = time.time() + 5.0
        while orch.queue.depth() < 2 and time.time() < deadline:
            time.sleep(0.01)  # consumer stalled: queue fills to capacity 2
        assert orch.queue.depth() == 2
        time.sleep(0.2)
        # capacity gate held: the producer did NOT run away with the data
        # cursor while the consumer stalled (idx 0,1 queued + at most one
        # in flight)
        assert orch._next_index <= 3, orch._next_index
        assert orch.queue.dropped == 0
        # two publishes WITHOUT consuming -> both queued samples (v0) are
        # now over-stale for max_staleness=1 and must be discarded
        orch.publish({})
        orch.publish({})
        s = orch.get()
        assert orch.queue.dropped >= 2
        assert orch.version - s.version <= 1  # delivered within the bound
    finally:
        orch.close()


def test_producer_error_surfaces_in_get():
    def dispatch(index, tree):
        raise RuntimeError("boom in producer")

    orch = RolloutOrchestrator(dispatch_fn=dispatch, initial_params={},
                               max_staleness=1)
    try:
        with pytest.raises(RuntimeError, match="rollout producer failed"):
            orch.get()
    finally:
        orch.close()


def test_queue_journal_and_restore_counters():
    q = BoundedStalenessQueue(max_staleness=2, policy="wait")
    from nanorlhf_tpu.orchestrator import QueuedSample

    q.put(QueuedSample(index=5, version=1, payload=None))
    q.advance_version(2)
    q.get()
    j = q.journal()
    assert j["version"] == 2 and j["staleness_counts"] == {"1": 1}

    q2 = BoundedStalenessQueue(max_staleness=2)
    q2.restore_counters(j)
    assert q2.staleness_counts == {1: 1} and q2.dropped == 0


def test_overlap_meter_interval_math():
    m = OverlapMeter()
    m.note_gen(0.0, 10.0)
    m.note_busy(2.0, 4.0)
    m.note_busy(3.0, 7.0)    # overlaps the previous busy window
    m.note_busy(20.0, 30.0)  # outside every gen window
    assert m.overlap_fraction() == pytest.approx(0.5)  # [2,7] of [0,10]
    assert OverlapMeter().overlap_fraction() == 0.0


def test_overlap_meter_compaction_preserves_fraction():
    """History folding (watermark compaction) must not change the
    cumulative fraction — and must actually bound the stored history."""
    compact = OverlapMeter()
    compact._COMPACT_AT = 8
    plain = OverlapMeter()  # default threshold: never compacts at this size
    rng = np.random.default_rng(0)
    t = 0.0
    for _ in range(500):
        g0 = t + rng.random() * 0.1
        g1 = g0 + 0.5 + rng.random()
        b0 = g0 + rng.random()
        b1 = b0 + 0.5 + rng.random()
        for m in (compact, plain):
            m.note_gen(g0, g1)
            m.note_busy(b0, b1)
        t = max(g1, b1)
    assert compact.overlap_fraction() == pytest.approx(
        plain.overlap_fraction(), rel=1e-9
    )
    assert len(compact._gen) + len(compact._busy) <= 16


# ---------------------------------------------------------------------------
# trainer integration (8-device CPU mesh)
# ---------------------------------------------------------------------------


def test_staleness0_matches_synchronous_trainer(tmp_path):
    """max_staleness=0 gates every rollout on the freshest published
    version — the orchestrated run must reproduce the synchronous loss
    trajectory (same data cursor, same index-keyed generation PRNG, same
    params at every dispatch)."""
    serial = make_trainer(AlgoName.GRPO, tmp_path / "serial",
                          total_episodes=48, save_steps=0)
    serial.train()
    serial.close()
    orch = make_trainer(AlgoName.GRPO, tmp_path / "orch", total_episodes=48,
                        save_steps=0, rollout_orchestrator=True,
                        max_staleness=0)
    orch.train()
    orch.close()

    m_serial = _metric_rows(tmp_path / "serial" / "grpo")
    m_orch = _metric_rows(tmp_path / "orch" / "grpo")
    assert len(m_serial) == len(m_orch) == 3
    for a, b in zip(m_serial, m_orch):
        for key in ("objective/kl_rollout_old", "eval_objective/scores_old",
                    "objective/entropy_old", "loss/policy_avg_new"):
            np.testing.assert_allclose(
                a[key], b[key], rtol=1e-5,
                err_msg=f"staleness-0 {key} diverged from synchronous",
            )
    # on-policy: every consumed sample reports staleness 0, nothing dropped
    for row in m_orch:
        assert row["orchestrator/staleness"] == 0.0
        assert row["orchestrator/dropped_total"] == 0.0


def test_truncated_is_staleness1_matches_onpolicy_when_policy_frozen(tmp_path):
    """learning_rate=0 freezes the policy, so a staleness-1 behavior policy
    IS the current policy: truncated-IS GRPO must reproduce the synchronous
    run's trajectory (IS weights ≈ 1 up to decode-vs-scoring numerics) —
    the unbiasedness anchor for the off-policy correction."""
    kw = dict(total_episodes=48, save_steps=0, learning_rate=0.0,
              sampler_logprob_capture=True)
    serial = make_trainer(AlgoName.GRPO, tmp_path / "serial", **kw)
    serial.train()
    serial.close()
    orch = make_trainer(AlgoName.GRPO, tmp_path / "orch",
                        rollout_orchestrator=True, max_staleness=1, **kw)
    orch.train()
    orch.close()

    m_serial = _metric_rows(tmp_path / "serial" / "grpo")
    m_orch = _metric_rows(tmp_path / "orch" / "grpo")
    assert len(m_serial) == len(m_orch) == 3
    for a, b in zip(m_serial, m_orch):
        # frozen policy → identical token streams → identical rewards
        np.testing.assert_allclose(
            a["eval_objective/scores_old"], b["eval_objective/scores_old"],
            rtol=1e-5,
        )
        # loss matches up to decode-vs-scoring float noise in the IS weight
        np.testing.assert_allclose(
            a["loss/policy_avg_new"], b["loss/policy_avg_new"], atol=2e-2,
        )
    # pipeline actually went one step stale, and the correction was live
    assert m_orch[-1]["orchestrator/staleness"] == 1.0
    assert m_orch[-1]["offpolicy/is_weight_mean_new"] == pytest.approx(
        1.0, abs=0.05
    )
    assert "offpolicy/is_trunc_frac_new" in m_orch[-1]


def test_checkpoint_resume_identical_token_streams(tmp_path):
    """Queue state survives checkpoint/resume: the journaled consumed-rollout
    cursor + index-keyed PRNG reproduce the uninterrupted run's token
    streams — a 2+resume+1 orchestrated run matches a straight 3-update run
    exactly at staleness 0."""
    full = make_trainer(AlgoName.GRPO, tmp_path / "full", total_episodes=48,
                        rollout_orchestrator=True, max_staleness=0)
    full.train()
    full.close()

    half = make_trainer(AlgoName.GRPO, tmp_path / "half", total_episodes=48,
                        rollout_orchestrator=True, max_staleness=0)
    half.train(num_updates=2)
    # the checkpoint journaled the orchestrator's queue state
    tstate = half.ckpt.load_trainer_state(2)
    assert "orchestrator" in tstate
    assert set(tstate["orchestrator"]) >= {"pending", "version", "dropped"}
    half.close()

    res = make_trainer(AlgoName.GRPO, tmp_path / "half", total_episodes=48,
                       rollout_orchestrator=True, max_staleness=0)
    res.resume_from_checkpoint()
    res.train()
    res.close()

    a = _metric_rows(tmp_path / "full" / "grpo")[-1]
    b = _metric_rows(tmp_path / "half" / "grpo")[-1]
    assert a["episode"] == b["episode"]
    for key in ("objective/kl_rollout_old", "eval_objective/scores_old",
                "objective/entropy_old", "loss/policy_avg_new"):
        np.testing.assert_allclose(a[key], b[key], rtol=1e-4, err_msg=key)


def test_resume_restores_orchestrator_counters(tmp_path):
    """Cumulative drop/staleness counters come back from the journal so the
    metric series stays continuous across resume."""
    tr = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=48,
                      rollout_orchestrator=True, max_staleness=1)
    tr.train(num_updates=2)
    hist_before = dict(tr._orchestrator.queue.staleness_counts)
    tr.close()

    tr2 = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=48,
                       rollout_orchestrator=True, max_staleness=1)
    tr2.resume_from_checkpoint()
    tr2.train(num_updates=1)
    hist_after = dict(tr2._orchestrator.queue.staleness_counts)
    tr2.close()
    assert sum(hist_after.values()) == sum(hist_before.values()) + 1


def test_orchestrator_rejected_on_sparse_and_with_rollout_ahead(tmp_path):
    with pytest.raises(ValueError, match="rollout_ahead"):
        make_trainer(AlgoName.GRPO, tmp_path, rollout_orchestrator=True,
                     rollout_ahead=True)

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import RLConfig
    from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer
    import jax.numpy as jnp

    tok = ToyTokenizer(256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=256)
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path / "sp"),
        response_length=8, sample_n=2, total_episodes=32,
        per_device_train_batch_size=4, gradient_accumulation_steps=1,
        num_mini_batches=1, use_lora=False, gradient_checkpointing=False,
        mesh=MeshConfig(-1, 1, 1), save_steps=0, report_to="none",
        rollout_orchestrator=True,
    )
    with pytest.raises(ValueError, match="not supported by SparseGRPOTrainer"):
        SparseGRPOTrainer(
            cfg, mcfg, tok,
            init_params(mcfg, jax.random.PRNGKey(0), jnp.float32),
            load_prompt_dataset("synthetic:64", tok, max_prompt_len=12),
            lambda prs, eos: np.zeros(len(prs), np.float32),
        )


# ---------------------------------------------------------------------------
# overlap fraction: pipelined orchestrator vs rollout_ahead (acceptance)
# ---------------------------------------------------------------------------


def _make_disagg(tmp_path, **overrides):
    """Disaggregated meshes: 4 train + 4 rollout devices (test_disaggregate
    layout) — generation runs on its own silicon."""
    from test_disaggregate import make_trainer as make_disagg

    return make_disagg(tmp_path, **overrides)


def test_overlap_frac_orchestrator_beats_rollout_ahead(tmp_path):
    """ISSUE-1 acceptance: with disaggregated meshes on the 8-device CPU
    mesh, a pipelined max_staleness=2 run reports strictly higher
    rollout/train overlap than rollout_ahead under the bench's invocation
    pattern (repeated train(num_updates=1) calls — where rollout_ahead's
    in-call prefetch never fires, while the orchestrator's producer thread
    keeps generating across call boundaries)."""
    ahead = _make_disagg(tmp_path / "ahead", rollout_ahead=True)
    ahead.cfg.total_episodes = 48
    for _ in range(3):
        ahead.train(num_updates=1)
    ahead_frac = ahead.rollout_overlap_frac()
    ahead.close()

    orch = _make_disagg(tmp_path / "orch", rollout_orchestrator=True,
                        max_staleness=2, report_to="jsonl")
    orch.cfg.total_episodes = 48
    for _ in range(3):
        orch.train(num_updates=1)
    orch_frac = orch.rollout_overlap_frac()
    # orchestrator metrics reached the payload surface
    rows = _metric_rows(tmp_path / "orch" / "disagg")
    assert "time/rollout_overlap_frac" in rows[-1]
    assert "orchestrator/queue_depth" in rows[-1]
    orch.close()

    assert orch_frac > ahead_frac, (
        f"pipelined overlap {orch_frac:.3f} not above rollout_ahead "
        f"{ahead_frac:.3f}"
    )


def test_orchestrated_all_dense_algos_one_update(tmp_path):
    """Every dense algorithm trains one update through the pipeline (PPO
    exercises the value path under staleness; RAFT skips the IS hook)."""
    for algo in (AlgoName.RLOO, AlgoName.RAFT, AlgoName.PPO):
        tr = make_trainer(algo, tmp_path / algo.value, total_episodes=16,
                          save_steps=0, rollout_orchestrator=True,
                          max_staleness=1, sampler_logprob_capture=True)
        state = tr.train()
        tr.close()
        assert state["global_step"] == 1, algo


# ---------------------------------------------------------------------------
# truncated-IS loss math
# ---------------------------------------------------------------------------


def test_truncated_is_loss_math():
    import jax.numpy as jnp

    from nanorlhf_tpu.algos.losses import (
        grpo_loss,
        ppo_clip_loss_sequence,
        ppo_clip_loss_token,
        truncated_is_weights,
    )

    rng = np.random.default_rng(0)
    B, T = 4, 6
    new = jnp.asarray(rng.normal(-1.0, 0.3, (B, T)).astype(np.float32))
    old = jnp.asarray(rng.normal(-1.0, 0.3, (B, T)).astype(np.float32))
    adv = jnp.asarray(rng.normal(0.0, 1.0, (B, T)).astype(np.float32))
    mask = jnp.ones((B, T), bool)

    # behavior == old → weights exactly 1 → losses identical to uncorrected
    for fn, args in [
        (ppo_clip_loss_token, (new, old, adv, mask, 0.2)),
        (grpo_loss, (new, old, old, adv, mask, 0.2, 0.05)),
        (ppo_clip_loss_sequence, (new, old, adv[:, 0], mask, 0.2)),
    ]:
        base, _ = fn(*args)
        corrected, aux = fn(*args, behavior_logprobs=old, is_truncation=2.0)
        np.testing.assert_allclose(np.asarray(base), np.asarray(corrected),
                                   rtol=1e-6)
        assert float(aux["is_weight_mean"]) == pytest.approx(1.0)
        assert float(aux["is_trunc_frac"]) == 0.0

    # a much-less-likely behavior token → raw weight above ρ̄ → truncated
    behavior = old - 3.0  # π_old/μ = e^3 ≈ 20 ≫ ρ̄
    w, truncated = truncated_is_weights(old, behavior, 2.0)
    assert np.all(np.asarray(w) == 2.0) and np.all(np.asarray(truncated))
    _, aux = ppo_clip_loss_token(new, old, adv, mask, 0.2,
                                 behavior_logprobs=behavior,
                                 is_truncation=2.0)
    assert float(aux["is_trunc_frac"]) == 1.0
    assert float(aux["is_weight_mean"]) == pytest.approx(2.0)
