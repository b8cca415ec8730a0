"""Resilience layer (nanorlhf_tpu/resilience/, docs/RESILIENCE.md):

- fault-injection schedules are deterministic and spec-parseable;
- a producer crash is restarted by the watchdog with bit-identical
  post-recovery token streams (staleness 0), and a persistently crashing
  producer degrades to synchronous rollouts that reproduce the serial
  trainer exactly instead of killing the run;
- a NaN update trips the sentinel, rolls back to the last committed
  checkpoint, quarantines the offending batch, and replays the stream
  bit-identically (lr=0 anchor against a clean run's rows);
- an injected checkpoint-write failure is retried and succeeds;
- SIGTERM commits a resumable emergency checkpoint and the resumed run
  matches an uninterrupted one;
- a no-fault run with the sentinel enabled is numerically identical to one
  with it disabled.
"""

import json
import os
import signal

import numpy as np
import pytest

from nanorlhf_tpu.resilience import (
    FaultInjector,
    InjectedFault,
    Preempted,
    PreemptionGuard,
    ProducerWatchdog,
    SentinelBudgetExceeded,
    SentinelConfig,
    TrainingSentinel,
    WatchdogConfig,
    parse_fault_spec,
    retry_with_backoff,
)
from nanorlhf_tpu.trainer import AlgoName, RLTrainer
from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer

from test_trainer_smoke import make_trainer

# the sentinel sits in the one loop's `guard` phase, so every trainer that
# runs the loop is held to it: (class, an algorithm it trains, the stream
# keys that do not depend on a keep-1-of-N draw — GRPO's rides the trainer
# PRNG key, which a rollback rewinds with the checkpoint)
BOTH_TRAINERS = pytest.mark.parametrize("cls,algo,replay_keys", [
    pytest.param(RLTrainer, AlgoName.REINFORCE, None, id="dense"),
    pytest.param(SparseGRPOTrainer, AlgoName.GRPO,
                 ("eval_objective/scores_old",), id="sparse"),
])


def _varied_reward(pmt_and_responses, *_):
    import zlib

    return np.asarray([(zlib.crc32(s.encode()) % 17) / 17.0
                       for s in pmt_and_responses], np.float32)


def _make(cls, algo, path, **kw):
    tr = make_trainer(algo, path, trainer_cls=cls, **kw)
    if cls is SparseGRPOTrainer:
        # scores that vary inside every group: no all-zero-advantage skip
        # takes an update out of the count the assertions below share
        tr.reward_func = _varied_reward
    return tr


def _metric_rows(outdir):
    rows = []
    with open(outdir / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if "episode" in row:
                rows.append(row)
    return rows


# rollout-level keys: functions of (data batch, generation PRNG, policy
# params) only — the bit-exact stream comparators used throughout
STREAM_KEYS = ("eval_objective/scores_old", "objective/entropy_old",
               "objective/kl_rollout_old")


# ---------------------------------------------------------------------------
# fault injection registry
# ---------------------------------------------------------------------------


def test_fault_spec_parsing_and_validation():
    scheds = parse_fault_spec("ckpt.save:at=3 rollout.produce:every=2;"
                              "update.step:prob=0.5,seed=7,action=nan")
    assert [s.point for s in scheds] == ["ckpt.save", "rollout.produce",
                                         "update.step"]
    assert scheds[0].at == 3 and scheds[0].count == 1  # `at` fires once
    assert scheds[2].action == "nan"
    with pytest.raises(ValueError, match="unknown injection point"):
        parse_fault_spec("no.such.point:at=1")
    with pytest.raises(ValueError, match="exactly one"):
        parse_fault_spec("ckpt.save:at=1,every=2")
    with pytest.raises(ValueError, match="action"):
        parse_fault_spec("ckpt.save:at=1,action=explode")


def test_fault_schedules_fire_deterministically():
    inj = FaultInjector.from_spec("ckpt.save:at=2")
    inj.fire("ckpt.save")                      # call 1: no fire
    with pytest.raises(InjectedFault):
        inj.fire("ckpt.save")                  # call 2: fires (once)
    inj.fire("ckpt.save")                      # call 3: spent
    assert inj.stats()["ckpt.save"] == {"calls": 3, "fires": 1}

    every = FaultInjector.from_spec("reward.exec:every=3")
    fired = []
    for i in range(1, 10):
        try:
            every.fire("reward.exec")
        except InjectedFault:
            fired.append(i)
    assert fired == [3, 6, 9]

    # seeded prob schedules replay the same fire pattern
    def pattern():
        inj = FaultInjector.from_spec("update.step:prob=0.4,seed=11,count=100")
        out = []
        for _ in range(50):
            try:
                inj.fire("update.step")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert pattern() == pattern()
    assert sum(pattern()) > 0

    # unarmed points are free and silent
    assert FaultInjector.from_spec(None).fire("ckpt.save") is None

    # nan action returns instead of raising
    nan = FaultInjector.from_spec("update.step:at=1,action=nan")
    assert nan.fire("update.step") == "nan"


def test_retry_with_backoff_counts_and_raises():
    calls = {"n": 0}
    retries = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    out = retry_with_backoff(flaky, attempts=3, backoff_base=0.0,
                             on_retry=lambda i, e: retries.append(i))
    assert out == "ok" and retries == [0, 1]

    def always_fail():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        retry_with_backoff(always_fail, attempts=2, backoff_base=0.0)


# ---------------------------------------------------------------------------
# sentinel / watchdog policy units (no trainer)
# ---------------------------------------------------------------------------


def test_sentinel_nonfinite_and_spike_detection():
    s = TrainingSentinel(SentinelConfig(spike_zscore=4.0, warmup_steps=5))
    assert s.observe(float("nan")) == "nonfinite"
    assert s.observe(1.0, grad_norm=float("inf")) == "nonfinite"
    rng = np.random.default_rng(0)
    for _ in range(30):
        assert s.observe(1.0 + 0.01 * rng.standard_normal()) is None
    assert s.observe(50.0) == "spike"
    # the spike was NOT folded into the EWMA: a repeat still trips
    assert s.observe(50.0) == "spike"
    # budget: two rollbacks allowed, the third raises
    s.cfg.rollback_budget = 2
    s.note_rollback(1, 0, "spike")
    s.note_rollback(2, 1, "spike")
    with pytest.raises(SentinelBudgetExceeded):
        s.note_rollback(3, 2, "spike")
    assert s.quarantined == {0, 1, 2}


def test_sentinel_journal_roundtrip():
    s = TrainingSentinel(SentinelConfig())
    for x in (1.0, 1.1, 0.9):
        s.observe(x)
    s.note_rollback(3, 7, "nonfinite")
    j = json.loads(json.dumps(s.journal()))  # must be JSON-able
    s2 = TrainingSentinel(SentinelConfig())
    s2.restore(j)
    assert s2.steps == s.steps and s2.ewma == pytest.approx(s.ewma)
    assert s2.rollbacks == 1 and s2.quarantined == {7}


def test_sentinel_disabled_observes_nothing():
    s = TrainingSentinel(SentinelConfig(enabled=False))
    assert s.observe(float("nan")) is None


def test_watchdog_budget_backoff_and_degrade():
    w = ProducerWatchdog(WatchdogConfig(restart_budget=2, backoff_base=0.5,
                                        backoff_max=10.0))
    d1, b1 = w.on_failure()
    d2, b2 = w.on_failure()
    assert (d1, d2) == (ProducerWatchdog.RESTART, ProducerWatchdog.RESTART)
    assert b2 == 2 * b1  # exponential
    d3, _ = w.on_failure()
    assert d3 == ProducerWatchdog.DEGRADE and w.degraded
    assert w.restarts_total == 2

    # a consumed sample resets the consecutive streak
    w2 = ProducerWatchdog(WatchdogConfig(restart_budget=1))
    assert w2.on_failure()[0] == ProducerWatchdog.RESTART
    w2.on_success()
    assert w2.on_failure()[0] == ProducerWatchdog.RESTART

    # degrade_to_sync=False re-raises instead
    w3 = ProducerWatchdog(WatchdogConfig(restart_budget=0,
                                         degrade_to_sync=False))
    assert w3.on_failure()[0] == ProducerWatchdog.RAISE


def test_queue_drains_buffered_samples_before_raising_producer_failure():
    """Device-ready samples already in the queue were never lost — a
    watchdog restart must not regenerate them. get() delivers the buffer
    first and only then surfaces the producer's failure."""
    from nanorlhf_tpu.orchestrator import BoundedStalenessQueue, QueuedSample
    from nanorlhf_tpu.orchestrator import ProducerFailed

    q = BoundedStalenessQueue(max_staleness=2)
    q.put(QueuedSample(index=0, version=0, payload="a"))
    q.put(QueuedSample(index=1, version=0, payload="b"))
    q.fail(RuntimeError("producer died"))
    assert q.get(timeout=0.1).payload == "a"
    assert q.get(timeout=0.1).payload == "b"
    with pytest.raises(ProducerFailed, match="rollout producer failed"):
        q.get(timeout=0.1)


def test_null_guard_is_fresh_per_call():
    """graceful_preemption=False trainers must not share trigger state — a
    shared guard would let one trainer's trigger() poison every later one."""
    from nanorlhf_tpu.resilience import null_guard

    a = null_guard()
    a.trigger()
    assert not null_guard().triggered


def test_preemption_guard_manual_and_signal():
    g = PreemptionGuard(install=False)
    assert not g.triggered
    g.trigger()
    assert g.triggered
    g.clear()

    g2 = PreemptionGuard()
    try:
        if g2.installed:  # main thread
            os.kill(os.getpid(), signal.SIGTERM)
            assert g2.triggered
    finally:
        g2.uninstall()


# ---------------------------------------------------------------------------
# fault matrix: producer crash → restart → bit-identical streams
# ---------------------------------------------------------------------------


def _fast_watchdog(**over):
    kw = dict(rollout_orchestrator=True, max_staleness=0, total_episodes=48,
              producer_backoff_base=0.01, producer_backoff_max=0.05)
    kw.update(over)
    return kw


def test_producer_crash_restart_bit_identical(tmp_path):
    """One injected producer crash: the watchdog restarts the pipeline from
    the consumed cursor and the run's rollout-level metric rows are
    BIT-IDENTICAL to an uninjected run's (staleness 0: every sample is
    regenerated from the same published version)."""
    clean = make_trainer(AlgoName.GRPO, tmp_path / "clean", save_steps=0,
                         **_fast_watchdog())
    clean.train()
    clean.close()

    faulted = make_trainer(AlgoName.GRPO, tmp_path / "faulted", save_steps=0,
                           fault_spec="rollout.produce:at=2",
                           **_fast_watchdog())
    faulted.train()
    assert faulted.watchdog.restarts_total == 1
    assert not faulted.watchdog.degraded
    faulted.close()

    a = _metric_rows(tmp_path / "clean" / "grpo")
    b = _metric_rows(tmp_path / "faulted" / "grpo")
    assert len(a) == len(b) == 3
    for ra, rb in zip(a, b):
        for key in STREAM_KEYS + ("loss/policy_avg_new",):
            np.testing.assert_allclose(ra[key], rb[key], rtol=1e-6,
                                       err_msg=key)
    assert b[-1]["resilience/producer_restarts"] == 1.0
    assert b[-1]["resilience/degraded_mode"] == 0.0


def test_producer_crash_degrades_to_sync_matches_serial(tmp_path):
    """A producer that dies on EVERY dispatch exhausts the restart budget
    and degrades to synchronous rollouts — the run completes with rows
    identical to the plain serial trainer (the documented fallback mode)."""
    serial = make_trainer(AlgoName.GRPO, tmp_path / "serial", save_steps=0,
                          total_episodes=48)
    serial.train()
    serial.close()

    deg = make_trainer(AlgoName.GRPO, tmp_path / "deg", save_steps=0,
                       fault_spec="rollout.produce:every=1",
                       **_fast_watchdog(producer_restart_budget=1))
    deg.train()
    assert deg.watchdog.degraded
    assert deg.watchdog.restarts_total == 1
    deg.close()

    a = _metric_rows(tmp_path / "serial" / "grpo")
    b = _metric_rows(tmp_path / "deg" / "grpo")
    assert len(a) == len(b) == 3
    for ra, rb in zip(a, b):
        for key in STREAM_KEYS + ("loss/policy_avg_new",):
            np.testing.assert_allclose(ra[key], rb[key], rtol=1e-6,
                                       err_msg=key)
    assert b[-1]["resilience/degraded_mode"] == 1.0
    # degraded rows must not pretend the pipeline is still up
    assert "orchestrator/queue_depth" not in b[-1]


def test_producer_degrade_disabled_reraises(tmp_path):
    tr = make_trainer(AlgoName.GRPO, tmp_path, save_steps=0,
                      fault_spec="rollout.produce:every=1",
                      **_fast_watchdog(producer_restart_budget=0,
                                       degrade_to_sync=False))
    with pytest.raises(RuntimeError, match="rollout producer"):
        tr.train()
    tr.close()


# ---------------------------------------------------------------------------
# fault matrix: NaN step → sentinel rollback → bit-identical replay
# ---------------------------------------------------------------------------


@BOTH_TRAINERS
def test_nan_step_rollback_replays_bit_identical_streams(tmp_path, cls, algo,
                                                         replay_keys):
    """update 2 observes an injected NaN: the sentinel restores checkpoint 1,
    quarantines update 2's rollout index, and replays. With lr=0 (REINFORCE:
    no selection PRNG) the post-rollback rows must be bit-identical to the
    CLEAN run's rows for the same rollout indices — the replayed data/PRNG
    streams are exactly the journal's."""
    kw = dict(total_episodes=64, learning_rate=0.0, save_steps=1)
    keys = replay_keys or STREAM_KEYS
    clean = _make(cls, algo, tmp_path / "clean", **kw)
    clean.train()  # 4 updates of 16 episodes
    clean.close()

    faulted = _make(cls, algo, tmp_path / "faulted",
                    fault_spec="update.step:at=2,action=nan", **kw)
    state = faulted.train()
    assert state["global_step"] == 4
    assert faulted.sentinel.rollbacks == 1
    assert faulted.sentinel.quarantined == {1}  # update 2's rollout index
    faulted.close()

    a = _metric_rows(tmp_path / "clean" / algo.value)
    b = _metric_rows(tmp_path / "faulted" / algo.value)
    assert len(a) == len(b) == 4
    # clean step k consumed rollout k-1; the faulted run quarantined rollout
    # 1, so its steps 2..4 consumed rollouts 2..4 — compare rollout-aligned
    # rows: faulted step s (s >= 2) vs clean step s+1
    for s in (1,):
        for key in keys:
            np.testing.assert_allclose(a[s - 1][key], b[s - 1][key],
                                       rtol=1e-6, err_msg=key)
    for s in (2, 3):
        for key in keys:
            np.testing.assert_allclose(a[s][key], b[s - 1][key], rtol=1e-6,
                                       err_msg=f"replayed {key} @ step {s}")
    assert b[-1]["resilience/rollbacks"] == 1.0
    # the tripped update's phase splits were discarded with it: the row of
    # the replayed step holds one update's wall time, not two
    assert all(r["time/update_s"] <= r["trainer/iteration_s"] for r in b)
    # sentinel journal rode into the checkpoint: a fresh trainer resumes
    # the rollback spend and quarantine set
    res = _make(cls, algo, tmp_path / "faulted", **kw)
    res.resume_from_checkpoint()
    assert res.sentinel.rollbacks == 1
    assert res.sentinel.quarantined == {1}
    res.close()


@BOTH_TRAINERS
def test_nan_step_budget_exhausted_raises(tmp_path, cls, algo, replay_keys):
    tr = _make(cls, algo, tmp_path, total_episodes=64,
               save_steps=1, rollback_budget=0,
               fault_spec="update.step:at=2,action=nan")
    with pytest.raises(SentinelBudgetExceeded):
        tr.train()
    tr.close()


def test_nan_step_without_checkpoint_raises(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=64,
                      save_steps=0,
                      fault_spec="update.step:at=1,action=nan")
    with pytest.raises(RuntimeError, match="no committed checkpoint"):
        tr.train()
    tr.close()


@pytest.mark.parametrize("cls", [RLTrainer, SparseGRPOTrainer],
                         ids=["dense", "sparse"])
def test_sentinel_enabled_is_numerically_inert(tmp_path, cls):
    """Acceptance: a no-fault run with the sentinel on is numerically
    identical to one with it off — the guard only observes."""
    on = make_trainer(AlgoName.GRPO, tmp_path / "on", total_episodes=48,
                      save_steps=0, sentinel=True, trainer_cls=cls)
    on.train()
    on.close()
    off = make_trainer(AlgoName.GRPO, tmp_path / "off", total_episodes=48,
                       save_steps=0, sentinel=False, trainer_cls=cls)
    off.train()
    off.close()
    a = _metric_rows(tmp_path / "on" / "grpo")
    b = _metric_rows(tmp_path / "off" / "grpo")
    assert len(a) == len(b) == 3
    for ra, rb in zip(a, b):
        for key in STREAM_KEYS + ("loss/policy_avg_new",
                                  "policy/grad_norm_new"):
            np.testing.assert_allclose(ra[key], rb[key], rtol=0, atol=0,
                                       err_msg=key)
        assert ra["policy/grad_norm_new"] > 0.0  # the guard had a norm to read


# ---------------------------------------------------------------------------
# fault matrix: checkpoint-write failure → retry succeeds
# ---------------------------------------------------------------------------


def test_ckpt_write_failure_retried_and_committed(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1, fault_spec="ckpt.save:at=1",
                      ckpt_retry_backoff=0.01)
    tr.train()
    assert tr.ckpt.retry_count == 1
    assert tr.ckpt.latest_step() == 2  # both saves committed
    tr.close()
    rows = _metric_rows(tmp_path / "reinforce")
    assert rows[-1]["resilience/ckpt_retries"] == 1.0
    # the retried checkpoint is genuinely restorable
    res = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32)
    res.resume_from_checkpoint()
    assert res.state["global_step"] == 2
    res.close()


def test_ckpt_restore_failure_retried(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1)
    tr.train()
    tr.close()
    res = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                       fault_spec="ckpt.restore:at=1",
                       ckpt_retry_backoff=0.01)
    res.resume_from_checkpoint()
    assert res.ckpt.retry_count == 1
    assert res.state["global_step"] == 2
    res.close()


def test_ckpt_exhausted_retries_raise(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1, ckpt_io_retries=1,
                      ckpt_retry_backoff=0.01,
                      fault_spec="ckpt.save:every=1,count=2")
    with pytest.raises(InjectedFault):
        tr.train()
    tr.close()


# ---------------------------------------------------------------------------
# fault matrix: SIGTERM → emergency checkpoint → resumable
# ---------------------------------------------------------------------------


def test_sigterm_emergency_checkpoint_resumes_bit_identical(tmp_path):
    """A SIGTERM delivered mid-run (from the reward phase of update 2 —
    a deterministic delivery point) commits an emergency checkpoint even
    with periodic saves OFF; resuming from it reproduces the uninterrupted
    run's rows exactly."""
    full = make_trainer(AlgoName.GRPO, tmp_path / "full", total_episodes=48,
                        save_steps=0)
    full.train()
    full.close()

    import test_trainer_smoke as smoke

    calls = {"n": 0}

    def sigterm_reward(pmt_and_responses, eos_token):
        calls["n"] += 1
        if calls["n"] == 2:
            os.kill(os.getpid(), signal.SIGTERM)
        return smoke.rule_reward(pmt_and_responses, eos_token)

    half = make_trainer(AlgoName.GRPO, tmp_path / "half", total_episodes=48,
                        save_steps=0)
    if not half._preemption.installed:  # non-main-thread runner: raw SIGTERM
        half.close()                    # would kill the test process
        pytest.skip("SIGTERM handler needs the main thread")
    half.reward_func = sigterm_reward
    with pytest.raises(Preempted, match="emergency checkpoint"):
        half.train()
    assert half.ckpt.latest_step() == 2
    half.close()

    res = make_trainer(AlgoName.GRPO, tmp_path / "half", total_episodes=48,
                       save_steps=0)
    res.resume_from_checkpoint()
    assert res.state["global_step"] == 2
    res.train()
    res.close()

    a = _metric_rows(tmp_path / "full" / "grpo")
    b = _metric_rows(tmp_path / "half" / "grpo")
    assert len(a) == len(b) == 3
    for key in STREAM_KEYS + ("loss/policy_avg_new",):
        np.testing.assert_allclose(a[-1][key], b[-1][key], rtol=1e-4,
                                   err_msg=key)


def test_sparse_trainer_polls_preemption(tmp_path):
    """The sparse runtime installs the same SIGTERM guard as the dense one —
    its loop must poll it too, or a preempted sparse run swallows SIGTERM
    and gets SIGKILLed with no emergency checkpoint."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import RLConfig
    from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer

    tok = ToyTokenizer(256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=256)
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path / "sp"),
        response_length=8, sample_n=2, total_episodes=64, kl_coef=0.0,
        per_device_train_batch_size=4, gradient_accumulation_steps=1,
        num_mini_batches=1, use_lora=False, gradient_checkpointing=False,
        mesh=MeshConfig(-1, 1, 1), save_steps=0, report_to="none",
    )
    rng = np.random.default_rng(0)
    tr = SparseGRPOTrainer(
        cfg, mcfg, tok, init_params(mcfg, jax.random.PRNGKey(0), jnp.float32),
        load_prompt_dataset("synthetic:64", tok, max_prompt_len=12),
        lambda prs, eos: rng.random(len(prs)).astype(np.float32),
    )
    tr._preemption.trigger()  # preempt before the first update completes
    with pytest.raises(Preempted, match="emergency checkpoint"):
        tr.train()
    assert tr.ckpt.latest_step() == tr.state["global_step"]
    tstate = tr.ckpt.load_trainer_state(tr.state["global_step"])
    assert tstate["rollouts"] == tr.state["rollouts"]  # sparse cursor saved
    tr.close()

    # the all-zero-advantage SKIP path must poll too: a skip streak would
    # otherwise bypass the bottom-of-loop poll forever
    cfg.output_dir = str(tmp_path / "sp2")  # tr is closed; reuse its config
    tr2 = SparseGRPOTrainer(
        cfg, mcfg, tok,
        init_params(mcfg, jax.random.PRNGKey(0), jnp.float32),
        load_prompt_dataset("synthetic:64", tok, max_prompt_len=12),
        lambda prs, eos: np.zeros(len(prs), np.float32),  # uniformly failed
    )
    tr2._preemption.trigger()
    with pytest.raises(Preempted, match="sparse skip streak"):
        tr2.train()
    assert tr2.ckpt.latest_step() == tr2.state["global_step"]
    tr2.close()


def test_rollback_rewinds_ewma_statistics(tmp_path):
    """The rollback path must restore checkpoint-era EWMA statistics, not
    the pre-trip ones — re-applying those would fold every replayed loss
    into the mean/variance twice."""
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=64,
                      learning_rate=0.0, save_steps=2,
                      fault_spec="update.step:at=4,action=nan")
    tr.train()
    assert tr.sentinel.rollbacks == 1
    # checkpoint 2 journaled 2 observations; the trip at step 4 rolled back
    # PAST healthy step 3, whose batch is then replayed — its loss must be
    # folded into checkpoint-era statistics exactly once (pre-fix: the
    # carried pre-trip EWMA counted it twice → steps == global_step + 1)
    assert tr.sentinel.steps == tr.state["global_step"]
    tr.close()


# ---------------------------------------------------------------------------
# reward dispatch retry
# ---------------------------------------------------------------------------


def test_reward_failure_retried(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=16,
                      save_steps=0, fault_spec="reward.exec:at=1",
                      reward_retries=1)
    state = tr.train()
    assert state["global_step"] == 1  # the injected failure was absorbed
    tr.close()


def test_reward_retries_exhausted_raise(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=16,
                      save_steps=0, fault_spec="reward.exec:every=1,count=5",
                      reward_retries=1)
    with pytest.raises(InjectedFault):
        tr.train()
    tr.close()


# ---------------------------------------------------------------------------
# executor hardening (spawn context + kill escalation)
# ---------------------------------------------------------------------------


def test_executor_spawn_context_and_sigterm_immune_child():
    from nanorlhf_tpu.rewards.python_executor import PythonExecutor

    ex = PythonExecutor(timeout=1.0, term_grace=0.5)
    assert ex.mp_context == "spawn"
    r = ex.run("answer = 6 * 7")
    assert r.ok and r.answer == "42"
    # a child that ignores SIGTERM must still die (kill escalation)
    r = ex.run(
        "import signal, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "time.sleep(60)\n"
    )
    assert not r.ok and "timeout" in r.error


# ---------------------------------------------------------------------------
# backoff_delay properties (docs/RESILIENCE.md: jittered retry schedule)
# ---------------------------------------------------------------------------


def test_backoff_delay_no_jitter_is_exact_exponential():
    from nanorlhf_tpu.resilience import backoff_delay

    base, cap = 0.1, 5.0
    for attempt in range(12):
        expect = min(cap, base * (2 ** attempt))
        assert backoff_delay(attempt, base, cap) == expect
    # negative attempts clamp to attempt 0, never shrink below base
    assert backoff_delay(-3, base, cap) == base


def test_backoff_delay_jitter_bounds_and_cap():
    import random

    from nanorlhf_tpu.resilience import backoff_delay

    base, cap, jitter = 0.05, 2.0, 0.25
    rng = random.Random(11)
    for attempt in range(64):
        a = attempt % 10
        d = backoff_delay(a, base, cap, jitter=jitter, rng=rng)
        raw = min(cap, base * (2 ** a))
        # spread is uniform over +/- jitter * raw, then re-capped
        assert d <= cap + 1e-12
        assert raw * (1.0 - jitter) - 1e-12 <= d
        assert d <= min(cap, raw * (1.0 + jitter)) + 1e-12


def test_backoff_delay_seeded_rng_is_deterministic():
    import random

    from nanorlhf_tpu.resilience import backoff_delay

    def seq(seed):
        rng = random.Random(seed)
        return [backoff_delay(a, 0.1, 10.0, jitter=0.5, rng=rng)
                for a in range(16)]

    assert seq(7) == seq(7)
    assert seq(7) != seq(8)


def test_backoff_delay_default_stream_not_global_random():
    """The rng=None default draws from a module-level SEEDED stream, so
    unrelated code reseeding the global `random` module cannot change
    the retry schedule (and the schedule actually varies — jitter is
    real, not a constant)."""
    import random

    from nanorlhf_tpu.resilience import retry as retry_mod
    from nanorlhf_tpu.resilience.retry import backoff_delay

    state = retry_mod._JITTER_RNG.getstate()
    try:
        retry_mod._JITTER_RNG.setstate(
            random.Random(0x6A177E12).getstate())
        random.seed(123)
        first = [backoff_delay(a, 0.1, 10.0, jitter=0.5)
                 for a in range(8)]
        retry_mod._JITTER_RNG.setstate(
            random.Random(0x6A177E12).getstate())
        random.seed(999)  # perturbing the global module changes nothing
        second = [backoff_delay(a, 0.1, 10.0, jitter=0.5)
                  for a in range(8)]
    finally:
        retry_mod._JITTER_RNG.setstate(state)
    assert first == second
    assert len(set(first)) > 1  # jitter varies across draws


# ---------------------------------------------------------------------------
# fault matrix: corrupt latest checkpoint -> fallback to earlier intact
# ---------------------------------------------------------------------------


def test_ckpt_corrupt_latest_falls_back_to_earlier_intact(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1)
    tr.train()
    tr.close()
    assert tr.ckpt.latest_step() == 2
    # the latest checkpoint reads as torn exactly once -> restore walks
    # down to step 1 instead of failing the resume
    res = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                       fault_spec="ckpt.corrupt:at=1",
                       ckpt_retry_backoff=0.01)
    res.resume_from_checkpoint()
    assert res.ckpt.fallback_count == 1
    assert res.ckpt.last_restored_step == 1
    assert res.state["global_step"] == 1  # adopted the fallback step
    # training onward from the fallback recommits step 2 and journals
    # the fallback on the metric surface
    res.train()
    res.close()
    rows = _metric_rows(tmp_path / "reinforce")
    assert rows[-1]["resilience/ckpt_fallbacks"] == 1.0
    assert res.ckpt.latest_step() == 2


def test_ckpt_really_corrupt_tree_falls_back(tmp_path):
    """Genuine on-disk damage (not just the injected site): gut the
    newest committed tree's payload files; restore must exhaust its
    retries on the damaged candidate and fall back to step 1."""
    import shutil

    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1)
    tr.train()
    tr.close()
    tree = tmp_path / "reinforce" / "checkpoint-2" / "tree"
    assert tree.exists()
    for child in tree.iterdir():  # keep the dir: still "committed"
        shutil.rmtree(child) if child.is_dir() else child.unlink()
    res = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                       ckpt_io_retries=1, ckpt_retry_backoff=0.01)
    res.resume_from_checkpoint()
    assert res.ckpt.fallback_count == 1
    assert res.ckpt.last_restored_step == 1
    assert res.state["global_step"] == 1
    res.close()


def test_ckpt_corrupt_everything_raises(tmp_path):
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                      save_steps=1)
    tr.train()
    tr.close()
    # every candidate reads as torn -> nothing intact at or below the
    # requested step -> the failure surfaces instead of a silent skip
    res = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=32,
                       fault_spec="ckpt.corrupt:every=1,count=9",
                       ckpt_retry_backoff=0.01)
    with pytest.raises(InjectedFault):
        res.resume_from_checkpoint()
    res.close()
