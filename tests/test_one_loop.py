"""The one training loop: `RLTrainer.train()` is a list of phases and
`SparseGRPOTrainer` overrides three of them (select, score, update).

The golden cases hold the refactor to the parent's arithmetic: for every
configuration below, `tests/data/one_loop_golden_pr28.json` holds the
`metrics.jsonl` rows and a digest of the trained parameters as the parent
commit (PR 28, two loops) wrote them on the CPU mesh. Recorded with
`ONE_LOOP_RECORD=1 python -m pytest tests/test_one_loop.py -k golden` in a
`git archive` of the parent. One record is the parent's with one line
changed, and says so in its `recorded_with`: `sparse_capture`, because the
parent's sparse rollout handed `generate()` unsharded prompts (the 8-device
mesh then computes the rollout replicated) and the one rollout body places
them batch-sharded, which moves the sampler-captured logprobs in the last
bit; with that placement in the parent, rows and parameters are the same.
"""

import dataclasses
import hashlib
import inspect
import json
import os
import re
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.data import ToyTokenizer
from nanorlhf_tpu.entrypoints.grpo_r1 import (
    build_prompt_dataset,
    make_accuracy_func,
    make_r1_reward,
    synthetic_math_corpus,
)
from nanorlhf_tpu.parallel import MeshConfig, make_mesh
from nanorlhf_tpu.resilience import Preempted
from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer
from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer
from test_sparse_grpo_sp import det_reward
from test_trainer_smoke import make_trainer

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "one_loop_golden_pr28.json")
# wall-clock keys: everything else in a row is arithmetic of the run
CLOCK_PREFIXES = ("time/", "perf/", "latency/")
CLOCK_KEYS = ("time", "t_mono", "trainer/iteration_s", "sec_per_episode")
# what the shared phases write, and the sparse loop of the parent did not
GAINED = ("time/rollout_s", "time/reward_s", "time/logprob_s",
          "time/update_s", "trainer/iteration_s")


def sparse_trainer(tmp_path, name, reward, *, mesh=None, accuracy_func=None,
                   **overrides):
    """The configurations of tests/test_sparse_grpo.py and
    tests/test_sparse_grpo_sp.py, by keyword."""
    tok = ToyTokenizer(512)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=512)
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    dataset = build_prompt_dataset(synthetic_math_corpus(32), tok,
                                   max_prompt_len=16)
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path / name),
        response_length=8, temperature=1.0, sample_n=2, kl_coef=0.0,
        total_episodes=16, per_device_train_batch_size=1,
        gradient_accumulation_steps=1, num_mini_batches=1,
        learning_rate=1e-4, use_lora=True, lora_r=4, lora_alpha=8,
        gradient_checkpointing=False, mesh=MeshConfig(-1, 1, 1),
        save_steps=0, eval_steps=0, report_to="jsonl",
    )
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return SparseGRPOTrainer(cfg, mcfg, tok, params, dataset, reward,
                             mesh=mesh, accuracy_func=accuracy_func)


def noisy_reward():
    rng = np.random.default_rng(0)
    return lambda prs, ids, tok: rng.random(len(prs)).astype(np.float32)


def _dense(algo, **kw):
    return lambda tmp: make_trainer(algo, tmp, total_episodes=32, **kw)


def _sparse_end_to_end(tmp):
    eval_qa = synthetic_math_corpus(8, seed=1)
    return sparse_trainer(
        tmp, "r1", noisy_reward(), total_episodes=64,
        gradient_accumulation_steps=2, num_mini_batches=2, eval_steps=2,
        save_steps=2,
        accuracy_func=make_accuracy_func(eval_qa, max_prompt_len=16,
                                         eval_response_length=4,
                                         use_subprocess=False))


def _sparse_all_zero(tmp):
    reward = make_r1_reward(dict(synthetic_math_corpus(32)),
                            use_subprocess=False)
    return sparse_trainer(tmp, "r0", reward, response_length=4)


def _sparse_sp(n_sp):
    def build(tmp):
        mesh = make_mesh(MeshConfig(1, 1, 1, n_sp), devices=jax.devices()[:n_sp])
        return sparse_trainer(tmp, f"sp{n_sp}", det_reward, mesh=mesh,
                              kl_coef=0.05, total_episodes=4,
                              per_device_train_batch_size=2,
                              learning_rate=1e-3)
    return build


# name -> (builder, updates asked of train(); None runs total_episodes out)
CASES = {
    "reinforce": (_dense(AlgoName.REINFORCE, advantage_whiten=True), None),
    "grpo": (_dense(AlgoName.GRPO), None),
    "rloo": (_dense(AlgoName.RLOO), None),
    "raft": (_dense(AlgoName.RAFT), None),
    "remax": (_dense(AlgoName.REMAX), None),
    "ppo_value": (_dense(AlgoName.PPO), None),
    "grpo_capture": (_dense(AlgoName.GRPO, sampler_logprob_capture=True), None),
    "sparse_end_to_end": (_sparse_end_to_end, None),
    "sparse_all_zero_skip": (_sparse_all_zero, None),
    "sparse_capture": (lambda tmp: sparse_trainer(
        tmp, "cap", noisy_reward(), sampler_logprob_capture=True), 1),
    "sparse_sp1": (_sparse_sp(1), None),
    "sparse_sp2": (_sparse_sp(2), None),
}


def _is_clock(key):
    return key in CLOCK_KEYS or key.startswith(CLOCK_PREFIXES)


def _digest(tr):
    h = hashlib.sha256()
    for tree in (tr.params, tr.value_params):
        for leaf in jax.tree.leaves(tree):
            a = np.asarray(leaf)
            h.update(str((a.dtype, a.shape)).encode())
            h.update(a.tobytes())
    return h.hexdigest()


_RUNS = {}


def cached_run(name, tmp_path_factory):
    """Each configuration trains once a process, whichever test asks."""
    if name not in _RUNS:
        _RUNS[name] = run_case(name, tmp_path_factory.mktemp(name))
    return _RUNS[name]


def run_case(name, tmp_path):
    build, updates = CASES[name]
    tr = build(tmp_path)
    state = tr.train(num_updates=updates)
    tr.close()
    path = os.path.join(tr.cfg.output_dir, "metrics.jsonl")
    rows = [json.loads(line) for line in open(path)]
    counts = dict(tr.timer.cumulative_counts)
    return {"rows": rows, "digest": _digest(tr), "counts": counts,
            "state": {k: state[k] for k in ("global_step", "rollouts",
                                            "episode", "opt_steps")}}


@pytest.mark.parametrize("name", sorted(CASES))
def test_golden_rows_and_parameters_reproduce(name, tmp_path_factory):
    got = cached_run(name, tmp_path_factory)
    if os.environ.get("ONE_LOOP_RECORD"):
        book = json.load(open(GOLDEN)) if os.path.exists(GOLDEN) else {}
        book[name] = {
            "rows": [{k: v for k, v in r.items() if not _is_clock(k)}
                     for r in got["rows"]],
            "digest": got["digest"], "state": got["state"]}
        with open(GOLDEN, "w") as f:
            json.dump(book, f, indent=0, sort_keys=True)
        return
    want = json.load(open(GOLDEN))[name]
    assert got["state"] == want["state"]
    assert len(got["rows"]) == len(want["rows"])
    for i, (g, w) in enumerate(zip(got["rows"], want["rows"])):
        have = {k: v for k, v in g.items() if not _is_clock(k)}
        # a counter the golden's parent did not write (ISSUE 33): these
        # caches are one block, so the rollout read all of it
        assert have.pop("rollout/attn_read_frac", 1.0) == 1.0
        # ... and one that says the loop kept no pages (ISSUE 52: off the
        # TPU its cache stays contiguous)
        assert have.pop("rollout/kv_in_place", 0) == 0
        # ... and one that says which pick the sampler took (ISSUE 60:
        # static in the rollout's rows; the same tokens either way)
        assert have.pop("rollout/sample_pick", 0) in (0, 1)
        if name.startswith("sparse"):
            # every key the parent wrote, with its value; the keys the
            # shared phases add are counted in the test below
            have = {k: v for k, v in have.items() if k in w}
        assert have == w, f"{name} row {i}: " + str(
            {k: (have.get(k), w.get(k)) for k in set(have) | set(w)
             if have.get(k) != w.get(k)})
    assert got["digest"] == want["digest"]


# -- (a) one train(), three overridden phases ----------------------------------

LOOP = {"train", "_rollout_body", "_ensure_handles", "_degrade_to_sync",
        "_fetch_sample", "_rollout", "_reward", "_select", "_score",
        "_advantages", "_update", "_guard", "_publish", "_metrics_row",
        "_report", "_checkpoint", "_close_update", "_evaluate", "_save_checkpoint",
        "_dispatch_reward", "_sentinel_rollback", "resume_from_checkpoint"}


def _sources():
    import nanorlhf_tpu.trainer.sparse_grpo as sparse_mod
    import nanorlhf_tpu.trainer.trainer as trainer_mod

    return inspect.getsource(trainer_mod), inspect.getsource(sparse_mod)


def test_sparse_trainer_overrides_three_phases_and_the_eval_hook():
    own = set(vars(SparseGRPOTrainer))
    assert "train" not in own and "_sparse_save" not in own
    assert own & LOOP == {"_select", "_score", "_update", "_evaluate"}
    assert LOOP <= set(vars(RLTrainer))
    dense, sparse = _sources()
    assert len(re.findall(r"^ +def train\(", dense + sparse, re.M)) == 1
    # the loop's one preemption poll, and a base class that does not know
    # its subclass
    assert (dense + sparse).count("_preemption.triggered") == 1
    code = re.sub(r'""".*?"""|#[^\n]*', "", dense, flags=re.S)
    assert "Sparse" not in code and "_sparse" not in code


def test_loop_of_train_reads_as_the_list_of_phases():
    lines = inspect.getsource(RLTrainer.train).split("\n")
    start = next(i for i, l in enumerate(lines)
                 if l.lstrip().startswith("while "))
    indent = len(lines[start]) - len(lines[start].lstrip())
    body = 0
    for l in lines[start + 1:]:
        if l.strip() and len(l) - len(l.lstrip()) <= indent:
            break
        body += 1
    assert body < 120
    assert "def " not in "\n".join(lines[start:start + body])
    assert len(dataclasses.fields(RLConfig)) <= 151


# -- (b) the shared phases time the sparse runtime too -------------------------

@pytest.mark.parametrize(
    "name", ["sparse_end_to_end", "sparse_capture", "sparse_sp2", "grpo"])
def test_every_step_row_carries_the_phase_splits(name, tmp_path_factory):
    got = cached_run(name, tmp_path_factory)
    steps = [r for r in got["rows"] if "episode" in r and r["step"] > 0]
    assert len(steps) == got["state"]["global_step"] > 0
    for row in steps:
        assert all(k in row and row[k] >= 0.0 for k in GAINED), sorted(row)
        # the iteration holds its phases
        assert row["trainer/iteration_s"] >= sum(
            row[k] for k in GAINED if k.startswith("time/"))
    # PhaseTimer.phase entered once an update for each phase (the sparse
    # capture case scores nothing, and still enters its logprob phase)
    for phase in ("rollout", "reward", "logprob", "update"):
        assert got["counts"][phase] == got["state"]["rollouts"], got["counts"]


def test_a_skipped_update_enters_only_the_phases_it_ran(tmp_path_factory):
    got = cached_run("sparse_all_zero_skip", tmp_path_factory)
    assert got["state"]["rollouts"] == 2 and got["state"]["global_step"] == 0
    assert got["counts"].get("rollout") == got["counts"].get("reward") == 2
    assert not got["counts"].get("logprob") and not got["counts"].get("update")
    assert [r["sparse_skip/rollout_index"] for r in got["rows"]] == [1.0, 2.0]
    assert all("episode" not in r and "time/rollout_s" not in r
               for r in got["rows"])


# -- (d) a no-step update leaves through the loop's one way out ----------------

def test_sigterm_in_a_skip_streak_commits_through_the_one_poll(tmp_path):
    """Uniformly failed rollouts: every update ends in `select` without a
    step. `state["rollouts"]` advances, `global_step` does not, each skip
    leaves one `sparse_skip/*` event row, and a SIGTERM raised inside the
    streak (from the second rollout's grading) is seen by the loop's one
    preemption poll, which commits the emergency checkpoint."""
    calls = {"n": 0}
    holder = {}

    def zero_reward(prs, ids, tok):
        calls["n"] += 1
        if calls["n"] == 2:
            if holder["tr"]._preemption.installed:
                os.kill(os.getpid(), signal.SIGTERM)
            else:  # not the main thread: the guard's manual trigger
                holder["tr"]._preemption.trigger()
        return np.zeros(len(prs), np.float32)

    tr = holder["tr"] = sparse_trainer(tmp_path, "streak", zero_reward,
                                       total_episodes=64)  # budget: 8
    with pytest.raises(Preempted, match="sparse skip streak"):
        tr.train()
    assert calls["n"] == 2
    assert tr.state["rollouts"] == 2 and tr.state["global_step"] == 0
    rows = [json.loads(l) for l in open(tmp_path / "streak" / "metrics.jsonl")]
    assert [sorted(k for k in r if k.startswith("sparse_skip/"))
            for r in rows] == [["sparse_skip/raw_score_mean",
                                "sparse_skip/rollout_index"]] * 2
    assert tr.ckpt.latest_step() == 0
    assert tr.ckpt.load_trainer_state(0)["rollouts"] == 2
    tr.close()

    # and the streak ends with train()'s budget when nothing interrupts it
    res = sparse_trainer(tmp_path, "streak", zero_reward, total_episodes=64)
    res.resume_from_checkpoint()
    assert res.state["rollouts"] == 2
    res.train(num_updates=3)
    assert res.state["rollouts"] == 5 and res.state["global_step"] == 0
    res.close()


def test_a_rollback_that_replays_a_skip_charges_it_once(tmp_path):
    """Both ways out in one run. With the smoke reward the second rollout
    of this configuration is an all-zero skip (one update of the budget of
    4, no step) and the third trips the sentinel: the rollback rewinds to
    checkpoint 1, whose cursor replays the skipped rollout. The replayed
    skip is not charged again, so the run still makes its three steps."""
    tr = make_trainer(AlgoName.GRPO, tmp_path, trainer_cls=SparseGRPOTrainer,
                      total_episodes=64, learning_rate=0.0, save_steps=1,
                      fault_spec="update.step:at=2,action=nan")
    state = tr.train()
    tr.close()
    rows = [json.loads(l) for l in open(tmp_path / "grpo" / "metrics.jsonl")]
    skips = [r["sparse_skip/rollout_index"] for r in rows
             if "sparse_skip/rollout_index" in r]
    assert skips == [2.0, 2.0]  # rollout 1, and its replay
    assert tr.sentinel.rollbacks == 1 and tr.sentinel.quarantined == {2}
    assert state["global_step"] == 3 and state["rollouts"] == 5
