"""Advantage estimators for all six algorithms — pure jnp, jit-safe.

Each function re-states, as a standalone pure function, advantage math the
reference inlines inside a 700-line `train()` body (SURVEY.md §2.4):

- GRPO group z-score       `/root/reference/GRPO/grpo_trainer.py:502-519`
- RLOO leave-one-out       `/root/reference/RLOO/rloo_trainer.py:595-599`
- ReMax greedy baseline    `/root/reference/ReMax/remax_trainer.py:506-513`
- PPO GAE(γ, λ)            `/root/reference/PPO/ppo_trainer.py:687-697`
- REINFORCE γ-discounting  `/root/reference/REINFORCE/reinforce_trainer.py:583-588`
- RAFT best-of-K           `/root/reference/RAFT/raft_trainer.py:585-588`
- sparse terminal reward   `/root/reference/GRPO/grpo_trainer.py:596-603`
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def grpo_group_advantage(scores: jnp.ndarray, sample_n: int) -> jnp.ndarray:
    """Per-prompt-group z-score: (r - mean_group) / std_group.

    `scores` is flat [B*N] ordered group-major (N consecutive samples per
    prompt — the order the sampler emits). Uses the unbiased (ddof=1) std to
    match `torch.Tensor.std` in the reference (`GRPO/grpo_trainer.py:508`).
    A zero-variance group divides 0/0; the reference maps the resulting NaN
    to 0 (`:513`), and so do we.
    """
    groups = scores.reshape(-1, sample_n).astype(jnp.float32)
    mean = groups.mean(axis=1, keepdims=True)
    std = jnp.sqrt(
        jnp.sum((groups - mean) ** 2, axis=1, keepdims=True) / (sample_n - 1)
    )
    adv = (groups - mean) / std
    adv = jnp.where(jnp.isnan(adv), 0.0, adv)
    return adv.reshape(-1)


def rloo_advantage(rlhf_reward: jnp.ndarray, sample_n: int) -> jnp.ndarray:
    """Leave-one-out baseline: r_i - mean(r_{j != i}).

    `rlhf_reward` is the flat [B*N] *sequence-level* reward (score + KL
    penalty summed over tokens), group-major. (`RLOO/rloo_trainer.py:595-599`.)
    """
    groups = rlhf_reward.reshape(-1, sample_n).astype(jnp.float32)
    baseline = (groups.sum(axis=1, keepdims=True) - groups) / (sample_n - 1)
    return (groups - baseline).reshape(-1)


def remax_advantage(scores: jnp.ndarray, greedy_scores: jnp.ndarray) -> jnp.ndarray:
    """Sampled-rollout reward minus greedy-rollout reward for the same prompt.

    (`ReMax/remax_trainer.py:513`.)
    """
    return scores - greedy_scores


def best_of_k_indices(
    rlhf_reward: jnp.ndarray, sample_k: int, key: jax.Array | None = None
) -> jnp.ndarray:
    """RAFT selection: index of the best of K samples per prompt.

    The reference computes argmax then immediately overwrites it with a random
    index (`RAFT/raft_trainer.py:585-588`) — the argmax is the documented
    intent ("keep those max reward RAFT samples"). We implement the intent:
    argmax by default; pass `key` to reproduce the as-shipped random-of-K.
    """
    groups = rlhf_reward.reshape(-1, sample_k)
    if key is not None:
        return jax.random.randint(key, (groups.shape[0],), 0, sample_k)
    return jnp.argmax(groups, axis=1)


def keep_one_of_n_indices(key: jax.Array, batch_size: int, sample_n: int) -> jnp.ndarray:
    """GRPO/RLOO keep-1-of-N: a uniformly random sample index per prompt.

    Used to drop N-1 of the N rollouts after the group baseline is computed,
    to save forward/backward time (`GRPO/grpo_trainer.py:505,510`).
    """
    return jax.random.randint(key, (batch_size,), 0, sample_n)


def sparse_terminal_rewards(
    scores: jnp.ndarray,
    sequence_lengths: jnp.ndarray,
    response_length: int,
    kl_penalty: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Build the per-token reward tensor: sparse score at EOS (+ optional KL).

    The score lands at `min(seq_len + 1, seq_len_if_out_of_range)` — i.e. one
    past the last real token when that position exists, else on the last token
    (`GRPO/grpo_trainer.py:596-603`). `kl_penalty`, when given, is the dense
    `-kl_coef * (logprobs - ref_logprobs)` term added at every position
    (KL-in-reward algorithms, e.g. `RLOO/rloo_trainer.py:570-578`).
    """
    batch = scores.shape[0]
    rewards = (
        jnp.zeros((batch, response_length), dtype=jnp.float32)
        if kl_penalty is None
        else kl_penalty.astype(jnp.float32)
    )
    seq_p1 = sequence_lengths + 1
    actual_end = jnp.where(seq_p1 < response_length, seq_p1, sequence_lengths)
    return rewards.at[jnp.arange(batch), actual_end].add(scores.astype(jnp.float32))


def grpo_turn_advantage(turn_rewards: jnp.ndarray, sample_n: int) -> jnp.ndarray:
    """Per-turn GRPO advantage: z-score each turn column within its group.

    `turn_rewards` is [B*N, K] group-major (K = max turns; absent turns
    hold 0 and a whole-group-absent column z-scores to 0 via the NaN→0
    rule). Normalizing per (group, turn-column) instead of on episode
    totals keeps the GRPO baseline semantics while crediting each turn
    against the SAME turn of its siblings — a strong turn 2 after a weak
    turn 1 is rewarded as such, not averaged away. Degenerate K=1 is
    exactly `grpo_group_advantage`.
    """
    rows, k = turn_rewards.shape
    groups = turn_rewards.reshape(-1, sample_n, k).astype(jnp.float32)
    mean = groups.mean(axis=1, keepdims=True)
    std = jnp.sqrt(
        jnp.sum((groups - mean) ** 2, axis=1, keepdims=True) / (sample_n - 1)
    )
    adv = (groups - mean) / std
    adv = jnp.where(jnp.isnan(adv), 0.0, adv)
    return adv.reshape(rows, k)


def per_turn_terminal_rewards(
    turn_rewards: jnp.ndarray,
    turn_ends: jnp.ndarray,
    response_length: int,
) -> jnp.ndarray:
    """Sparse per-token rewards with one spike at EACH turn's final token.

    Multi-turn generalization of `sparse_terminal_rewards`: `turn_ends`
    [B, K] holds the response-coordinate index of each turn's last model
    token (−1 for absent turns — dropped via out-of-range scatter). Running
    `discounted_returns(γ=1)` over the result broadcasts each turn's
    credit over the tokens that produced it AND every earlier turn —
    reward-to-go per turn, the per-turn attribution the multi-turn GRPO
    path scores with.
    """
    batch = turn_rewards.shape[0]
    rewards = jnp.zeros((batch, response_length), jnp.float32)
    ends = jnp.where(turn_ends < 0, response_length, turn_ends)
    return rewards.at[
        jnp.arange(batch)[:, None], ends
    ].add(turn_rewards.astype(jnp.float32), mode="drop")


@partial(jax.jit, static_argnames=("gamma",))
def discounted_returns(rewards: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """Reversed cumulative sum with discount: A_t = r_t + γ A_{t+1}.

    γ=1 is the GRPO token-advantage broadcast (`GRPO/grpo_trainer.py:610-620`);
    γ<1 is REINFORCE (`REINFORCE/reinforce_trainer.py:583-588`).

    Jitted, like `gae`: the trainer calls both from the host once per update,
    and an eager `lax.scan` over a per-call closure is a new function to jax
    every time — one backend compile per update, for ever (`perf/recompiles`
    showed it on the first run that looked).
    """

    def step(carry, r_t):
        a_t = r_t + gamma * carry
        return a_t, a_t

    _, out = jax.lax.scan(step, jnp.zeros_like(rewards[:, 0]), rewards.T, reverse=True)
    return out.T


@partial(jax.jit, static_argnames=("gamma", "lam"))
def gae(
    rewards: jnp.ndarray, values: jnp.ndarray, gamma: float, lam: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Generalized advantage estimation.

    `values[:, t]` is the value of the state *before* emitting token t; there
    are T positions (value at one-past-EOS already zeroed by padding_mask_p1).
    delta_t = r_t + γ V_{t+1} - V_t ; A_t = delta_t + γλ A_{t+1} ;
    returns = A + V. (`PPO/ppo_trainer.py:687-697`.)
    """
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
    )

    def step(carry, inp):
        r_t, v_t, nv_t = inp
        delta = r_t + gamma * nv_t - v_t
        a_t = delta + gamma * lam * carry
        return a_t, a_t

    _, out = jax.lax.scan(
        step,
        jnp.zeros_like(rewards[:, 0]),
        (rewards.T, values.T, next_values.T),
        reverse=True,
    )
    advantages = out.T
    returns = advantages + values
    return advantages, returns
