"""Loss functions for all six algorithms — pure jnp, jit/grad-safe.

Pinned to the per-trainer inlined losses (SURVEY.md §2.4):

- token-level PPO-clip       `/root/reference/GRPO/grpo_trainer.py:655-661`
- GRPO k3-KL term            `/root/reference/GRPO/grpo_trainer.py:662-672`
- sequence-level PPO-clip    `/root/reference/RLOO/rloo_trainer.py:660-669`
- clipped value loss         `/root/reference/PPO/ppo_trainer.py:742-756`
- RAFT SFT loss              `/root/reference/RAFT/raft_trainer.py:636`

Every function returns `(loss, aux)` where `aux` holds the detached stats the
reference accumulates per microbatch (`GRPO/grpo_trainer.py:674-689`).

The PPO-style losses optionally take `behavior_logprobs` — the logprobs of
the STALE policy that actually sampled the tokens under the async rollout
orchestrator (docs/ORCHESTRATOR.md) — and re-weight each loss term by the
truncated importance ratio min(π_old/μ, ρ̄) (`truncated_is_weights`), keeping
GRPO/PPO/RLOO unbiased at staleness ≥ 1. With `behavior_logprobs=None`
every loss is bit-identical to its synchronous form.

With in-flight mid-sequence weight swaps (`cfg.rollout_inflight_swaps`,
docs/ORCHESTRATOR.md §in-flight swaps) one generation spans SEVERAL behavior
policies — the ledger's `segments:[{policy_version, tok_range}]` list. The
losses then additionally take `segment_ages`: per-token int ages
`v_newest_segment_in_row − v_segment(t)` (0 everywhere for a single-segment
generation), and the ratio clamp tightens per segment — `segment_is_weights`.
A single-segment generation reduces BIT-EXACTLY to the whole-sequence path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nanorlhf_tpu.ops.masking import masked_mean


def truncated_is_weights(
    old_logprobs: jnp.ndarray,
    behavior_logprobs: jnp.ndarray,
    truncation: float,
):
    """Per-token truncated importance weight ρ = min(π_old/μ, ρ̄).

    Under the async rollout orchestrator (docs/ORCHESTRATOR.md) samples are
    generated by a STALE behavior policy μ (the sampler-captured logprobs at
    decode time), while the PPO-style losses clip against π_old — the policy
    the scoring pass measured at the START of the update. Re-weighting every
    token's loss term by π_old(a|s)/μ(a|s) restores the on-policy
    expectation E_μ[ρ·f] = E_{π_old}[f]; truncating at ρ̄ (IMPALA/V-trace
    style, PipelineRL §3) bounds the variance at a small, bounded bias.
    At staleness 0 (μ == π_old) every weight is exactly 1 and the losses
    reduce to their synchronous forms.

    Padded positions carry INVALID_LOGPROB in BOTH tensors, so their weight
    is exactly 1 — inert under the loss masks.

    Returns (weights, truncated_mask). Not differentiated: both inputs are
    data constants of the update, so no stop_gradient is needed.
    """
    log_ratio = old_logprobs - behavior_logprobs
    raw = jnp.exp(log_ratio)
    return jnp.minimum(raw, truncation), raw > truncation


def _segment_clamp(segment_ages: jnp.ndarray, truncation: float) -> jnp.ndarray:
    """Per-token truncation bound for multi-segment generations.

    `segment_ages[b, t]` = newest policy version in row b's generation minus
    the version that sampled token t (so the freshest segment is age 0). The
    expected |log π_old − log μ| drift grows with how far behind the policy a
    segment was sampled, so its IS ratios get a TIGHTER clamp: a geometric
    schedule on the log-bound, ρ̄_a = ρ̄^(1/(1+a)) → ρ̄ at age 0, → 1 as
    a → ∞ — the oldest segments contribute bounded-variance, near-uniform
    weights instead of exploding ratios. Age ≤ 0 returns the literal ρ̄, so
    an all-zero age map is BIT-IDENTICAL to the whole-sequence clamp.
    """
    age = segment_ages.astype(jnp.float32)
    return jnp.where(
        segment_ages <= 0,
        jnp.asarray(truncation, jnp.float32),
        jnp.exp(jnp.log(truncation) / (1.0 + age)),
    )


def segment_is_weights(
    old_logprobs: jnp.ndarray,
    behavior_logprobs: jnp.ndarray,
    segment_ages: jnp.ndarray,
    truncation: float,
):
    """Per-segment truncated IS weights for in-flight weight swaps.

    Same raw ratio as `truncated_is_weights` (π_old/μ per token — μ is
    whichever mid-rollout policy version actually sampled the token, since
    the sampler captures logprobs at decode time), but the clamp is the
    per-segment `_segment_clamp` bound instead of one global ρ̄. With
    `segment_ages` all zero (single-segment generation, or no swap fired)
    the result is bit-identical to `truncated_is_weights`.

    Returns (weights, truncated_mask).
    """
    raw = jnp.exp(old_logprobs - behavior_logprobs)
    clamp = _segment_clamp(segment_ages, truncation)
    return jnp.minimum(raw, clamp), raw > clamp


# static cap on distinct IS segments per row for the sequence-level (RLOO)
# weight: runs of constant age beyond the cap merge into the last slot. One
# segment per mid-rollout publish + 1 — a generation would need 8 publishes
# to overflow, far past any real staleness bound.
MAX_IS_SEGMENTS = 8


def _segment_onehot(segment_ages: jnp.ndarray) -> jnp.ndarray:
    """[B, MAX_IS_SEGMENTS, T] bool one-hot of age-run ids.

    Segments are maximal runs of constant `segment_ages` along the token
    axis — valid because versions are strictly increasing along a row, so
    two distinct segments can never share an age.
    """
    boundary = jnp.concatenate(
        [jnp.ones_like(segment_ages[:, :1], dtype=bool),
         segment_ages[:, 1:] != segment_ages[:, :-1]],
        axis=1,
    )
    run = jnp.cumsum(boundary.astype(jnp.int32), axis=1) - 1
    run = jnp.minimum(run, MAX_IS_SEGMENTS - 1)
    return run[:, None, :] == jnp.arange(MAX_IS_SEGMENTS)[None, :, None]


def _is_aux(weights, truncated, mask):
    return {
        "is_weight_mean": masked_mean(weights, mask),
        "is_trunc_frac": masked_mean(truncated.astype(jnp.float32), mask),
    }


def _ratio_and_stats(new_logprobs, old_logprobs):
    """logprob diff / importance ratio shared by all PPO-style losses.

    Padded positions carry INVALID_LOGPROB in both tensors, so their diff is 0
    and the ratio is exactly 1 there — harmless under the mask, and it keeps
    the unmasked `approxkl = 0.5 * mean(diff²)` identical to the reference's
    (`GRPO/grpo_trainer.py:684`).
    """
    logprobs_diff = new_logprobs - old_logprobs
    ratio = jnp.exp(logprobs_diff)
    approxkl = 0.5 * jnp.mean(logprobs_diff**2)
    return logprobs_diff, ratio, approxkl


@jax.named_scope("loss")
def ppo_clip_loss_token(
    new_logprobs: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    advantages: jnp.ndarray,
    mask: jnp.ndarray,
    cliprange: float,
    behavior_logprobs: jnp.ndarray | None = None,
    is_truncation: float = 2.0,
    segment_ages: jnp.ndarray | None = None,
):
    """Token-level clipped policy-gradient loss (PPO/ReMax/REINFORCE/GRPO core).

    `mask` is True on *real* tokens (the reference passes `~padding_mask`).
    `behavior_logprobs` (the sampler-captured logprobs of a STALE behavior
    policy) switches on truncated-IS off-policy correction: each token's
    clipped PG term is re-weighted by min(π_old/μ, is_truncation) — see
    `truncated_is_weights`. `segment_ages` (in-flight weight swaps) switches
    the clamp to the per-segment bound — see `segment_is_weights`.
    """
    _, ratio, approxkl = _ratio_and_stats(new_logprobs, old_logprobs)
    pg_losses = -advantages * ratio
    pg_losses2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange)
    pg_loss_max = jnp.maximum(pg_losses, pg_losses2)
    aux = {}
    if behavior_logprobs is not None:
        if segment_ages is not None:
            w, truncated = segment_is_weights(
                old_logprobs, behavior_logprobs, segment_ages, is_truncation
            )
        else:
            w, truncated = truncated_is_weights(
                old_logprobs, behavior_logprobs, is_truncation
            )
        pg_loss_max = w * pg_loss_max
        aux = _is_aux(w, truncated, mask)
    loss = masked_mean(pg_loss_max, mask)
    aux.update({
        "pg_clipfrac": masked_mean((pg_losses2 > pg_losses).astype(jnp.float32), mask),
        "approxkl": approxkl,
        "ratio_mean": jnp.mean(ratio),
        "pg_loss": loss,
    })
    return loss, aux


def k3_kl(new_logprobs: jnp.ndarray, ref_logprobs: jnp.ndarray) -> jnp.ndarray:
    """k3 KL estimator: e^{-kl} + kl - 1 where kl = logπ - logπ_ref.

    Always ≥ 0; the GRPO in-loss KL penalty (`GRPO/grpo_trainer.py:667-670`).
    """
    kl = new_logprobs - ref_logprobs
    return jnp.exp(-kl) + kl - 1.0


@jax.named_scope("loss")
def grpo_loss(
    new_logprobs: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    ref_logprobs: jnp.ndarray,
    advantages: jnp.ndarray,
    mask: jnp.ndarray,
    cliprange: float,
    kl_coef: float,
    behavior_logprobs: jnp.ndarray | None = None,
    is_truncation: float = 2.0,
    segment_ages: jnp.ndarray | None = None,
):
    """GRPO = token-level PPO-clip + kl_coef · k3-KL, jointly masked-meaned.

    (`GRPO/grpo_trainer.py:662-672` — note the KL term sits *inside* the
    masked mean with the clipped PG term.) With `behavior_logprobs` the
    truncated IS weight multiplies the WHOLE per-token objective (PG + KL):
    both terms are expectations over π_old's visitation, estimated here at
    tokens the stale behavior policy μ sampled (`truncated_is_weights`;
    per-segment clamp via `segment_ages` — `segment_is_weights`).
    """
    _, ratio, approxkl = _ratio_and_stats(new_logprobs, old_logprobs)
    pg_losses = -advantages * ratio
    pg_losses2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange)
    kl = new_logprobs - ref_logprobs
    kl_term = kl_coef * k3_kl(new_logprobs, ref_logprobs)
    pg_loss_max = jnp.maximum(pg_losses, pg_losses2) + kl_term
    aux = {}
    if behavior_logprobs is not None:
        if segment_ages is not None:
            w, truncated = segment_is_weights(
                old_logprobs, behavior_logprobs, segment_ages, is_truncation
            )
        else:
            w, truncated = truncated_is_weights(
                old_logprobs, behavior_logprobs, is_truncation
            )
        pg_loss_max = w * pg_loss_max
        aux = _is_aux(w, truncated, mask)
    loss = masked_mean(pg_loss_max, mask)
    aux.update({
        "pg_clipfrac": masked_mean((pg_losses2 > pg_losses).astype(jnp.float32), mask),
        "approxkl": approxkl,
        "ratio_mean": jnp.mean(ratio),
        "refkl_mean": jnp.mean(kl),
        "pg_loss": loss,
    })
    return loss, aux


@jax.named_scope("loss")
def ppo_clip_loss_sequence(
    new_logprobs: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    advantages: jnp.ndarray,
    mask: jnp.ndarray,
    cliprange: float,
    behavior_logprobs: jnp.ndarray | None = None,
    is_truncation: float = 2.0,
    segment_ages: jnp.ndarray | None = None,
):
    """Sequence-level PPO-clip (RLOO): ratio of summed logprobs, plain mean.

    The reference sums the INVALID_LOGPROB-filled tensors directly
    (`RLOO/rloo_trainer.py:660-662`); the pad contributions cancel in the
    diff, so masking before the sum is exactly equivalent.
    `advantages` is sequence-level, shape [B]. With `behavior_logprobs` the
    IS weight is sequence-level too: min(exp(Σ(logπ_old − logμ)), ρ̄).
    With `segment_ages` (in-flight weight swaps) the sequence weight
    factorizes over segments — Π_s min(exp(Σ_s(logπ_old − logμ)), ρ̄_s)
    with the per-segment `_segment_clamp` bound — computed over at most
    `MAX_IS_SEGMENTS` runs of constant age (later runs merge into the
    last, keeping the clamp of the OLDEST surviving segment; swaps per
    generation are bounded by publishes per rollout, so the cap is slack
    in practice). A single-segment row (age constant) is bit-identical to
    the whole-sequence weight.
    """
    mask_f = mask.astype(new_logprobs.dtype)
    new_sum = jnp.sum(new_logprobs * mask_f, axis=1)
    old_sum = jnp.sum(old_logprobs * mask_f, axis=1)
    logprobs_diff = new_sum - old_sum
    ratio = jnp.exp(logprobs_diff)
    pg_losses = -advantages * ratio
    pg_losses2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange)
    pg_loss_max = jnp.maximum(pg_losses, pg_losses2)
    aux = {}
    if behavior_logprobs is not None and segment_ages is not None:
        onehot = _segment_onehot(segment_ages)            # [B, S, T] bool
        oh_mask = onehot.astype(mask_f.dtype) * mask_f[:, None, :]
        old_sum_s = jnp.sum(old_logprobs[:, None, :] * oh_mask, axis=2)
        beh_sum_s = jnp.sum(behavior_logprobs[:, None, :] * oh_mask, axis=2)
        raw_s = jnp.exp(old_sum_s - beh_sum_s)            # [B, S]
        age_s = jnp.max(
            jnp.where(onehot, segment_ages[:, None, :], 0), axis=2
        )
        clamp_s = _segment_clamp(age_s, is_truncation)
        # a run holding no REAL token (the pad tail, unused slots) must
        # contribute a factor of exactly 1, not min(e^0, ρ̄)
        seg_has = jnp.any(onehot & mask[:, None, :], axis=2)
        w_s = jnp.where(seg_has, jnp.minimum(raw_s, clamp_s), 1.0)
        w = jnp.prod(w_s, axis=1)
        trunc_s = seg_has & (raw_s > clamp_s)
        pg_loss_max = w * pg_loss_max
        aux = {
            "is_weight_mean": jnp.mean(w),
            "is_trunc_frac": jnp.mean(
                jnp.any(trunc_s, axis=1).astype(jnp.float32)
            ),
        }
    elif behavior_logprobs is not None:
        behavior_sum = jnp.sum(behavior_logprobs * mask_f, axis=1)
        raw = jnp.exp(old_sum - behavior_sum)
        w = jnp.minimum(raw, is_truncation)
        pg_loss_max = w * pg_loss_max
        aux = {
            "is_weight_mean": jnp.mean(w),
            "is_trunc_frac": jnp.mean((raw > is_truncation).astype(jnp.float32)),
        }
    loss = jnp.mean(pg_loss_max)
    aux.update({
        "pg_clipfrac": jnp.mean((pg_losses2 > pg_losses).astype(jnp.float32)),
        "approxkl": 0.5 * jnp.mean(logprobs_diff**2),
        "ratio_mean": jnp.mean(ratio),
        "pg_loss": loss,
    })
    return loss, aux


@jax.named_scope("loss")
def value_loss_clipped(
    vpred: jnp.ndarray,
    values: jnp.ndarray,
    returns: jnp.ndarray,
    mask_p1: jnp.ndarray,
    cliprange_value: float,
):
    """PPO clipped value loss: 0.5 · masked_mean(max((v-R)², (v_clip-R)²)).

    `mask_p1` is True on valid value positions (~padding_mask_p1).
    (`PPO/ppo_trainer.py:742-748`.)
    """
    vpredclipped = jnp.clip(vpred, values - cliprange_value, values + cliprange_value)
    vf_losses1 = jnp.square(vpred - returns)
    vf_losses2 = jnp.square(vpredclipped - returns)
    vf_loss_max = jnp.maximum(vf_losses1, vf_losses2)
    loss = 0.5 * masked_mean(vf_loss_max, mask_p1)
    aux = {
        "vf_loss": loss,
        "vf_clipfrac": masked_mean((vf_losses2 > vf_losses1).astype(jnp.float32), mask_p1),
    }
    return loss, aux


@jax.named_scope("loss")
def sft_loss(new_logprobs: jnp.ndarray, mask: jnp.ndarray):
    """RAFT: negative summed logprob of the kept sample, mean over batch.

    The reference sums the INVALID_LOGPROB-filled tensor
    (`RAFT/raft_trainer.py:636`), adding a gradient-free -1·n_pad constant per
    row; we mask before summing — identical gradients, cleaner loss value.
    """
    mask_f = mask.astype(new_logprobs.dtype)
    loss = -jnp.mean(jnp.sum(new_logprobs * mask_f, axis=1))
    return loss, {"pg_loss": loss}
