"""Pure sequence-masking / whitening numerics shared by every algorithm.

These are the TPU-native equivalents of the TRL helpers the reference trainers
import (`/root/reference/GRPO/grpo_trainer.py:54` — `first_true_indices`,
`truncate_response`, `masked_mean`, `masked_whiten`, `exact_div`) plus the
padding-mask construction inlined in every `train()` body
(`/root/reference/GRPO/grpo_trainer.py:588-594`).

All functions are pure jnp so they can live inside a jit/pjit-compiled step.
Semantics are pinned by unit tests in tests/test_masking.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Sentinel written into logprob tensors at padded positions
# (`/root/reference/GRPO/grpo_trainer.py:81,591-592`). A *positive* logprob is
# impossible, so downstream masked reductions can never confuse it with data —
# but it must be masked out before any mean/sum.
INVALID_LOGPROB = 1.0


def exact_div(a: int, b: int, custom_error_message: str = "") -> int:
    """Integer division that refuses to lose a remainder.

    Batch-size hierarchy guard (`/root/reference/GRPO/grpo_trainer.py:226-231`).
    """
    q = a // b
    if a != q * b:
        raise ValueError(f"{custom_error_message}, inexact division: {a} / {b} = {a / b}")
    return q


def first_true_indices(bools: jnp.ndarray, dtype=jnp.int32) -> jnp.ndarray:
    """Index of the first True along the last axis; row length if no True.

    Matches TRL `first_true_indices` used for sequence-length discovery
    (`/root/reference/GRPO/grpo_trainer.py:565`).
    """
    row_len = bools.shape[-1]
    idxs = jnp.arange(row_len, dtype=dtype)
    # Where False, pretend the index is row_len so min() skips it.
    masked = jnp.where(bools, idxs, row_len)
    return jnp.min(masked, axis=-1).astype(dtype)


def truncate_response(
    stop_token_id: int, pad_token_id: int, responses: jnp.ndarray
) -> jnp.ndarray:
    """Replace everything *after* the first stop token with pad.

    The stop token itself is kept — identical contract to TRL
    `truncate_response` (used at `/root/reference/GRPO/grpo_trainer.py:559-562`).
    """
    trunc_idxs = first_true_indices(responses == stop_token_id)[..., None]
    idxs = jnp.arange(responses.shape[-1])
    idxs = jnp.broadcast_to(idxs, responses.shape)
    return jnp.where(idxs > trunc_idxs, pad_token_id, responses)


def masked_mean(values: jnp.ndarray, mask: jnp.ndarray, axis=None) -> jnp.ndarray:
    """Mean of `values` over positions where `mask` is True."""
    mask = mask.astype(values.dtype)
    return jnp.sum(values * mask, axis=axis) / jnp.maximum(jnp.sum(mask, axis=axis), 1e-8)


def masked_var(
    values: jnp.ndarray, mask: jnp.ndarray, unbiased: bool = True
) -> jnp.ndarray:
    """Variance over masked positions, with Bessel correction by default.

    Mirrors TRL `masked_var` semantics (global reduction, used inside
    `masked_whiten` at e.g. `/root/reference/GRPO/grpo_trainer.py:608`).
    """
    mean = masked_mean(values, mask)
    centered = values - mean
    var = masked_mean(centered * centered, mask)
    if unbiased:
        n = jnp.sum(mask.astype(values.dtype))
        bessel = n / jnp.maximum(n - 1, 1.0)
        var = var * bessel
    return var


def masked_whiten(
    values: jnp.ndarray, mask: jnp.ndarray, shift_mean: bool = True
) -> jnp.ndarray:
    """Whiten to zero mean / unit variance over masked positions.

    `shift_mean=False` keeps the original mean (reward whitening path,
    `/root/reference/GRPO/grpo_trainer.py:606-608`).
    """
    mean = masked_mean(values, mask)
    var = masked_var(values, mask)
    whitened = (values - mean) * jax.lax.rsqrt(var + 1e-8)
    if not shift_mean:
        whitened = whitened + mean
    return whitened


def response_padding_masks(responses: jnp.ndarray, sequence_lengths: jnp.ndarray):
    """Build the (padding_mask, padding_mask_p1) pair every trainer uses.

    `sequence_lengths` is the index of the last real generated token.
    `padding_mask` is True strictly after it (logprobs/advantages);
    `padding_mask_p1` is True strictly after the one-past position
    (values/rewards). (`/root/reference/GRPO/grpo_trainer.py:588-594`.)
    """
    response_idxs = jnp.broadcast_to(
        jnp.arange(responses.shape[-1]), responses.shape
    )
    padding_mask = response_idxs > sequence_lengths[..., None]
    padding_mask_p1 = response_idxs > (sequence_lengths[..., None] + 1)
    return padding_mask, padding_mask_p1


# Floor for every temperature division in the repo. ONE constant, ONE guard:
# the sampler's decode-time logprob capture, the scoring-pass
# `logprobs_from_logits`, and the update-pass entropy stat previously used
# three different guards (max(t, 1e-6) / raw t / t + 1e-7), so captured
# behavior logprobs and scoring logprobs disagreed bit-for-bit at small
# temperatures — exactly where the IS-ratio math is most sensitive.
MIN_TEMPERATURE = 1e-6


def guard_temperature(temperature):
    """`max(temperature, MIN_TEMPERATURE)` — the shared division guard.

    Accepts a static python float (sampler/scoring pass the config value,
    returning a float that folds into the jitted graph as a constant) or a
    traced array.
    """
    if isinstance(temperature, (int, float)):
        return max(float(temperature), MIN_TEMPERATURE)
    return jnp.maximum(temperature, MIN_TEMPERATURE)


@jax.named_scope("logprob")
def logprobs_from_logits(
    logits: jnp.ndarray, labels: jnp.ndarray, temperature: float = 1.0
) -> jnp.ndarray:
    """log softmax(logits / temperature) gathered at `labels`.

    Temperature divides the logits *before* log-softmax, exactly as in the
    reference logprob pass (`/root/reference/GRPO/grpo_trainer.py:547-549`),
    through the shared `guard_temperature` floor (so sampler-captured and
    scoring logprobs agree bit-for-bit at any temperature).

    Memory-shaped for big vocabularies: computed as
    `logit[label]/T − logsumexp(logits/T)` so no [B, T, V] log-softmax (or
    f32 copy of the logits) is ever materialized — the f32 convert fuses
    into the logsumexp reduction. At Qwen2's 152k vocab this halves the
    peak HBM of the scoring/update passes. f32 math throughout. (The
    fully-fused path that never sees [B, T, V] logits at all lives in
    ops/fused_logprob.py.)
    """
    temperature = guard_temperature(temperature)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32) / temperature, axis=-1
    )
    return label_logits.astype(jnp.float32) / temperature - lse


def entropy_from_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Per-position entropy: logsumexp(z) - sum softmax(z) * z.

    Matches the stats computation at
    `/root/reference/GRPO/grpo_trainer.py:679-680`.
    """
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return jax.scipy.special.logsumexp(logits, axis=-1) - jnp.sum(
        probs * logits, axis=-1
    )
