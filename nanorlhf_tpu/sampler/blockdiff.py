"""Sampling for generation by diffusion over blocks (docs/BLOCKDIFF.md).

A denoise forward gives logits at every position of a row's block; this
module turns them into the forward's result for the row:

- `sample_positions`: a token at each position it is given (greedy, or
  temperature / top-p over the serving sampler's candidate set, per row)
  and its CONFIDENCE, the token's probability under the distribution it was
  drawn from (a greedy token's: under the plain softmax of its logits);
- `transfer_count`: how many masked positions step s of S unmasks, the
  block's B spread over the S steps as evenly as integers allow, the earlier
  steps taking the remainder;
- `choose_unmask`: WHICH masked positions a row unmasks, by its strategy
  (`REMASKING`): `low_confidence_static` the `n` of highest confidence,
  `low_confidence_dynamic` every one whose confidence passes the threshold
  and at least the static step's `n` by rank, `sequential` the leftmost `n`.
  Ties go to the lower position; a position already unmasked is never
  chosen (a token once unmasked never changes).

Everything is per ROW: rows of one forward stand at different steps of
different blocks under different requests' parameters, as traced `[R]`
arrays of one compiled program (`sampler/paged/session._block_body`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nanorlhf_tpu.ops.masking import guard_temperature
from nanorlhf_tpu.sampler.sampler import (
    _categorical_rows, _nucleus_candidates,
)

# a request's `remasking`, by name; a row carries the index
REMASKING = ("low_confidence_static", "low_confidence_dynamic", "sequential")
DYNAMIC, SEQUENTIAL = (REMASKING.index("low_confidence_dynamic"),
                       REMASKING.index("sequential"))
# the family's script's default threshold for `low_confidence_dynamic`
CONFIDENCE_THRESHOLD = 0.9


def transfer_count(step, steps, block_length: int):
    """Masked positions denoise step `step` (0-based) of `steps` unmasks:
    `block_length // steps`, one more in the first `block_length % steps`
    steps. Arrays of one shape, or ints."""
    return block_length // steps + (step < block_length % steps)


@jax.named_scope("sample")
def sample_positions(key, logits, temperature, top_p, greedy, *, top_k,
                     approx_top_k, draw=None):
    """logits [M, V], one row a POSITION of some row's block; temperature /
    top_p / greedy [M], the position's row's. Returns `(tokens [M] int32,
    confidence [M] float32)`: `session._serving_sample`'s draw at each
    position (one candidate set a position), and the drawn token's
    probability under the kept candidates' renormalised distribution; a
    greedy row takes each position's argmax and its probability under the
    plain softmax. `draw`: `sampler._categorical_rows`' (the positions given
    are some of a forward's, and draw as they would among all of them)."""
    flat = logits.astype(jnp.float32)
    scaled = flat / guard_temperature(temperature)[:, None]
    top_logits, top_idx, keep = _nucleus_candidates(
        scaled, top_p[:, None], top_k, approx_top_k)
    kept = jnp.where(keep, top_logits, -jnp.inf)
    choice = _categorical_rows(key, kept, draw)
    sampled = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    p_sampled = jnp.take_along_axis(
        jax.nn.softmax(kept, axis=-1), choice[:, None], axis=-1)[:, 0]
    best = jnp.argmax(flat, axis=-1)
    p_best = jnp.exp(jnp.max(flat, axis=-1)
                     - jax.nn.logsumexp(flat, axis=-1))
    return (jnp.where(greedy, best, sampled).astype(jnp.int32),
            jnp.where(greedy, p_best, p_sampled))


def choose_unmask(confidence, masked, n, remasking,
                  threshold: float = CONFIDENCE_THRESHOLD):
    """Which positions a forward unmasks, `[R, B]` bool. confidence [R, B]
    float32; masked [R, B] bool; n [R] int32 (`transfer_count`); remasking
    [R] int32 (an index into `REMASKING`)."""
    B = confidence.shape[1]
    pos = jnp.arange(B, dtype=jnp.int32)
    # what a row ranks its masked positions by: confidence, or (sequential)
    # how far left; an unmasked position ranks behind every masked one
    score = jnp.where((remasking == SEQUENTIAL)[:, None],
                      -pos[None, :].astype(jnp.float32), confidence)
    score = jnp.where(masked, score, -jnp.inf)
    ahead = ((score[:, None, :] > score[:, :, None])
             | ((score[:, None, :] == score[:, :, None])
                & (pos[None, None, :] < pos[None, :, None])))
    rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)      # [R, B]
    chosen = rank < n[:, None]
    over = (remasking == DYNAMIC)[:, None] & (confidence > threshold)
    return masked & (chosen | over)
