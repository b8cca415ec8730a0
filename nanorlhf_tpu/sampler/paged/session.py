"""The decode session: one composable continuous-batching loop.

Before this module the decode stack was three pairwise-exclusive forks
over the same machinery: the rollout scheduler's queued loop
(sampler/paged/scheduler.py), the serving engine's private fixed-shape
chunk loop (serving/engine.py), and the speculative carry — with the
radix prefix cache legal in exactly one of them at a time. `DecodeSession`
collapses the forks: every resident row carries ONE uniform state record —
page-table row, sampling params, output/logprob/mask slots, speculative
draft state, and (when admitted through the radix cache) its prefix-cache
plan — and admission, step, verify, and release are methods on the
session instead of per-mode code paths. The drivers that remain are thin
policy loops: the rollout scheduler owns queue order and output
collection, the serving engine owns threads/SLO/shed, and both submit
rows into the same jitted chunk functions defined here.

Compositions this buys (all pinned in tests/test_session.py; none
measured on a chip but what `serve-1.5b-chat` runs):

  * **spec decode under the radix prefix cache** — the n-gram drafter's
    lookup window is seeded from the radix tree's cached continuation of
    the matched prefix (`RadixCache.matched_continuation`), so
    prefix-heavy corpora draft usefully from the first generated token
    instead of waiting for the row's own buffer to self-repeat. Greedy
    output is bit-identical to each feature alone (greedy acceptance is
    draft-independent), with strictly fewer model dispatches on an
    overlapping corpus.
  * **chunked prefill** — a long cold prompt's admission is split into
    `prefill_chunk`-token KV-only forwards (`core/model.decode_verify`
    with `want_logits=False`) interleaved one-per-sync-chunk with decode
    steps, so resident rows' inter-token latency no longer absorbs the
    whole prefill wall. The final chunk runs through `suffix_logits` and
    samples the first token with the SAME admission PRNG fold as the
    unchunked path, so chunked-on/off GREEDY output is bit-identical
    (the suffix-equals-prefill equivalence, chained per chunk); sampled
    output is equal in distribution only, because a chunk-delayed row
    decodes at later global `fold_in(key, it)` iterations.
  * **serving as a session client** — the engine's per-request sampling
    params ([R] temperature/top_p/greedy/budget arrays) become traced
    arguments of the shared chunk body instead of a private carry layout;
    one compiled decode program serves rollout and gateway traffic.

  * **generation by blocks** (docs/BLOCKDIFF.md) — a model whose config
    says `block_generation` is served by a THIRD chunk program,
    `_block_loop`: a forward runs one block of `block_length` tokens a row
    (`core/model.block_forward`), rows standing at different denoise steps
    of different blocks, and yields 0 to `block_length` tokens a row. Its
    carry is the base carry (slot 2 holds, a token, the denoise step that
    unmasked it; `n_gen` counts a row's FINAL tokens, its longest in-order
    run of unmasked ones) and five slots more (`_BLOCK_SLOTS`).

Carry layout (identical to the pre-session scheduler, which is what keeps
every greedy stream bit-identical through the refactor):

  base  (10): it · out · lp_out · caches · key_mask · done · cur_tok ·
              n_gen · prompt_len · key
  spec  (15): base + n_drafted · n_accepted · n_emitted · n_rowsteps ·
              row_acc   (sampler/speculative.py)

Dispatch accounting: `launches` counts model-forward dispatches
(admission prefills, per-chunk prefill forwards, decode iterations,
verify steps — each one full weight stream); `dispatch_tokens` counts
prefill/suffix tokens only. Spec decode trades MORE tokens per verify
launch for FEWER launches, so the combined spec+radix A/B gates on
launches (`dispatch_events`) and on prefill tokens vs the spec-alone
baseline — docs/DECODE_ANALYSIS.md walks the arithmetic.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from nanorlhf_tpu.core.model import (
    _logits, attention_form, block_forward, decode_step, decode_verify,
    leaves_in_place, paged_write_forms, prefill,
)
from nanorlhf_tpu.ops.decode_attention import (
    paged_item_counts, paged_pages_per_item,
)
from nanorlhf_tpu.ops.masking import guard_temperature
from nanorlhf_tpu.sampler.paged.pages import (
    PageState, RingPages, alloc_row, blocks_per_row, full_table, release_row,
    ring_blocks,
)
from nanorlhf_tpu.sampler.blockdiff import (
    REMASKING, choose_unmask, sample_positions, transfer_count,
)
from nanorlhf_tpu.sampler.sampler import (
    _categorical_rows,
    _nucleus_candidates,
    _prefill_state,
    _sample_token,
    _token_logprob,
    sample_picks,
)
from nanorlhf_tpu.utils.donation import jit_donating
from nanorlhf_tpu.utils.profiling import PhaseTimer

# the host phases of a beat and of an admission, each a `PhaseTimer.phase`
# (docs/SERVING.md "engine loop account")
SESSION_PHASES = ("prefill_tick", "dispatch", "sync", "plan", "admit_forward")

# admitted rows re-key the PRNG far away from the per-iteration fold_in
# stream (iteration counters are bounded by max_tokens << this)
_ADMIT_BASE = 10_000_000

# the session drives _prefill_state from the host (sampler.py's callers
# run it inside their own jits), so it needs its own jit wrapper or the
# initial batch prefill executes op-by-op
_prefill_state_jit = partial(
    jax.jit,
    static_argnames=("config", "max_tokens", "eos_token_id", "pad_token_id",
                     "temperature", "top_p", "greedy", "lora_scale", "top_k",
                     "capture_logprobs", "approx_top_k", "page_size"),
)(_prefill_state)

_CHUNK_STATIC = (
    "config", "Tp", "max_tokens", "page_size", "sync_every", "eos_token_id",
    "pad_token_id", "temperature", "top_p", "greedy", "lora_scale", "top_k",
    "capture_logprobs", "approx_top_k",
)


@jax.named_scope("sample")
def _serving_sample(key, logits, temperature, top_p, greedy, *, top_k,
                    approx_top_k, draw=None):
    """Per-ROW sampling: `sampler._sample_token` with `temperature` /
    `top_p` / `greedy` as traced `[R]` arrays so one compiled decode
    step serves heterogeneous requests. Both branches are computed and
    selected with `jnp.where(greedy, ...)`; the nucleus keep rule
    broadcasts `top_p[:, None]` against the `[R, K]` candidate set.
    Unlike the rollout sampler there is no exact full-vocab escape for
    `top_p >= 1` — serving always samples in top-k candidate space
    (`top_p = 1` keeps every candidate), which is the usual serving
    trade and keeps the row-mixed program shape fixed. `draw`:
    `sampler._categorical_rows`' (the rows given are some of a step's, and
    draw as they would among all of them)."""
    scaled = (logits.astype(jnp.float32)
              / guard_temperature(temperature)[:, None])
    top_logits, top_idx, keep = _nucleus_candidates(
        scaled, top_p[:, None], top_k, approx_top_k)
    kept = jnp.where(keep, top_logits, -jnp.inf)
    choice = _categorical_rows(key, kept, draw)
    sampled = jnp.take_along_axis(
        top_idx, choice[..., None], axis=-1)[..., 0]
    return jnp.where(greedy, jnp.argmax(logits, axis=-1),
                     sampled).astype(jnp.int32)


def needed_sizes(items: int) -> tuple:
    """The sizes a served forward's sampler runs at: an eighth, a quarter, a
    half and all of its items, none under 8."""
    return tuple(items // d for d in (8, 4, 2) if items // d >= 8) + (items,)


def _over_needed(need, hidden, head, sample):
    """A served forward's head and sampler with the items that NEED a token
    first (docs/PAGED_CACHE.md "The rows a step scores"): `need` [N] bool,
    `hidden` [N, D] the model's final hidden state, `head(hidden [N, D])` its
    logits `[N, V]`, `sample(logits [s, V], idx [s])` the sampler over the
    items `idx`, returning a tuple of `[s]` arrays. The hidden state is
    gathered with the needed items to the front, the head runs over it once
    (a weight stream: rows do not shrink it, and no branch takes the weight
    as an operand, which the compiler relays where it is stored minor-axis
    first), and ONE branch of a `lax.switch` samples, over the leading rows
    of the logits: the smallest of `needed_sizes(N)` that holds the needed
    ones (a branch's other items are unneeded ones, sampled as every item
    was before). Each scatters its tokens back to `[N]`, zeros elsewhere, so
    a needed item reads what it would have read with every item sampled.
    Returns `(the tuple of [N] arrays, the size taken [] int32)`."""
    N = need.shape[0]
    sizes = needed_sizes(N)
    with jax.named_scope("sample"):
        order = jnp.argsort(~need, stable=True)
        which = jnp.sum(jnp.sum(need, dtype=jnp.int32)
                        > jnp.asarray(sizes[:-1], jnp.int32), dtype=jnp.int32)
    with jax.named_scope("head"):
        front = hidden[order]
    logits = head(front)

    def at(size):
        def branch(order, logits):
            idx = order[:size]
            sampled = sample(logits[:size], idx)
            with jax.named_scope("sample"):
                return tuple(jnp.zeros((N,), a.dtype).at[idx].set(a)
                             for a in sampled)
        return branch

    out = jax.lax.switch(which, [at(size) for size in sizes], order, logits)
    return out, jnp.asarray(sizes, jnp.int32)[which]


@partial(jax.jit, static_argnames=("top_k", "approx_top_k"))
@jax.named_scope("install")
def _first_token(logits, key, temperature, top_p, greedy, *, top_k,
                 approx_top_k):
    """Sample one admission's first token from its suffix logits [V]."""
    return _serving_sample(key, logits[None, :], temperature[None],
                           top_p[None], greedy[None], top_k=top_k,
                           approx_top_k=approx_top_k)[0]


@jax.named_scope("decode")
def _session_decode_body(params, config, s, table, row_params, *, Tp,
                         max_tokens, page_size, eos_token_id, pad_token_id,
                         temperature, top_p, greedy, lora_scale, top_k,
                         capture_logprobs, approx_top_k, count_experts=False):
    """One decode step over the session carry — `sampler._decode_body`
    generalized to PER-ROW generation counts (resident rows sit at
    different depths) and table-routed cache writes. `row_params` is None
    for the rollout mode (static sampling params, row budget =
    `max_tokens`) or the serving mode's traced `[R]`
    (temperature, top_p, greedy, budget) tuple — a trace-time branch. The
    rollout mode compiles to exactly the program its pre-session driver ran;
    the serving mode samples the LIVE rows alone (`_over_needed`).
    With `count_experts` the result is `(carry, held experts the live rows
    reached)`, in the serving mode always `(carry, [that or 0, the rows the
    sampler ran over])` (`_chunk_loop`)."""
    (it, out, lp_out, caches, key_mask, done, cur_tok, n_gen, prompt_len,
     key) = s
    R = cur_tok.shape[0]
    rows = jnp.arange(R)
    slot = Tp + n_gen - 1                      # [R] cache slot of cur_tok
    key_mask = key_mask.at[rows, slot].set(True)
    position = prompt_len + n_gen - 1
    live = ~done
    logits, caches, *reached = decode_step(
        params, config, cur_tok, position, slot, key_mask, caches,
        lora_scale=lora_scale, page_table=table, page_size=page_size,
        live=live, **({"count_experts": True} if count_experts else {}),
        want_logits=row_params is None,
    )
    if row_params is None:
        tok = _sample_token(jax.random.fold_in(key, it), logits, temperature,
                            top_p, greedy, top_k, approx_top_k)
        limit = max_tokens
    else:
        r_temp, r_topp, r_greedy, r_budget = row_params
        hidden = logits     # (`want_logits=False`: the head runs below)
        step_key = jax.random.fold_in(key, it)
        (tok,), taken = _over_needed(
            live, hidden, partial(_logits, config, params),
            lambda logits, idx: (_serving_sample(
                step_key, logits, r_temp[idx], r_topp[idx], r_greedy[idx],
                top_k=top_k, approx_top_k=approx_top_k, draw=(idx, R)),))
        limit = r_budget
    tok = jnp.where(done, pad_token_id, tok)
    wpos = jnp.where(live, n_gen, max_tokens)  # done rows drop their write
    out = out.at[rows, wpos].set(tok, mode="drop")
    if capture_logprobs:
        lp = _token_logprob(logits, tok, temperature)
        lp_out = lp_out.at[rows, wpos].set(lp, mode="drop")
    cur_tok = jnp.where(live, tok, cur_tok)
    n_gen = n_gen + live.astype(jnp.int32)
    done = done | (tok == eos_token_id) | (n_gen >= limit)
    carry = (it + 1, out, lp_out, caches, key_mask, done, cur_tok, n_gen,
             prompt_len, key)
    if row_params is not None:
        return carry, jnp.stack([reached[0] if count_experts
                                 else jnp.int32(0), taken])
    return (carry, reached[0]) if count_experts else carry


def _chunk_loop(params, config, state, table, row_params, statics):
    """Up to `sync_every` decode iterations; exits early once every
    resident row is done (the iteration counter then stops, so it counts
    true decode dispatches).

    The program of a model with expert layers (`config.live_rows_dispatch`:
    a chip's share of them or all) also returns, beside the carry, the held
    experts its LIVE rows reached (a step dispatches no other row: `moe_mlp`), summed
    over the chunk's steps and the layers (`DecodeSession.held_experts_hit`):
    the routed kernels the steps had to read. The serving mode's returns
    `[that or 0, the rows its sampler ran over]` summed over the steps
    (`DecodeSession.sample_rows`)."""
    statics = dict(statics)
    sync_every = statics.pop("sync_every")
    experts = config.live_rows_dispatch
    if experts or row_params is not None:
        def counted(cs):
            c, s, seen = cs
            s, more = _session_decode_body(
                params, config, s, table, row_params, count_experts=experts,
                **statics)
            return c + 1, s, seen + more

        _, state, seen = jax.lax.while_loop(
            lambda cs: (cs[0] < sync_every) & ~jnp.all(cs[1][5]), counted,
            (jnp.int32(0), state,
             jnp.int32(0) if row_params is None
             else jnp.zeros((2,), jnp.int32)))
        return state, seen

    def cond(cs):
        c, s = cs
        return (c < sync_every) & ~jnp.all(s[5])

    def body(cs):
        c, s = cs
        return c + 1, _session_decode_body(params, config, s, table,
                                           row_params, **statics)

    _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state


# Every program below that takes the page pool and returns it DONATES it
# (utils/donation.py: on an accelerator), so the pool is written where it
# lies and no call copies it. The chunk programs carry it in slot 3 of
# `state` and donate the carry whole; the admission programs donate `caches`.
# A donated argument is consumed: the caller puts the result in its place in
# the same statement and holds no other reference (`DecodeSession` docstring).

@partial(jit_donating, donate=2, static_argnames=_CHUNK_STATIC)
def _decode_chunk(params, config, state, table, **statics):
    """Rollout-mode chunk: static sampling params (the pre-session
    scheduler's `_decode_chunk`, bit-identical program)."""
    return _chunk_loop(params, config, state, table, None, statics)


@partial(jit_donating, donate=2, static_argnames=_CHUNK_STATIC)
def _serving_chunk(params, config, state, table, r_temp, r_topp, r_greedy,
                   r_budget, **statics):
    """Serving-mode chunk: per-request sampling params and token budgets
    ride as traced [R] arrays (the pre-session engine's `_engine_chunk`,
    bit-identical program — the params moved from carry slots to
    arguments, the values are the same)."""
    return _chunk_loop(params, config, state, table,
                       (r_temp, r_topp, r_greedy, r_budget), statics)


@partial(jax.jit, static_argnames=("width",))
@jax.named_scope("install")
def _beat_report(it, out, done, n_gen, seen, *, width):
    """What the host reads of a serving beat, as two small arrays of their
    own: `[it, *seen]` (`_chunk_loop`'s two counts) and, a row, `done ·
    n_gen · the row's last `width` tokens` (right-aligned at `n_gen`: a chunk
    writes at most `width` a row, so the new ones are among them). Enqueued behind the chunk over its
    carry and donating nothing, so the NEXT chunk may consume that carry
    while the host still waits for these (`DecodeSession.dispatch`)."""
    cols = n_gen[:, None] - width + jnp.arange(width, dtype=jnp.int32)[None]
    toks = jnp.take_along_axis(
        out, jnp.clip(cols, 0, out.shape[1] - 1), axis=1)
    rows = jnp.concatenate(
        [done[:, None].astype(jnp.int32), n_gen[:, None], toks], axis=1)
    return jnp.concatenate([it[None], seen]), rows


# a block session's carry past the base ten (docs/BLOCKDIFF.md "row state"):
#   blk [R, B] int32     the row's current block: its tokens unmasked so far
#   masked [R, B] bool   which of its positions are still masked
#   step [R] int32       denoise forwards the row has run on this block
#   base [R] int32       the block's first position (a multiple of B; its
#                        cache slot is `Tp - prompt_len + base`)
#   counts [5] int32     never reset: live row-forwards, commit forwards,
#                        tokens unmasked, blocks fully unmasked, pages the
#                        live rows' block reads spanned
_BLOCK_SLOTS = 5
_BLOCK_STATIC = ("config", "Tp", "page_size", "sync_every", "eos_token_id",
                 "lora_scale", "top_k", "approx_top_k")


@jax.named_scope("decode")
def _block_body(params, config, s, table, row_params, *, Tp, page_size,
                eos_token_id, lora_scale, top_k, approx_top_k):
    """One forward of generation by blocks over the session carry
    (docs/BLOCKDIFF.md): every live row runs its current block through
    `block_forward`. A row whose block still has masked positions takes a
    DENOISE forward: a token is sampled at its masked positions, its
    confidence taken, and the row's strategy unmasks some of them
    (`sampler/blockdiff.py`); the block's K/V just written stay PROVISIONAL
    (`key_mask` is left alone: the next forward writes the same slots
    again). A row whose block has no mask left takes its COMMIT forward:
    the K/V written are final, the block's slots become valid, the row ends
    if the block holds EOS inside its budget or reaches the budget, else
    its next block opens all masked. `row_params`: the traced `[R]`
    (temperature, top_p, greedy, budget, denoising steps, remasking)."""
    (it, out, rec, caches, key_mask, done, cur_tok, n_gen, prompt_len, key,
     blk, masked, step, base, counts) = s
    r_temp, r_topp, r_greedy, r_budget, r_steps, r_remask = row_params
    R, B = blk.shape
    W = out.shape[1]
    rows = jnp.arange(R)
    live = ~done
    fill = Tp - prompt_len + base                       # [R] the block's slot
    within = jnp.arange(B, dtype=jnp.int32)[None, :]
    tokens = jnp.where(masked, config.mask_token_id, blk)
    hidden, caches, reached = block_forward(
        params, config, tokens, base[:, None] + within, fill, key_mask,
        caches, lora_scale=lora_scale, page_table=table, page_size=page_size,
        live=live, count_experts=True, want_logits=False)
    open_ = masked.any(axis=1)
    denoise, commit = live & open_, live & ~open_
    # a token and a confidence where one can be used: at the masked
    # positions of the rows on a denoise forward (`chosen` is among them)
    step_key = jax.random.fold_in(key, it)
    (tok, conf), taken = _over_needed(
        (live[:, None] & masked).reshape(R * B),
        hidden.reshape(R * B, hidden.shape[-1]),
        partial(_logits, config, params),
        lambda logits, idx: sample_positions(
            step_key, logits, r_temp[idx // B], r_topp[idx // B],
            r_greedy[idx // B], top_k=top_k, approx_top_k=approx_top_k,
            draw=(idx, R * B)))
    tok, conf = tok.reshape(R, B), conf.reshape(R, B)
    # (inside `sample`: the benchmark's scope reduction keeps the families
    # `attn.`, `moe.`, `mla.` and reads this as its parent's)
    with jax.named_scope("sample"), jax.named_scope("sample.unmask"):
        chosen = denoise[:, None] & choose_unmask(
            conf, masked, transfer_count(step, r_steps, B), r_remask)
        blk = jnp.where(chosen, tok, blk)
        masked = masked & ~chosen
        gen = (base - prompt_len)[:, None] + within     # index among the new
        at = jnp.where(chosen, gen, W)                  # (a tail's is < 0:
        out = out.at[rows[:, None], at].set(blk, mode="drop")   # never chosen)
        rec = rec.at[rows[:, None], at].set(
            jnp.broadcast_to(step[:, None], (R, B)), mode="drop")
        step = step + denoise.astype(jnp.int32)
        closed = denoise & ~masked.any(axis=1)
        # the commit: the block's slots are valid from here on
        slot = jnp.arange(key_mask.shape[1], dtype=jnp.int32)[None, :]
        key_mask = key_mask | (commit[:, None] & (slot >= fill[:, None])
                               & (slot < fill[:, None] + B))
        ends = ((gen >= 0) & (gen < r_budget[:, None])
                & (blk == eos_token_id)).any(axis=1)
        base = base + B * commit.astype(jnp.int32)
        done = done | (commit & (ends | (base - prompt_len >= r_budget)))
        blk = jnp.where(commit[:, None], config.mask_token_id, blk)
        masked = masked | commit[:, None]
        step = jnp.where(commit, 0, step)
        # a row's final tokens: everything before its block, and the
        # block's leading run of unmasked positions
        lead = jnp.sum(jnp.cumprod(~masked, axis=1), axis=1, dtype=jnp.int32)
        n_gen = jnp.where(live, jnp.maximum(base - prompt_len + lead, 0),
                          n_gen)
        first = jnp.where(key_mask.any(axis=1), jnp.argmax(key_mask, axis=1),
                          fill)
        pages = jnp.where(live, (fill + B - 1) // page_size
                          - first // page_size + 1, 0)
        counts = counts + jnp.stack([
            jnp.sum(live, dtype=jnp.int32), jnp.sum(commit, dtype=jnp.int32),
            jnp.sum(chosen, dtype=jnp.int32), jnp.sum(closed, dtype=jnp.int32),
            jnp.sum(pages, dtype=jnp.int32)])
    carry = (it + 1, out, rec, caches, key_mask, done, cur_tok, n_gen,
             prompt_len, key, blk, masked, step, base, counts)
    return carry, jnp.stack([reached, taken])


def _block_loop(params, config, state, table, row_params, statics):
    """Up to `sync_every` forwards of generation by blocks; exits early once
    every resident row is done. Returns `(carry, [held experts the live rows
    reached, positions the sampler ran over])`, as `_chunk_loop` does in the
    serving mode."""
    statics = dict(statics)
    sync_every = statics.pop("sync_every")

    def body(cs):
        c, s, seen = cs
        s, more = _block_body(params, config, s, table, row_params, **statics)
        return c + 1, s, seen + more

    _, state, seen = jax.lax.while_loop(
        lambda cs: (cs[0] < sync_every) & ~jnp.all(cs[1][5]), body,
        (jnp.int32(0), state, jnp.zeros((2,), jnp.int32)))
    return state, seen


@partial(jit_donating, donate=2, static_argnames=_BLOCK_STATIC)
def _block_chunk(params, config, state, table, r_temp, r_topp, r_greedy,
                 r_budget, r_steps, r_remask, **statics):
    """Serving-mode chunk of a model that generates by blocks."""
    return _block_loop(params, config, state, table,
                       (r_temp, r_topp, r_greedy, r_budget, r_steps,
                        r_remask), statics)


@partial(jax.jit, static_argnames=("width",))
@jax.named_scope("install")
def _block_report(it, out, rec, done, n_gen, seen, counts, *, width):
    """`_beat_report` for a block session: `[it, *seen, *counts]` and, a row,
    `done · n_gen · the last `width` FINAL tokens · the denoise step that
    unmasked each` (right-aligned at `n_gen`, the row's count of final
    tokens: a chunk of `sync_every` forwards finalises at most `width`)."""
    cols = n_gen[:, None] - width + jnp.arange(width, dtype=jnp.int32)[None]
    cols = jnp.clip(cols, 0, out.shape[1] - 1)
    rows = jnp.concatenate(
        [done[:, None].astype(jnp.int32), n_gen[:, None],
         jnp.take_along_axis(out, cols, axis=1),
         jnp.take_along_axis(rec, cols, axis=1)], axis=1)
    return jnp.concatenate([it[None], seen, counts]), rows


@partial(jax.jit, static_argnames=("Tp", "pad_token_id", "mask_token_id"))
@jax.named_scope("install")
def _install_block_row(state, r, plen, whole, tail, *, Tp, pad_token_id,
                       mask_token_id):
    """Row `r` of a block session's carry for a freshly admitted prompt of
    `plen` tokens whose `whole` leading tokens (its whole blocks) are
    prefilled: those slots valid, nothing generated, and the first block
    open with the prompt's tail `tail` [B] (its first `plen - whole`
    entries) already unmasked. `state` comes `_sans_pool`."""
    s = list(state)
    W, T_mask, B = s[1].shape[1], s[4].shape[1], s[10].shape[1]
    slot = jnp.arange(T_mask, dtype=jnp.int32)
    within = jnp.arange(B, dtype=jnp.int32)
    s[1] = s[1].at[r].set(jnp.full((W,), pad_token_id, jnp.int32))
    s[2] = s[2].at[r].set(jnp.zeros((W,), s[2].dtype))
    s[4] = s[4].at[r].set((slot >= Tp - plen) & (slot < Tp - plen + whole))
    s[5] = s[5].at[r].set(False)
    s[7] = s[7].at[r].set(jnp.int32(0))
    s[8] = s[8].at[r].set(plen)
    is_tail = within < plen - whole
    s[10] = s[10].at[r].set(jnp.where(is_tail, tail, mask_token_id))
    s[11] = s[11].at[r].set(~is_tail)
    s[12] = s[12].at[r].set(jnp.int32(0))
    s[13] = s[13].at[r].set(whole)
    return tuple(s)


_SPEC_CHUNK_STATIC = _CHUNK_STATIC + ("spec_k", "spec_ngram")


def _spec_loop(params, config, state, table, prompt_rep, seed_rep, seed_len,
               statics):
    """Speculative twin of `_chunk_loop`: draft + verify per iteration
    over the 15-slot speculative carry, with the live block table routed
    into the verify forward. `prompt_rep` is the RESIDENT prompts [R, Tp]
    (it changes at admission, hence a traced argument); `seed_rep` /
    `seed_len`, when present, prepend the radix-matched cached
    continuation to each row's n-gram lookup window
    (`speculative._draft_fn`)."""
    from nanorlhf_tpu.sampler.speculative import _draft_fn, _verify_fn

    statics = dict(statics)
    sync_every = statics.pop("sync_every")
    spec_ngram = statics.pop("spec_ngram")
    ver_kw = dict(statics)
    ver_kw.pop("pad_token_id")
    spec_k = statics["spec_k"]
    Tp, pad = statics["Tp"], statics["pad_token_id"]

    def cond(cs):
        c, s = cs
        return (c < sync_every) & ~jnp.all(s[5])

    def body(cs):
        c, s = cs
        drafts = _draft_fn(prompt_rep, s, Tp=Tp, spec_k=spec_k,
                           spec_ngram=spec_ngram, pad_token_id=pad,
                           seed_rep=seed_rep, seed_len=seed_len)
        return c + 1, _verify_fn(params, config, s, drafts, page_table=table,
                                 pad_token_id=pad, **ver_kw)

    _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state


@partial(jit_donating, donate=2, static_argnames=_SPEC_CHUNK_STATIC)
def _spec_chunk(params, config, state, table, prompt_rep, **statics):
    """Spec chunk, own-buffer drafting only (spec without the radix
    cache — the pre-session scheduler's `_spec_chunk`)."""
    return _spec_loop(params, config, state, table, prompt_rep, None, None,
                      statics)


@partial(jit_donating, donate=2, static_argnames=_SPEC_CHUNK_STATIC)
def _spec_chunk_seeded(params, config, state, table, prompt_rep, seed_rep,
                       seed_len, **statics):
    """Spec chunk with the radix-seeded lookup window (spec × prefix
    cache). Greedy acceptance is draft-independent, so seeding changes
    dispatch counts, never greedy output."""
    return _spec_loop(params, config, state, table, prompt_rep, seed_rep,
                      seed_len, statics)


@partial(jit_donating, donate=4,
         static_argnames=("config", "page_size", "T_max", "temperature",
                          "top_p", "greedy", "top_k", "approx_top_k",
                          "lora_scale"))
@jax.named_scope("prefill")
def _admit_one(params, config, pids, pmask, caches, row_table, key, *,
               page_size, T_max, temperature, top_p, greedy, top_k,
               approx_top_k, lora_scale):
    """Single-row admission prefill: write the prompt KV through the row's
    freshly allocated block table into the SHARED pool, sample the first
    token. pids/pmask: [1, Tp]; row_table: [nb]. Returns
    (caches, tok0, lp0, prompt_len) with row-0 scalars."""
    logits, caches = prefill(
        params, config, pids, pmask.astype(bool), caches,
        lora_scale=lora_scale,
        page_table=jax.tree.map(lambda t: t[None, :], row_table),
        page_size=page_size, logical_len=T_max,
    )
    tok0 = _sample_token(key, logits, temperature, top_p, greedy, top_k,
                         approx_top_k)
    lp0 = _token_logprob(logits, tok0, temperature)
    plen = jnp.sum(pmask.astype(jnp.int32), axis=1)
    return caches, tok0[0], lp0[0], plen[0]


@partial(jax.jit, static_argnames=("Tp", "max_tokens", "eos_token_id",
                                   "pad_token_id", "spec", "per_row"))
@jax.named_scope("install")
def _install_row(state, r, tok0, lp0, pmask_row, plen, budget=None,
                 *, Tp, max_tokens, eos_token_id, pad_token_id, spec,
                 per_row=False):
    """Re-initialize resident row `r` of the carry for a freshly admitted
    prompt (out/lp rows cleared, key_mask reset to the prompt mask, counters
    to the post-prefill values). Works for both carry layouts — the first
    ten slots of the spec carry line up, and `spec` additionally resets the
    per-row accepted-draft counter. `per_row` (serving) folds the traced
    token `budget` into the initial done flag (a budget-1 request is done
    at its first token). `state` comes `_sans_pool` and goes back so: the
    admission forward has the pool, this program never sees it."""
    s = list(state)
    T_mask = s[4].shape[1]
    s[1] = s[1].at[r].set(
        jnp.full((max_tokens,), pad_token_id, jnp.int32).at[0].set(tok0))
    s[2] = s[2].at[r].set(jnp.zeros((max_tokens,), jnp.float32).at[0].set(lp0))
    s[4] = s[4].at[r].set(
        jnp.zeros((T_mask,), bool).at[:Tp].set(pmask_row.astype(bool)))
    if per_row:
        s[5] = s[5].at[r].set((tok0 == eos_token_id) | (budget <= 1))
    else:
        s[5] = s[5].at[r].set(tok0 == eos_token_id)
    s[6] = s[6].at[r].set(tok0)
    s[7] = s[7].at[r].set(jnp.int32(1))
    s[8] = s[8].at[r].set(plen)
    if spec:
        s[14] = s[14].at[r].set(jnp.int32(0))
    return tuple(s)


def _sans_pool(state):
    """The carry with nothing in the pool's slot (None is an empty pytree)."""
    return state[:3] + (None,) + state[4:]


def _with_pool(state, caches):
    return state[:3] + (caches,) + state[4:]


_release_jit = jax.jit(jax.named_scope("install")(release_row))
_alloc_jit = jax.jit(jax.named_scope("install")(alloc_row))


@jax.jit
@jax.named_scope("install")
def _end_row(done, r):
    """Row `r`'s done flag forced (`DecodeSession.cancel_row`)."""
    return done.at[r].set(True)


@partial(jax.jit, static_argnames=("temperature", "top_p", "greedy", "top_k",
                                   "approx_top_k"))
@jax.named_scope("install")
def _admit_sample(logits, key, *, temperature, top_p, greedy, top_k,
                  approx_top_k):
    """First token + logprob from a single row's admission logits [V] —
    the sampling half of `_admit_one`, split out so the radix path can
    feed it suffix-prefill logits instead of full-prefill logits."""
    tok0 = _sample_token(key, logits[None, :], temperature, top_p, greedy,
                         top_k, approx_top_k)
    return tok0[0], _token_logprob(logits[None, :], tok0, temperature)[0]


@partial(jit_donating, donate=6,
         static_argnames=("config", "page_size", "lora_scale"))
@jax.named_scope("prefill")
def _prefill_chunk_fwd(params, config, chunk_ids, positions, fill, key_mask,
                       caches, row_table, call_keys=None, *, page_size,
                       lora_scale):
    """One KV-only prefill chunk: a `decode_verify` forward over a
    fixed-width slice of a long cold prompt, writing its KV through the
    row's block table and skipping the lm_head matmul entirely
    (`want_logits=False`) — only the FINAL chunk needs logits, and it
    runs through `suffix_logits` instead."""
    _, caches = decode_verify(
        params, config, chunk_ids, positions, fill, key_mask, caches,
        lora_scale=lora_scale,
        page_table=jax.tree.map(lambda t: t[None, :], row_table),
        page_size=page_size, want_logits=False, call_keys=call_keys,
    )
    return caches


@dataclass
class _PendingPrefill:
    """A chunked admission in flight: the row's pages are claimed and its
    carry row is parked done=True; `next_slot` advances one chunk per
    session step until the final chunk installs the row."""
    row: int
    index: int                    # the admission's `admit_index`
    toks: np.ndarray              # [Tp] left-padded
    mask: np.ndarray              # [Tp] bool
    pad_count: int
    next_slot: int                # next absolute cache slot to prefill
    admit_key: jax.Array
    t_start: float
    kelems: Optional[tuple] = None        # radix key (radix mode)
    plan_hit: int = 0
    seed: Optional[np.ndarray] = None     # drafter seed (spec × radix)
    budget: Optional[int] = None          # per-row mode request params
    temperature: float = 1.0
    top_p: float = 1.0
    greedy: bool = False
    row_table: Optional[np.ndarray] = None  # non-radix: device row snapshot
    meta: dict = field(default_factory=dict)
    end: int = 0                          # one past the last slot to prefill
                                          # (Tp; a block session: the
                                          # prompt's whole blocks' end)
    denoising_steps: int = 0              # a block session's request params
    remasking: int = 0


@dataclass
class FirstToken:
    """An admission's first token once the host has it (`DecodeSession.read`):
    the row, the admission's `admit_index`, the token, and the seconds the
    host has stood waiting for the device since the last report, this
    token's read included (the running beat's `BeatReport.wait_s` so far)."""
    row: int
    index: int
    token: int
    wait_s: float = 0.0


@dataclass
class BeatReport:
    """One decode chunk as the host read it (`DecodeSession.read`). `done`
    is the rows' flags after the chunk and `its` the iterations it ran. A
    serving-mode session also gives `n_gen` (tokens a row has so far),
    `tokens` (each row's last `width`, right-aligned at `n_gen`) and
    `current`: the rows that still hold the request the chunk ran for. A
    block session's `n_gen` counts a row's FINAL tokens (its longest in-order
    run of unmasked ones: a token once unmasked never changes), so a beat
    carries 0 to `sync_every x block_length` new tokens a row. A
    report read one beat late (`dispatch`) says nothing of a row that was
    released, cancelled or admitted into since its chunk was dispatched.

    What the beat was, for whoever books it to a request: `period_s`, the
    seconds from the report before this one (or this chunk's dispatch, where
    that came later) to this report; `wait_s`, those of them the host stood
    waiting for the device inside `read()`, for this report and for every
    first token read since the report before it (an admission forward or a
    last prefill piece had to run first); `foreign`, the forwards of more
    than one token (admission forwards, prefill pieces) the session enqueued
    between the chunk before this one and this one, which the device ran in
    between and which delayed every resident row; `t`, the report's
    instant (`time.perf_counter()`, the clock of all of these)."""
    its: int
    done: np.ndarray
    n_gen: Optional[np.ndarray] = None
    tokens: Optional[np.ndarray] = None
    current: Optional[np.ndarray] = None
    steps: Optional[np.ndarray] = None      # a block session: beside each of
                                            # `tokens`, the denoise step of
                                            # its block that unmasked it
    period_s: float = 0.0
    wait_s: float = 0.0
    foreign: int = 0
    t: float = 0.0

    def new_tokens(self, r: int, since: int) -> np.ndarray:
        """Row `r`'s tokens from its `since`-th on."""
        new = int(self.n_gen[r]) - int(since)
        return self.tokens[r, self.tokens.shape[1] - new:]

    def new_steps(self, r: int, since: int) -> np.ndarray:
        """`new_tokens`' tokens' denoise steps (a block session)."""
        new = int(self.n_gen[r]) - int(since)
        return self.steps[r, self.steps.shape[1] - new:]


@dataclass
class _First:
    """An installed admission whose first token is still on the device."""
    pend: _PendingPrefill
    tok: jax.Array


@dataclass
class _Flight:
    """A dispatched chunk the host has not read: when it was dispatched, who
    held each row then, the forwards of more than one token enqueued since
    the chunk before it (`launches`' gain), the counts its program handed
    back beside the carry (`hit`: `_chunk_loop`'s) and (serving mode) its
    `_beat_report`, all still on the device."""
    t0: float
    occupants: np.ndarray
    foreign: int = 0
    hit: Optional[jax.Array] = None
    meta: Optional[jax.Array] = None
    rows: Optional[jax.Array] = None


class DecodeSession:
    """One resident decode batch with uniform per-row state.

    Owns the carry, the page table (radix-refcounted or device
    free-stack), the speculative draft seeds, the chunked-prefill
    backlog, and the latency-hub recording; exposes
    `admit` / `bootstrap` / `step` (or its halves `dispatch` / `read`) /
    `release` / `cancel_row` to the two drivers (rollout scheduler, serving
    engine). Modes:

      * `per_row=False` (rollout): static sampling params, every row
        shares `max_tokens`; spec decode composes (`spec_k > 0`), with
        the drafter seeded from the radix tree when `prefix_cache` is
        also attached.
      * `per_row=True` (serving): traced per-row temperature / top_p /
        greedy / budget; `capture_logprobs` is illegal (the logprob
        write needs a static temperature) — `sampler.compose_check`
        documents the matrix.

    Who owns the pool: `self.state` holds the ONLY reference to the page
    pool (slot 3), and every program that takes it donates it, so a call
    consumes the pool it is given and the session puts the returned one in
    its place at once. The chunk programs consume the whole carry, so
    nothing outside `self.state` may alias one of its arrays (the carry has
    a PRNG key of its own: `_key` / `_admit_key` are folded for as long as
    the session lives), and only the thread that drives the session may
    read `self.state`. Other threads read `status()` / `iterations()`,
    which answer from the host's record of the last sync. A donating call
    that raises leaves a consumed pool in the carry: the next use raises
    "Array has been deleted", the session is finished and nothing decodes
    on freed pages (docs/SERVING.md "Who owns the page pool").

    A beat has two halves. `dispatch()` enqueues at most one pending
    prefill piece and one decode chunk and returns; `read()` waits for the
    oldest thing the device still owes the host, in the device's own order:
    an admission's first token (`FirstToken`; a serving-mode `_install`
    leaves it on the device) or a chunk's `BeatReport`. `step()` is the
    serial beat, a dispatch and then every read, and is what the rollout
    scheduler and a speculative engine run: they need `done` to refill or
    verify before their next chunk. A serving-mode session without
    speculation `looks_ahead`: its driver dispatches chunk k+1 BEFORE it
    reads chunk k, so the device goes from one chunk into the next while
    the host waits, streams, releases and admits (docs/SERVING.md "the
    beat"). What makes that sound:

      * what the host reads of a chunk is `_beat_report`'s two small arrays,
        not the carry, which chunk k+1 consumes; the tables and the per-row
        parameters a chunk is given are copies taken at its dispatch
        (`jnp.array`: `jnp.asarray` may alias the host's arrays, which the
        host goes on changing under the flight);
      * everything enqueued after the chunk in flight runs after it. A row
        the host releases on report k is DONE in chunk k+1: a done row only
        writes the K/V of its last token again, the same values into its own
        slot, and drops its `out` write (`_session_decode_body`), so the
        pages may be handed on under the flight, and the admission forward
        that writes them next queues behind the chunk. A row cancelled while
        live decodes to the flight's end into pages nobody reads before
        their next owner's forward, which queues behind it as well;
      * a report read late is held to the rows whose occupant has not
        changed since its dispatch (`BeatReport.current`), and a first token
        is always read before the report of the first chunk its row ran in.

    The session NEVER resets an attached `prefix_cache` implicitly at
    step time — it resets it exactly once at construction (the rollout
    driver builds a session per generate call, giving the per-call reset
    the staleness note in serving/radix.py requires; the engine builds
    one session for its lifetime, keeping its tree warm)."""

    def __init__(self, params, config, *, rows, prompt_len, max_tokens,
                 page_size, eos_token_id, pad_token_id, key,
                 temperature=1.0, top_p=0.95, greedy=False, top_k=64,
                 approx_top_k=True, capture_logprobs=False, lora_scale=1.0,
                 per_row=False, spec_k=0, spec_ngram=3, prefix_cache=None,
                 prefill_chunk=0, sync_every=8, latency=None,
                 admit_key=None):
        # a model that generates by blocks (docs/BLOCKDIFF.md): its block
        # length, 0 for every autoregressive model
        self.block = int(config.block_length)
        # which kind of model each mechanism takes: core/config.MECHANISMS
        if per_row:
            config.require("the serving session")
        else:
            config.require("the rollout scheduler (per_row=False)",
                           "a page pool of one kind")
        if spec_k:
            config.require(f"speculative decode (spec_k={spec_k})",
                           "speculative decode")
        if config.spmd_mesh is not None:
            config.require("a mesh under a decode session")
        if prefix_cache is None or not getattr(prefix_cache, "enabled", False):
            config.require("a decode session without a RadixCache",
                           "a page pool of one kind")
        if self.block and prefill_chunk % self.block:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} on a model that generates by "
                f"blocks of {self.block} ({config.model_type}): a prefill "
                "piece ends on a block's end, so the chunk is a multiple of "
                "the block length")
        if per_row and capture_logprobs:
            raise ValueError(
                "capture_logprobs is incompatible with per-row sampling "
                "params: the logprob write shares the chunk body's static "
                "temperature — see sampler.compose_check")
        if per_row and spec_k > 0 and not greedy:
            raise ValueError(
                "per-row spec decode requires the session's static "
                "greedy=True: the verify/accept rule compiles against "
                "static sampling params, so a spec serving engine admits "
                "greedy requests only — see sampler.compose_check")
        self.params = params
        self.config = config
        self.rows = int(rows)
        self.Tp = int(prompt_len)
        self.max_tokens = int(max_tokens)
        self.page_size = int(page_size)
        self.eos_token_id = int(eos_token_id)
        self.pad_token_id = int(pad_token_id)
        self.per_row = bool(per_row)
        self.spec = int(spec_k) > 0
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.prefill_chunk = int(prefill_chunk)
        self.lora_scale = lora_scale
        self.capture_logprobs = bool(capture_logprobs)
        self._key = key
        self._admit_key = key if admit_key is None else admit_key
        self._hub = latency if (latency is not None
                                and getattr(latency, "enabled", False)) \
            else None
        # the beat's host account (`session.<phase>` in a profiler trace):
        # names up front, the engine's metrics() copies the totals from
        # another thread
        self.timer = PhaseTimer(span_prefix="session.", names=SESSION_PHASES)

        # (a block session's last block may reach past the budget: its
        # slots are written and read like any block's, so they are real)
        self.T_max = self.Tp + self.max_tokens + self.block
        self.nb = blocks_per_row(self.T_max, self.page_size)
        # (a looped model, docs/OURO.md: every pass of every layer keeps a
        # slot of its own, `cache_layers`, under this one table)
        # a model with window layers (docs/SWA.md): a second pool and table
        # for them, a ring of pages a row
        self.window_layers = config.window_layers
        patterned = config.attention_pattern is not None
        # a model with conv layers or state-space layers (docs/STATE.md,
        # docs/SSM.md): a state a row beside the pages, which only this
        # serving session keeps right
        self.state_layers = config.state_layers

        self._radix = prefix_cache if (
            prefix_cache is not None
            and getattr(prefix_cache, "enabled", False)) else None
        if self._radix is not None:
            self.num_pages = (self.rows * self.nb
                              + self._radix.extra_pages(self.rows, self.nb))
            self._radix.reset(num_pages=self.num_pages,
                              page_size=self.page_size)
            self.table_np = np.full((self.rows, self.nb), self.num_pages,
                                    np.int32)
            self._pstate = None
        else:
            self.num_pages = self.rows * self.nb
            self.table_np = None
            # the free-stack allocator starts EMPTY: bootstrap() claims
            # the whole pool through the identity table, and churn begins
            # at the first release
            self._pstate = PageState(
                free=jnp.arange(self.num_pages, dtype=jnp.int32),
                top=jnp.asarray(0, jnp.int32),
                table=full_table(self.rows, self.nb))

        from nanorlhf_tpu.core.model import init_paged_kv_cache
        self._ring = None
        self.num_pages_window = 0
        if patterned:
            # the longest forward a row's admission makes: a prefill chunk,
            # or without chunking the whole prompt, in the power-of-two
            # bucket `_admit_now` pads it to
            from nanorlhf_tpu.serving.radix import bucket_len
            self.nbw = min(self.nb, ring_blocks(
                config.sliding_window, self.page_size,
                bucket_len(self.prefill_chunk or self.Tp, self.T_max))
            ) if self.window_layers else 1
            self.num_pages_window = self.rows * self.nbw
            self._ring = RingPages(self.num_pages_window, self.rows, self.nb,
                                   self.nbw)
        R = self.rows
        caches0 = init_paged_kv_cache(
            config, (self.num_pages, self.num_pages_window) if patterned
            else self.num_pages, self.page_size,
            params["embed_tokens"].dtype,
            **({"state_rows": R} if self.state_layers else {}))
        # what the state holds a row, over every layer that keeps one and
        # every leaf of it (`serving/state_bytes_per_row`), and the rows a
        # decode chunk's state "table" names: all of them, in order
        # (`_conv_operator`)
        self.state_bytes_per_row = 0
        self._state_rows = None
        if self.state_layers:
            self.state_bytes_per_row = sum(
                leaf.nbytes for leaf in caches0[2]) // R
            self._state_rows = jnp.arange(R, dtype=jnp.int32)[:, None]
        # admissions that started a row from a zero state, and forwards of a
        # chunked admission that took the state its last piece left
        # (`serving/state_resets`, `serving/state_piece_carries`)
        self.state_resets = 0
        self.state_piece_carries = 0
        # a model with sparse-attention layers (docs/SALA.md): the decode
        # steps of live rows past `sparse_dense_len` (`serving/sparse_rows`),
        # the slots those rows held and the slots the selection let a layer
        # read of them (`sparse_topk` blocks at most)
        self._sparse_reads = (config.sparse_topk * config.sparse_block_size
                              if config.sparse_layers else 0)
        self.sparse_rows = 0
        self.sparse_slots_held = 0
        self.sparse_slots_read = 0
        # the rows a sparse layer's decode selection ran over (the trips of
        # `core/sala.select_needed`'s loop: the step's selecting rows, by
        # its own rule over the host's record of each row's length) and the
        # resident rows, which it ran over before it had a list, summed
        # over steps and sparse layers (`serving/select_rows_run`,
        # `serving/select_rows_resident`)
        self.select_rows_run = 0
        self.select_rows_resident = 0
        # the REAL tokens of those forwards (`dispatch_tokens` holds a
        # bucket's pads too): what a state's recurrence ran over
        self.state_tokens = 0
        # empty carry: every row starts done; admit() installs rows
        # through the same path mid-loop admissions use
        self.state = self._carry(
            jnp.full((R, self.max_tokens + self.block), self.pad_token_id,
                     jnp.int32),
            jnp.zeros((R, self.max_tokens + self.block),
                      jnp.int32 if self.block else jnp.float32),
            caches0,
            jnp.zeros((R, self.T_max), bool),
            jnp.ones((R,), bool),
            jnp.zeros((R,), jnp.int32),
            jnp.zeros((R,), jnp.int32))
        if self.per_row:
            # a cancel's one program, compiled now: no warm-up can make a
            # client vanish, and the first that does must compile nothing
            _end_row(self.state[5], 0)
        # 1 when the session's programs consume the pool they are given
        # (`serving/pool_donated`); one rule for all of them
        self.pool_donated = int(_decode_chunk.donates(caches0))
        # what the pool holds a token slot, over every layer and array
        # (`serving/kv_bytes_per_token`: read off the pool the model's cache
        # spec gave), and whether it is MLA's latent pool
        # (`serving/latent_cache`)
        pool_bytes = lambda pool, pages: sum(   # noqa: E731
            c.nbytes for c in jax.tree.leaves(pool)) // max(
                pages * self.page_size, 1)
        if patterned:   # a slot inside the window is held once a kind
            self.kv_bytes_per_token_global = pool_bytes(caches0[0],
                                                        self.num_pages)
            self.kv_bytes_per_token_window = pool_bytes(
                caches0[1], self.num_pages_window)
        else:
            self.kv_bytes_per_token_global = pool_bytes(caches0,
                                                        self.num_pages)
            self.kv_bytes_per_token_window = 0
        self.kv_bytes_per_token = (self.kv_bytes_per_token_global
                                   + self.kv_bytes_per_token_window)
        self.latent_cache = int(bool(config.kv_lora_rank))
        # a model with expert layers: the held experts the live rows
        # reached, summed over every decode step and layer so far
        # (`serving/held_experts_hit`; `_chunk_loop`)
        self.held_experts_hit = 0
        # the serving mode: the rows (a block session: positions) the
        # sampler ran over, summed over every step so far, and the rows
        # there were (`serving/sample_rows`, `serving/sample_slots`;
        # `_over_needed`)
        self.sample_rows = 0
        self.sample_slots = 0
        # static: the sizes of `needed_sizes` whose branch takes its
        # candidates by selection (`serving/sample_pick_sizes`)
        self.sample_pick_sizes = tuple(
            s for s in needed_sizes(R * max(self.block, 1))
            if sample_picks((s, config.vocab_size), top_k, approx_top_k)
        ) if per_row and not self.spec else ()
        # the host's record of the carry, as of the last sync and the
        # admissions and cancels since: what other threads may read
        self._done_np = np.ones((R,), bool)
        # what the device owes the host, in the device's own order: first
        # tokens (`_First`) and chunks (`_Flight`); `read()` takes the oldest
        self._unread: deque = deque()
        # counts a row's changes of occupant (install, release, cancel): a
        # flight keeps the counts of its dispatch, and its report speaks for
        # the rows where they still stand
        self._occupant_np = np.zeros((R,), np.int64)
        # a serving-mode report carries the tokens a chunk can have written a
        # row: one an iteration, or a whole row's where an iteration emits
        # several (speculation; its release wants the whole stream too)
        self._report_width = (
            self.max_tokens if self.spec
            else min(int(sync_every) * max(self.block, 1), self.max_tokens))
        self._nothing_seen = jnp.zeros((2,), jnp.int32)
        self._t_report = 0.0
        # beats dispatched while an earlier chunk's report was unread, and
        # first tokens the host read after their admission had returned
        # (`serving/beats_overlapped`, `serving/first_tokens_deferred`)
        self.beats_overlapped = 0
        self.first_tokens_deferred = 0
        # the beats by kind, never reset, of the reports that took a decode
        # step: clean, or loaded with a forward of more than one token that
        # the device ran before the chunk (`serving/beats_clean`, `_loaded`,
        # `beat_clean_s`, `beat_loaded_s`); and those forwards as the reports
        # brought them, every report's (`serving/foreign_forwards`)
        self.beats_clean = 0
        self.beats_loaded = 0
        self.beat_clean_s = 0.0
        self.beat_loaded_s = 0.0
        self.foreign_forwards = 0
        self._launches_flown = 0    # `launches` as of the last flight
        self._sync_reported = 0.0   # the `sync` seconds as of the last report

        self._sample_kw = dict(temperature=temperature, top_p=top_p,
                               greedy=greedy, top_k=top_k,
                               approx_top_k=approx_top_k)
        self._statics = dict(
            Tp=self.Tp, max_tokens=self.max_tokens, page_size=self.page_size,
            sync_every=int(sync_every), eos_token_id=self.eos_token_id,
            pad_token_id=self.pad_token_id, temperature=temperature,
            top_p=top_p, greedy=greedy, lora_scale=lora_scale, top_k=top_k,
            capture_logprobs=self.capture_logprobs,
            approx_top_k=approx_top_k,
        )
        if self.spec:
            self._statics.update(spec_k=self.spec_k,
                                 spec_ngram=self.spec_ngram)
        if self.block:
            self._block_statics = dict(
                Tp=self.Tp, page_size=self.page_size,
                sync_every=int(sync_every), eos_token_id=self.eos_token_id,
                lora_scale=lora_scale, top_k=top_k,
                approx_top_k=approx_top_k)
        # a block session's requests: denoise steps a block and the index
        # of the strategy (`blockdiff.REMASKING`); and its counters, the
        # carry's own as the last read brought them (`_BLOCK_SLOTS`), with
        # the prompt tokens that opened a first block already unmasked
        self._steps_np = np.full((R,), max(self.block, 1), np.int32)
        self._remask_np = np.zeros((R,), np.int32)
        self.block_counts = np.zeros((5,), np.int64)
        self.prompt_tail_tokens = 0

        # per-row sampling params (serving mode): host-of-record arrays,
        # uploaded as traced chunk arguments — the values the pre-session
        # engine kept in carry slots 8–11
        self._temp_np = np.ones((R,), np.float32)
        self._topp_np = np.ones((R,), np.float32)
        self._greedy_np = np.zeros((R,), bool)
        self._budget_np = np.ones((R,), np.int32)

        # speculative draft state: resident prompts + radix-seeded windows
        self._prompt_res_np = np.full((R, self.Tp), self.pad_token_id,
                                      np.int32)
        self._prompt_rep = jnp.asarray(self._prompt_res_np)
        self.seed_window = (self.max_tokens + self.spec_ngram
                            if (self.spec and self._radix is not None) else 0)
        if self.seed_window:
            self._seed_np = np.full((R, self.seed_window), self.pad_token_id,
                                    np.int32)
            self._seed_len_np = np.zeros((R,), np.int32)
            self._seed_rep = jnp.asarray(self._seed_np)
            self._seed_len = jnp.asarray(self._seed_len_np)

        self._kelems: list = [None] * R       # radix keys of resident rows
        self._pending: list[_PendingPrefill] = []

        # what the paged decode read touches (`_count_attention`): the
        # resident rows' first slot and depth as the scheduler's own events
        # give them, never read back from the device
        self._row_start_np = np.zeros((R,), np.int64)
        self._row_gen_np = np.zeros((R,), np.int64)
        self._row_live_np = np.zeros((R,), bool)
        self.attn_live_pages = 0
        self.attn_table_pages = 0
        # slots a decode step's read touched in a layer of each kind
        # (`serving/global_slots_read`, `serving/window_slots_read`), and
        # window pages written again behind the window, of which by a row
        # that was DECODING (its ring wrapped after its prompt:
        # `serving/window_pages_reused_in_decode`), and the live rows whose
        # context had passed the window, summed a step
        # (`serving/rows_past_window`, beside `serving/decode_steps`)
        self.global_slots_read = 0
        self.window_slots_read = 0
        self.window_pages_reused = 0
        self.window_pages_reused_in_decode = 0
        self.rows_past_window = 0
        self.live_row_steps = 0     # live rows, summed a step
        self._row_reused_np = np.zeros((R,), np.int64)
        # what the model layer will do, asked of the function that decides
        # it (`core/model.attention_form`), not worked out again here: the
        # decode step's paged read in place ...
        paged_read = partial(
            attention_form, config, cached=True, paged=True,
            cache_len=self.T_max)
        self.attn_in_place = int(
            not self.spec and paged_read(
                max(self.block, 1), decode=True) in ("paged_decode",
                                                     "paged_block"))
        # ... whose work list cuts a row's pages into items of this many
        # (`serving/paged_items`, `serving/paged_short_items`, counted where
        # the pages are: `_count_attention`); 0 where no such list is made
        # (another read, or a sparse model's own lists of chosen blocks)
        self._item_pages = 0
        if self.attn_in_place and not config.sparse_layers:
            self._item_pages = paged_pages_per_item(
                caches0[0][0] if patterned else caches0[0])
        self.paged_items = 0
        self.paged_short_items = 0
        # ... and, of the forwards dispatched for chunked admissions
        # (`_prefill_tick`: the pieces and each one's closing suffix
        # forward), whether their T > 1 paged read is the flash kernel over
        # the pages in place or XLA's walk / a slab / the gathered view
        self.prefill_pieces = 0
        self.prefill_read_in_place = int(paged_read(
            self.prefill_chunk or self.Tp, verify=True) == "paged_flash")
        # which write the programs were built with (`core/model.
        # _paged_cache_update`, `_kind_views`): a prefill piece's by page,
        # and a decode step's live rows through ops/paged_cache_write
        self.kv_write_by_page, live_rows = paged_write_forms(
            config, caches0, self.page_size, self.prefill_chunk or self.Tp,
            self.nb)
        self.kv_write_live_rows = int(live_rows and not self.spec
                                      and not self.block)
        # whether a layer takes its kernels by index into the whole stacks
        # (`core/model.leaves_in_place`, the layer runner's rule: every
        # cached forward of a pattern model; a model without a pattern scans
        # one layer a trip and never copied a period)
        self.layer_kernels_in_place = int(leaves_in_place(config, cached=True))
        # and whether its attention then fences the q, k and v projections'
        # results from the head split, so that each is a matmul over the
        # stack where it lies (`core/model._attention`; MLA's projections
        # are core/mla.py's own)
        self.qkv_kernels_in_place = int(
            self.layer_kernels_in_place and not config.kv_lora_rank)

        # dispatch accounting (module docstring): launches = model
        # forwards outside the decode/verify loop; decode iterations come
        # from the carry's own counter
        self.launches = 0
        self.dispatch_tokens = 0
        self.hit_tokens = 0
        self.chunked_admissions = 0
        self.backlog_peak = 0
        self._it_prev = 0

    def _carry(self, out, lp_out, caches, key_mask, done, cur_tok,
               prompt_len):
        """The carry at iteration 1 with one token a row. Every slot is an
        array of the carry's own, none shared with another slot or with
        the session: the chunk programs donate all of them."""
        R = self.rows
        state = (jnp.int32(1), out, lp_out, caches, key_mask, done, cur_tok,
                 jnp.ones((R,), jnp.int32), prompt_len, jnp.copy(self._key))
        if self.spec:
            # n_drafted · n_accepted · n_emitted · n_rowsteps · row_acc
            # (speculative._spec_state)
            state += tuple(jnp.int32(0) for _ in range(4)) + (
                jnp.zeros((R,), jnp.int32),)
        if self.block:      # blk · masked · step · base · counts
            B = self.block
            state = (state[:7] + (jnp.zeros((R,), jnp.int32),) + state[8:] + (
                jnp.full((R, B), self.config.mask_token_id, jnp.int32),
                jnp.ones((R, B), bool), jnp.zeros((R,), jnp.int32),
                jnp.zeros((R,), jnp.int32), jnp.zeros((5,), jnp.int32)))
        return state

    def _set_pool(self, caches):
        self.state = _with_pool(self.state, caches)

    # ------------------------------------------------------------- #
    # admission
    # ------------------------------------------------------------- #

    def bootstrap(self, prompt_ids, prompt_mask):
        """Batched initial admission for the non-radix rollout mode: one
        `_prefill_state` over the first `rows` prompts, pool fully
        claimed by the identity table — exactly the pre-session
        scheduler's initial batch, which is what keeps its greedy streams
        (and TTFT semantics) bit-identical. Never chunked: chunked
        prefill protects RESIDENT rows' latency, and there are none yet."""
        assert self._radix is None, "radix mode admits rows individually"
        R = self.rows
        t0 = time.perf_counter()
        base = _prefill_state_jit(
            self.params, self.config, prompt_ids[:R], prompt_mask[:R],
            self._key, max_tokens=self.max_tokens,
            eos_token_id=self.eos_token_id, pad_token_id=self.pad_token_id,
            lora_scale=self.lora_scale,
            capture_logprobs=self.capture_logprobs,
            page_size=self.page_size, **self._sample_kw)
        (_one, out0, lp0, caches, key_mask0, done0, tok0, plen0, _key) = base
        self.launches += 1
        self.dispatch_tokens += R * self.Tp
        if self._hub is not None:
            # every initial-batch row's first token exists once this
            # prefill lands: one TTFT observation per admitted request
            jax.block_until_ready(tok0)
            ttft0 = time.perf_counter() - t0
            for _ in range(R):
                self._hub.record("latency/ttft_s", ttft0)
        self.state = self._carry(out0, lp0, caches, key_mask0, done0, tok0,
                                 plen0)
        self._prompt_res_np[:] = np.asarray(prompt_ids[:R])
        self._prompt_rep = jnp.asarray(self._prompt_res_np)
        self._it_prev = 0
        self._done_np[:] = False    # exact at the first sync
        self._row_start_np[:] = self.Tp - np.asarray(prompt_mask[:R]).sum(1)
        self._row_gen_np[:] = 1
        self._row_live_np[:] = True

    def admit(self, r: int, toks_np, mask_np, admit_index: int, *,
              budget=None, temperature=None, top_p=None, greedy=None,
              t_start=None, denoising_steps=None, remasking=None):
        """Admit one prompt into resident row `r`.

        `admit_index` keys the admission PRNG fold
        (`fold_in(admit_key, _ADMIT_BASE + admit_index)`) — the rollout
        driver passes the queue index, the engine the request id.
        Rollout mode ignores the per-request kwargs (sampling params are
        session statics); serving mode requires `budget`.

        Radix mode may raise RuntimeError (pool exhausted even after
        eviction) BEFORE any row state changes — the engine sheds on it.

        A block session (docs/BLOCKDIFF.md) also takes the request's
        `denoising_steps` (1..block_length; None: block_length) and
        `remasking` (a name of `blockdiff.REMASKING`); it prefills the
        prompt's whole blocks only and installs the row with no first token
        (returns None: a row's tokens all come through `read()`'s reports).

        Per-row mode waits for nothing: it returns the first token as it
        stands ON THE DEVICE, and `read()` hands it to the driver as a host
        int (`FirstToken`) in its turn. Rollout mode returns None (it waits
        for the token only to time it, with a latency hub attached), and so
        does a chunked admission in either mode (the
        first token lands when the final chunk installs the row — drivers
        must treat `is_pending(r)` rows as not-yet-done)."""
        toks_np = np.asarray(toks_np, np.int32)
        mask_np = np.asarray(mask_np, bool)
        t0 = time.perf_counter() if t_start is None else t_start
        if self.block:      # (before any page is claimed)
            steps = int(denoising_steps or self.block)
            remask = REMASKING.index(remasking or REMASKING[0])
            if not 1 <= steps <= self.block:
                raise ValueError(
                    f"denoising_steps={denoising_steps} outside "
                    f"[1, {self.block}] (the block length)")
        pad_count = int(self.Tp - mask_np.sum())
        a_key = jax.random.fold_in(self._admit_key, _ADMIT_BASE
                                   + int(admit_index))

        kelems = plan = seed = None
        if self._radix is not None:
            from nanorlhf_tpu.serving.radix import copy_page, prompt_key
            with self.timer.phase("plan", request=int(admit_index), row=r):
                kelems = prompt_key(toks_np, mask_np)
                # may raise RuntimeError — before any state mutation
                plan = self._radix.plan(kelems, pad_count=pad_count,
                                        n_blocks=self.nb, prompt_len=self.Tp)
                if self.seed_window:
                    seed = self._radix.matched_continuation(
                        kelems, self.seed_window)
                if plan.m > 0 or plan.cow_src is not None:
                    self.config.require("a radix prefix hit")
                self.table_np[r] = plan.row_pages
                if self._ring is not None:
                    self._claim_ring(r, plan, pad_count, budget)
                if plan.cow_src is not None:
                    self._set_pool(copy_page(self.state[3], plan.cow_src,
                                             plan.cow_dst))
                # per-row mode runs the unified suffix forward even on a
                # cold miss (start = pad_count, pad KV never written);
                # rollout mode keeps the cold full-row prefill so its
                # streams stay bit-identical to the uncached scheduler
                if plan.m > 0:
                    start = plan.m
                elif self.per_row:
                    start = pad_count
                else:
                    start = None
        else:
            self._pstate, ok = _alloc_jit(self._pstate, r, self.nb)
            assert bool(ok), \
                "allocator underflow: full-budget rows recycle uniformly"
            start = None

        row_table_np = None
        if self._radix is None:
            row_table_np = self._pstate.table[r]

        pend = _PendingPrefill(
            row=r, index=int(admit_index), toks=toks_np, mask=mask_np,
            pad_count=pad_count,
            next_slot=0, admit_key=a_key, t_start=t0, kelems=kelems,
            plan_hit=(plan.hit_tokens if plan is not None else 0),
            seed=seed, budget=budget,
            temperature=(1.0 if temperature is None else float(temperature)),
            top_p=(1.0 if top_p is None else float(top_p)),
            greedy=bool(greedy), row_table=row_table_np, end=self.Tp)
        if self.block:
            # the prompt's whole blocks are prefilled; its tail opens the
            # first generated block
            plen = self.Tp - pad_count
            pend.end = pad_count + plen // self.block * self.block
            pend.denoising_steps, pend.remasking = steps, remask

        if start is None:
            # cold full-row prefill (rollout mode): identical to the
            # uncached path, and — when chunking is on — chunked from the
            # first REAL token through the same KV-only forwards
            start_abs = pad_count
            full_cold = True
        else:
            start_abs = start
            full_cold = False
        s_real = pend.end - start_abs
        C = self.prefill_chunk
        if C > 0 and s_real > C:
            pend.next_slot = start_abs
            pend.meta["full_cold"] = full_cold
            self._pending.append(pend)
            self.backlog_peak = max(self.backlog_peak,
                                    self._backlog_tokens())
            self.chunked_admissions += 1
            return None
        with self.timer.phase("admit_forward", request=int(admit_index),
                              row=r):
            return self._admit_now(pend, full_cold=full_cold,
                                   start_abs=start_abs)

    def _claim_ring(self, r, plan, pad_count, budget):
        """The window layers' pages of row `r`, for its real blocks from the
        first prompt token to its budget's last slot. The radix tree holds no
        window state, so a prefix hit would hand the row global pages whose
        window twins nobody wrote: none can occur (`_install` inserts
        nothing for such a model), and one that did has raised (`admit`)."""
        last = self.Tp + (self.max_tokens if budget is None else int(budget)) - 1
        try:
            self._ring.claim(r, pad_count // self.page_size,
                             min(last // self.page_size, self.nb - 1))
        except RuntimeError:
            self._radix.release(self.table_np[r])
            self.table_np[r] = self.num_pages
            raise
        self._row_reused_np[r] = 0

    def _row_table(self, r: int):
        """Row `r`'s block table for an admission forward: the global pages,
        with the window ring's beside them for a pattern model."""
        if self._ring is not None:
            kinds = (jnp.array(self.table_np[r]),
                     jnp.array(self._ring.table[r]))
            if self.state_layers:   # the row's place in the state
                kinds += (jnp.array([r], jnp.int32),)
            return kinds
        return jnp.array(self.table_np[r])

    def _admit_now(self, pend: _PendingPrefill, *, full_cold: bool,
                   start_abs: int):
        """Unchunked (or final-chunk-only) admission forward + install."""
        from nanorlhf_tpu.serving.radix import bucket_len, suffix_logits
        p = pend
        row_table = (self._row_table(p.row)
                     if self._radix is not None else p.row_table)
        if self.block:
            # KV only, in the power-of-two bucket of what is left of the
            # prompt's whole blocks: nothing is sampled from a prompt, and
            # the bucket's pad tokens land in slots no one marks valid,
            # which the first block's forwards write again
            s_real = p.end - start_abs
            if s_real > 0:
                Sb = bucket_len(s_real, self.T_max - start_abs)
                suffix = np.zeros((1, Sb), np.int32)
                suffix[0, :s_real] = p.toks[start_abs:p.end]
                pos = ((start_abs - p.pad_count)
                       + np.arange(Sb, dtype=np.int32)[None])
                km = np.zeros((1, self.T_max), bool)
                km[0, p.pad_count:start_abs] = True
                self._set_pool(_prefill_chunk_fwd(
                    self.params, self.config, jnp.asarray(suffix),
                    jnp.asarray(pos), jnp.asarray([start_abs], jnp.int32),
                    jnp.asarray(km), self.state[3], row_table,
                    page_size=self.page_size, lora_scale=self.lora_scale))
                self.dispatch_tokens += Sb
                self.launches += 1
            return self._install_block(p)
        if full_cold and not self.per_row and self.prefill_chunk == 0:
            # the pre-session cold path: one full-row prefill (pads
            # included) — kept verbatim so rollout parity pins hold
            caches, t0, l0, plen = _admit_one(
                self.params, self.config, jnp.asarray(p.toks[None, :]),
                jnp.asarray(p.mask[None, :]), self.state[3], row_table,
                p.admit_key, page_size=self.page_size, T_max=self.T_max,
                lora_scale=self.lora_scale, **self._sample_kw)
            self._set_pool(caches)
            self.dispatch_tokens += self.Tp
        else:
            s_real = self.Tp - start_abs
            Sb = bucket_len(s_real, self.T_max - start_abs)
            suffix = np.zeros((1, Sb), np.int32)
            suffix[0, :s_real] = p.toks[start_abs:]
            pos = ((start_abs - p.pad_count)
                   + np.arange(Sb, dtype=np.int32)[None])
            km = np.zeros((1, self.T_max), bool)
            km[0, p.pad_count:start_abs] = True
            self._count_state(p, start_abs)
            logits, caches = suffix_logits(
                self.params, self.config, jnp.asarray(suffix),
                jnp.asarray(pos), jnp.asarray([start_abs], jnp.int32),
                jnp.int32(s_real - 1), jnp.asarray(km), self.state[3],
                row_table, *self._call_keys(p), page_size=self.page_size,
                lora_scale=self.lora_scale)
            self._set_pool(caches)
            self.dispatch_tokens += Sb
            self.hit_tokens += p.plan_hit
            if self.per_row:
                t0 = _first_token(
                    logits, p.admit_key, jnp.float32(p.temperature),
                    jnp.float32(p.top_p), jnp.asarray(p.greedy),
                    top_k=self._sample_kw["top_k"],
                    approx_top_k=self._sample_kw["approx_top_k"])
                l0 = jnp.float32(0.0)
            else:
                t0, l0 = _admit_sample(logits, p.admit_key,
                                       **self._sample_kw)
            plen = jnp.int32(int(p.mask.sum()))
        self.launches += 1
        return self._install(p, t0, l0, plen)

    def _install(self, p: _PendingPrefill, t0, l0, plen):
        r = p.row
        if self._radix is not None and self._ring is None:
            self._radix.insert(p.kelems, self.table_np[r], self.Tp)
            self._kelems[r] = p.kelems
        if self.per_row:
            self._temp_np[r] = p.temperature
            self._topp_np[r] = p.top_p
            self._greedy_np[r] = p.greedy
            self._budget_np[r] = int(p.budget)
        self._row_start_np[r] = p.pad_count
        self._row_gen_np[r] = 1
        self._row_live_np[r] = not self.per_row or int(p.budget) > 1
        if self.spec:
            self._prompt_res_np[r] = p.toks
            self._prompt_rep = jnp.asarray(self._prompt_res_np)
            if self.seed_window:
                W = self.seed_window
                self._seed_np[r] = self.pad_token_id
                n = 0 if p.seed is None else min(len(p.seed), W)
                if n:
                    self._seed_np[r, W - n:] = p.seed[:n]
                self._seed_len_np[r] = n
                self._seed_rep = jnp.asarray(self._seed_np)
                self._seed_len = jnp.asarray(self._seed_len_np)
        if self._hub is not None and not self.per_row:
            # t0 is the admission forward's sampled first token: blocking
            # on it gives this request's true TTFT
            jax.block_until_ready(t0)
            self._hub.record("latency/ttft_s",
                             time.perf_counter() - p.t_start)
        self.state = _with_pool(_install_row(
            _sans_pool(self.state), r, t0, l0, jnp.asarray(p.mask), plen,
            (jnp.int32(int(p.budget)) if self.per_row else None),
            Tp=self.Tp, max_tokens=self.max_tokens,
            eos_token_id=self.eos_token_id, pad_token_id=self.pad_token_id,
            spec=self.spec, per_row=self.per_row), self.state[3])
        self._occupant_np[r] += 1
        # live until a read says otherwise (a budget of one token is spent
        # already)
        self._done_np[r] = self.per_row and int(p.budget) <= 1
        if not self.per_row:
            return None
        # serving: the token stays on the device, queued for `read()` behind
        # whatever was dispatched before this admission
        self._unread.append(_First(p, t0))
        return t0

    def _install_block(self, p: _PendingPrefill):
        """A block session's install: the request's parameters, and the
        row's carry with its first block open (`_install_block_row`). No
        page of the row enters the radix tree and no first token exists."""
        r, B = p.row, self.block
        self._temp_np[r] = p.temperature
        self._topp_np[r] = p.top_p
        self._greedy_np[r] = p.greedy
        self._budget_np[r] = int(p.budget)
        self._steps_np[r] = p.denoising_steps
        self._remask_np[r] = p.remasking
        self._row_start_np[r] = p.pad_count
        self._row_live_np[r] = True
        tail = np.zeros((B,), np.int32)
        tail[:self.Tp - p.end] = p.toks[p.end:]
        self.prompt_tail_tokens += self.Tp - p.end
        self.state = _with_pool(_install_block_row(
            _sans_pool(self.state), r, jnp.int32(self.Tp - p.pad_count),
            jnp.int32(p.end - p.pad_count), jnp.asarray(tail), Tp=self.Tp,
            pad_token_id=self.pad_token_id,
            mask_token_id=self.config.mask_token_id), self.state[3])
        self._occupant_np[r] += 1
        self._done_np[r] = False
        return None

    # ------------------------------------------------------------- #
    # stepping
    # ------------------------------------------------------------- #

    def _prefill_tick(self):
        """Advance the OLDEST pending chunked admission by exactly one
        KV-only chunk forward; the final chunk (<= prefill_chunk real
        tokens) runs the normal suffix+install path, with the SAME
        admission PRNG fold as an unchunked admission — chunked-on/off
        greedy streams are bit-identical (sampled rows decode at later
        global folds, so they match in distribution only)."""
        p = self._pending[0]
        self.prefill_pieces += 1
        remaining = p.end - p.next_slot
        C = self.prefill_chunk
        if remaining <= C:
            self._pending.pop(0)
            self._admit_now(p, full_cold=p.meta.get("full_cold", False),
                            start_abs=p.next_slot)
            return (p.row, None)
        chunk = p.toks[p.next_slot:p.next_slot + C][None, :]
        pos = ((p.next_slot - p.pad_count)
               + np.arange(C, dtype=np.int32)[None])
        km = np.zeros((1, self.T_max), bool)
        km[0, p.pad_count:p.next_slot] = True
        row_table = (self._row_table(p.row)
                     if self._radix is not None else p.row_table)
        self._count_state(p, p.next_slot)
        self._set_pool(_prefill_chunk_fwd(
            self.params, self.config, jnp.asarray(chunk), jnp.asarray(pos),
            jnp.asarray([p.next_slot], jnp.int32), jnp.asarray(km),
            self.state[3], row_table, *self._call_keys(p),
            page_size=self.page_size, lora_scale=self.lora_scale))
        p.next_slot += C
        self.launches += 1
        self.dispatch_tokens += C
        return None

    def _call_keys(self, p: _PendingPrefill) -> tuple:
        """What a piece of `p`'s prompt says of the call it belongs to, for
        a model with sparse-attention layers (docs/SALA.md: a prompt selects
        in whole or not at all, whatever the pieces it is cut into): the
        prompt's length; nothing for every other model."""
        if not self.config.sparse_layers:
            return ()
        return (jnp.asarray([self.Tp - p.pad_count], jnp.int32),)

    def _count_state(self, p: _PendingPrefill, start_abs: int) -> None:
        """An admission forward from slot `start_abs`, as the conv state
        sees it: with no slot of the row before it the forward starts from
        zeros (`decode_verify`'s `fresh`), else it takes what the piece
        before it left."""
        if self.state_layers:
            if start_abs > p.pad_count:
                self.state_piece_carries += 1
            else:
                self.state_resets += 1
            self.state_tokens += min(p.end - start_abs,
                                     self.prefill_chunk or self.Tp)

    @property
    def looks_ahead(self) -> bool:
        """Whether the driver may dispatch the next chunk before it has read
        this one: what the session is, not an option. A serving-mode
        session reads only reports; the rollout scheduler reads `done` off
        the carry to refill, and speculation verifies against it, before
        their next chunk."""
        return self.per_row and not self.spec

    def unread(self) -> int:
        """First tokens and chunks the device still owes the host."""
        return len(self._unread)

    def step(self):
        """One serial beat: `dispatch()`, then every `read()`. Returns
        (done_h, installed) — the host done flags and the (row, None) of an
        admission whose final chunk landed this beat, if any."""
        installed = self.dispatch()
        while self._unread:
            got = self.read()       # first tokens, then the chunk's report
        return got.done, installed

    def dispatch(self):
        """The device's half of a beat, enqueued and not waited for: at most
        one pending-prefill chunk, then one decode (or draft+verify) chunk
        of up to `sync_every` iterations and, in serving mode, the report
        the host will read of it. Returns the (row, None) of an admission
        whose final chunk this beat installed, if any."""
        installed = None
        phase = self.timer.phase
        if self._pending:
            p = self._pending[0]
            with phase("prefill_tick", request=p.index, row=p.row):
                installed = self._prefill_tick()
        flight = _Flight(time.perf_counter(), self._occupant_np.copy(),
                         self.launches - self._launches_flown)
        self._launches_flown = self.launches
        with phase("dispatch"):
            if any(isinstance(u, _Flight) for u in self._unread):
                self.beats_overlapped += 1
            # copies (class docstring): the host changes these under a
            # flight
            table_dev = (jnp.array(self.table_np)
                         if self._radix is not None else self._pstate.table)
            if self._ring is not None:
                ring_table = self._ring.table
                if self._pending:
                    # a row between two of its prefill pieces is resident
                    # and not live, and a decode step still writes such a
                    # row's token at slot Tp - 1 wherever the write is the
                    # row scatter (every backend but a TPU under the
                    # live-row kernel). Its global page is the last piece's
                    # to overwrite; in the RING that block shares a page
                    # with a block `ring` blocks earlier, which the next
                    # piece's window may still read. The chunk sees no ring
                    # of a pending row (the pieces take `_row_table`).
                    ring_table = ring_table.copy()
                    ring_table[[p.row for p in self._pending]] = \
                        self._ring.num_pages
                table_dev = (table_dev, jnp.array(ring_table))
                if self.state_layers:
                    table_dev += (self._state_rows,)
            if self.spec:
                if self.seed_window:
                    result = _spec_chunk_seeded(
                        self.params, self.config, self.state, table_dev,
                        self._prompt_rep, self._seed_rep, self._seed_len,
                        **self._statics)
                else:
                    result = _spec_chunk(
                        self.params, self.config, self.state, table_dev,
                        self._prompt_rep, **self._statics)
            elif self.block:
                result = _block_chunk(
                    self.params, self.config, self.state, table_dev,
                    jnp.array(self._temp_np), jnp.array(self._topp_np),
                    jnp.array(self._greedy_np), jnp.array(self._budget_np),
                    jnp.array(self._steps_np), jnp.array(self._remask_np),
                    **self._block_statics)
            elif self.per_row:
                result = _serving_chunk(
                    self.params, self.config, self.state, table_dev,
                    jnp.array(self._temp_np), jnp.array(self._topp_np),
                    jnp.array(self._greedy_np), jnp.array(self._budget_np),
                    **self._statics)
            else:
                result = _decode_chunk(
                    self.params, self.config, self.state, table_dev,
                    **self._statics)
            # a model with expert layers, and the serving mode, hand their
            # counts back beside the carry (`_chunk_loop`); they stay on the
            # device until the read
            if ((self.config.live_rows_dispatch or self.per_row)
                    and not self.spec):
                result, flight.hit = result
            self.state = s = result
            if self.block:
                flight.meta, flight.rows = _block_report(
                    s[0], s[1], s[2], s[5], s[7], flight.hit, s[14],
                    width=self._report_width)
                flight.meta.copy_to_host_async()
                flight.rows.copy_to_host_async()
            elif self.per_row:
                flight.meta, flight.rows = _beat_report(
                    s[0], s[1], s[5], s[7],
                    self._nothing_seen if flight.hit is None else flight.hit,
                    width=self._report_width)
                flight.meta.copy_to_host_async()
                flight.rows.copy_to_host_async()
            self._unread.append(flight)
        return installed

    def read(self):
        """Wait for the oldest thing the device owes the host and bring the
        host's records up to it: a `FirstToken`, or a chunk's `BeatReport`
        (the counters of a beat all advance here, together)."""
        item = self._unread.popleft()
        phase = self.timer.phase    # "sync": the host waits for the device
        if isinstance(item, _First):
            p = item.pend
            with phase("sync", request=p.index, row=p.row):
                tok = int(item.tok)
            self.first_tokens_deferred += 1
            if self._hub is not None:       # TTFT: the host has the token
                self._hub.record("latency/ttft_s",
                                 time.perf_counter() - p.t_start)
            return FirstToken(p.row, p.index, tok, self._waited())
        with phase("sync", foreign=item.foreign) as span:
            if item.rows is None:       # rollout mode reads the carry itself
                done_h = np.asarray(self.state[5])
                it_now = int(self.state[0])
                hit = 0 if item.hit is None else int(item.hit)
            else:
                meta, rows = np.asarray(item.meta), np.asarray(item.rows)
                it_now, hit, taken = meta[:3]
                if self.block:
                    self.block_counts = meta[3:].astype(np.int64)
            it_now = int(it_now) - 1
            its = it_now - self._it_prev
            span.set_metadata(its=its)
        now = time.perf_counter()
        # one mean inter-token gap a chunk: from the report before this one
        # (or this chunk's dispatch, if that came later) to this report.
        # The serving driver only records when the counter advanced (its
        # loop also spins on admission-only beats).
        since = max(item.t0, self._t_report)
        self._t_report = now
        period = now - since
        if self._hub is not None and (its > 0 or not self.per_row):
            self._hub.record("latency/intertoken_s", period / max(1, its))
        self.foreign_forwards += item.foreign
        if its > 0 and item.foreign:
            self.beats_loaded += 1
            self.beat_loaded_s += period
        elif its > 0:
            self.beats_clean += 1
            self.beat_clean_s += period
        self.held_experts_hit += int(hit)
        if item.rows is not None and not self.spec:
            self.sample_rows += int(taken)
            self.sample_slots += its * self.rows * max(self.block, 1)
        if item.rows is None:
            self._count_attention(its, done_h)
            report = BeatReport(its, done_h)
            np.copyto(self._done_np, done_h)
        elif self.block:
            current = item.occupants == self._occupant_np
            w = self._report_width
            report = BeatReport(its, rows[:, 0].astype(bool), rows[:, 1],
                                rows[:, 2:2 + w], current, rows[:, 2 + w:])
            # what the live rows' block reads spanned is the carry's own
            # count (rows stand at different blocks: the host cannot know)
            self.live_row_steps = int(self.block_counts[0])
            self.attn_live_pages = int(self.block_counts[4])
            self.attn_table_pages += its * self.rows * self.nb
            self._row_live_np[current] &= ~report.done[current]
            self._done_np[current] = report.done[current]
        else:
            current = item.occupants == self._occupant_np
            report = BeatReport(its, rows[:, 0].astype(bool), rows[:, 1],
                                rows[:, 2:], current)
            self._count_attention(its, report.done, report.n_gen, current)
            self._done_np[current] = report.done[current]
        self._it_prev = it_now
        report.period_s, report.wait_s, report.t = period, self._waited(), now
        report.foreign = item.foreign
        self._sync_reported = self.timer.cumulative["sync"]
        return report

    def _waited(self) -> float:
        """The seconds of `sync` since the last report."""
        return self.timer.cumulative["sync"] - self._sync_reported

    def _count_attention(self, its: int, done_h, n_gen=None,
                         current=None) -> None:
        """Never-reset `attn_live_pages` / `attn_table_pages`, summed over
        the `its` decode steps of the chunk just read: the pages the
        in-place read touches (a live row's blocks from its first real slot
        to the slot it writes) against the `rows x blocks` the gathered view
        builds whatever is live; `live / table` is the share of the view the
        kernel has to read. A serving-mode report says how far each row got
        (`n_gen`), and the count is held to the rows it still speaks for
        (`current`: a row cancelled under the flight is not counted for it).
        Rollout mode counts from the host's own record of each row (pad
        count, tokens so far, budget): a row that ends on EOS inside a chunk
        is counted to its budget or the chunk's end. Speculative sessions
        verify, they take no single-token step: not counted."""
        if self.spec or its <= 0:
            return
        if n_gen is None:
            live = self._row_live_np
            steps = np.where(live, np.minimum(
                its, self.max_tokens - self._row_gen_np), 0)
        else:
            live = self._row_live_np & current
            steps = np.where(live, n_gen - self._row_gen_np, 0)
        first = self._row_start_np // self.page_size
        window = self.config.sliding_window if self.window_layers else 0
        for s in range(its):
            slot = self.Tp + self._row_gen_np + s - 1
            last = slot // self.page_size
            pages = np.where(steps > s, last - first + 1, 0)
            self.attn_live_pages += int(pages.sum())
            if self._item_pages:
                items, short = paged_item_counts(pages, self._item_pages)
                self.paged_items += items
                self.paged_short_items += short
            span = np.where(steps > s, slot - self._row_start_np + 1, 0)
            self.live_row_steps += int((steps > s).sum())
            self.global_slots_read += int(span.sum())
            if self._sparse_reads:      # (docs/SALA.md; docs/METRICS.md)
                past = span >= self.config.sparse_dense_len
                layers = self.config.sparse_layers
                self.sparse_rows += int(past.sum())
                self.sparse_slots_held += int(span[past].sum())
                self.sparse_slots_read += int(np.minimum(
                    span[past], self._sparse_reads).sum())
                self.select_rows_run += layers * int(past.sum())
                self.select_rows_resident += layers * self.rows
            if window:
                self.window_slots_read += int(np.minimum(span, window).sum())
                self.rows_past_window += int((span > window).sum())
        self.attn_table_pages += its * self.rows * self.nb
        self._row_gen_np += steps
        if self._ring is not None:
            for r in np.flatnonzero(live):
                reused = self._ring.reused(
                    r, (self.Tp + self._row_gen_np[r] - 2) // self.page_size)
                self.window_pages_reused += reused - self._row_reused_np[r]
                # what the prompt's own blocks had wrapped is not decode's
                self.window_pages_reused_in_decode += max(0, reused - max(
                    int(self._row_reused_np[r]), self._ring.reused(
                        r, (self.Tp - 1) // self.page_size)))
                self._row_reused_np[r] = reused
        self._row_live_np[live] &= ~done_h[live]

    # ------------------------------------------------------------- #
    # release / introspection
    # ------------------------------------------------------------- #

    @property
    def pool_reserved_slots(self) -> int:
        """The slots the live rows' pages reserved, summed over the decode
        steps counted so far: a row claims its whole budget's `nb` pages at
        admission (`serving/pool_reserved_slots`; the slots of them that
        held a token are `global_slots_read`, `serving/pool_live_slots`)."""
        return self.live_row_steps * self.nb * self.page_size

    def iterations(self) -> int:
        """Decode/verify iterations so far: the carry's own counter as
        `read()` last brought it to the host (only chunks advance it)."""
        return self._it_prev

    def dispatch_events(self) -> int:
        """Total model-forward launches: admission/chunk forwards plus
        decode (or verify) iterations — the spec+radix A/B's unit."""
        return self.launches + self.iterations()

    def is_pending(self, r: int) -> bool:
        return any(p.row == r for p in self._pending)

    def pending_rows(self):
        return {p.row for p in self._pending}

    def has_pending(self) -> bool:
        return bool(self._pending)

    def _backlog_tokens(self) -> int:
        return int(sum(p.end - p.next_slot for p in self._pending))

    def release(self, r: int, gen_tokens=None) -> int:
        """Release row `r`'s pages (radix: drop the ROW's refs — tree
        refs survive as cached prefix KV; free-stack: push the row's
        pages). When the drafter seed is active and `gen_tokens` (the
        row's emitted tokens, EOS included) is given, the generated
        continuation is appended to the radix tree as TEXT-ONLY nodes
        (`RadixCache.extend_text`) so the next overlapping admission can
        seed its n-gram window from it. Returns pages freed."""
        self._occupant_np[r] += 1
        if self._radix is not None:
            if (self.seed_window and gen_tokens is not None
                    and self._kelems[r] is not None):
                ext = self._kelems[r] + tuple(
                    int(t) * 2 + 1 for t in np.asarray(gen_tokens).ravel())
                self._radix.extend_text(ext)
            freed = self._radix.release(self.table_np[r])
            self.table_np[r] = self.num_pages
            self._kelems[r] = None
            if self._ring is not None:
                self._ring.release(r)
            return freed
        self._pstate, m = _release_jit(self._pstate, r)
        return int(m)

    def cancel_row(self, r: int) -> None:
        """Serving-side reap: drop any pending chunked admission for the
        row, force its done flag (the next chunk then skips it; one in
        flight decodes the row to its end, class docstring), and free its
        pages — mirrors the completion path exactly so a disconnect can
        never leak what a completion would have freed."""
        self._pending = [p for p in self._pending if p.row != r]
        self._row_live_np[r] = False
        self._done_np[r] = True
        s = list(self.state)
        s[5] = _end_row(s[5], r)
        self.state = tuple(s)
        self.release(r)

    def utilization(self) -> float:
        """Allocated / total pages right now."""
        if self._radix is not None:
            return 1.0 - self._radix.pool.free_count / self.num_pages
        return 1.0 - float(np.asarray(self._pstate.top)) / self.num_pages

    def shared_pages(self) -> int:
        return (self._radix.pool.shared_count()
                if self._radix is not None else 0)

    def status(self) -> dict:
        """JSON-able /statusz `session` section: resident rows, the
        chunked-prefill backlog, and per-row feature flags. Safe from any
        thread: it reads the host's record, never the carry."""
        done_h = self._done_np.copy()
        pend = self.pending_rows()
        return {
            "rows": self.rows,
            "live_rows": int((~done_h).sum()),
            "mode": "serving" if self.per_row else "rollout",
            "features": {
                "spec_k": self.spec_k,
                "prefix_cache": self._radix is not None,
                "prefill_chunk": self.prefill_chunk,
                "per_row_sampling": self.per_row,
                "drafter_seed_window": self.seed_window,
            },
            "pending_prefill": {
                "rows": sorted(pend),
                "backlog_tokens": self._backlog_tokens(),
            },
            "row_flags": [
                {"live": bool(not done_h[r]),
                 "chunk_pending": r in pend,
                 "seeded_draft_len": (int(self._seed_len_np[r])
                                      if self.seed_window else 0)}
                for r in range(self.rows)
            ],
            "counters": {
                "launches": self.launches,
                "decode_iterations": self.iterations(),
                "dispatch_events": self.dispatch_events(),
                "dispatch_tokens": self.dispatch_tokens,
                "prefix_hit_tokens": self.hit_tokens,
                "chunked_admissions": self.chunked_admissions,
                "prefill_backlog_peak": self.backlog_peak,
            },
        }
