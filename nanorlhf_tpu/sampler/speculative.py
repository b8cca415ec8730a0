"""Draft-free speculative decode: n-gram drafting + batched k-token verify.

The rollout at the headline operating point is HBM-bandwidth-bound: ≈8.7 of
the ≈9 ms/step roofline is weight + KV-cache streaming, paid once per
SINGLE generated token (docs/DECODE_ANALYSIS.md). Verifying k drafted
tokens in one `decode_verify` forward amortizes that dominant stream over
every accepted token — the decode-side lever TPU-scale RL stacks lean on
to keep generation off the critical path (RLAX, arxiv 2512.06392;
PipelineRL, arxiv 2509.19128). R1-style math rollouts are highly
self-repetitive (restated problem text, `\\boxed{}` scaffolding, step
templates), so a FREE drafter — prompt-lookup n-gram matching against the
row's own prompt+output buffer, no draft model, zero extra weights —
gets useful acceptance with zero extra model memory.

Per iteration (one `lax.while_loop` step, fully jitted, static shapes):

  1. **draft**: match the last `spec_ngram` emitted tokens of each row
     against every earlier window of its prompt+output buffer (pure
     shifted-compare + gather — no sort, no host sync); propose the
     `spec_k` tokens that followed the most recent match. No match →
     propose pads; verification rejects them and the row still advances
     one token (the bounded-overhead case: one verify forward per token,
     ≈ the monolithic step plus the k extra query rows).
  2. **verify**: ONE small-T causal forward over [cur_tok, d_1..d_k]
     against the cache (`core/model.decode_verify`), producing the exact
     next-token distribution after each candidate prefix.
  3. **accept**: greedy rows keep the longest draft prefix that matches
     the argmax chain — bit-exact vs the monolithic loop. Sampled rows run
     Leviathan/Chen rejection sampling with the deterministic drafter as
     the proposal (accept d with prob p̃(d); on reject, sample from p̃ with
     d removed, renormalized) against the SAME filtered distribution
     `_sample_token` draws from (`filtered_logits_full` shares the
     candidate/keep-rule code), so the output distribution is provably
     unchanged — pinned by the enumeration test in
     tests/test_speculative.py. Every iteration emits between 1 and k+1
     tokens per live row.

Bookkeeping is per-row (accepted rows advance at different rates): the
carry holds [B] generated-token counts, cache fill follows
`Tp + n_gen - 1`, accepted candidates' KV (already written by the verify
forward) is made visible by extending `key_mask`, and rejected candidates
leave garbage KV in never-validated slots that the next verify overwrites.
The KV cache carries `spec_k` slack slots past Tp + max_tokens so a row
one token short of the budget can still absorb a full k+1 candidate write
without clamping into valid slots. On the PAGED layout (`page_size` > 0)
that slack is gated to 0: a candidate write past the row's page budget
drops at the table-routed scatter instead of clobbering anything, and the
dropped positions sit beyond `max_tokens - n_gen`, which the emission
clamp truncates anyway — see docs/PAGED_CACHE.md for the bound.

The paged cache (SamplingParams.page_size) is the straggler lever and
COMPOSES with this path: monolithic paged verify here, and the
continuous-batching scheduler (sampler/paged/scheduler.py) reuses
`_draft_fn`/`_verify_fn` directly with a live block table.

`capture_logprobs` reuses the verify logits: accepted tokens carry the
same full-distribution logprob `_token_logprob` computes in the monolithic
loop (greedy parity is test-pinned).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from nanorlhf_tpu.core.config import ModelConfig
from nanorlhf_tpu.core.model import decode_verify
from nanorlhf_tpu.ops.masking import guard_temperature
from nanorlhf_tpu.sampler.paged.pages import full_table
from nanorlhf_tpu.sampler.sampler import (
    _prefill_state,
    filtered_logits_full,
)

# static-arg sets for the jitted entrypoints (each lists exactly the
# names present in the wrapped signature — jax rejects unknown names)
_GEN_STATIC = (
    "config", "max_tokens", "eos_token_id", "pad_token_id", "spec_k",
    "spec_ngram", "temperature", "top_p", "greedy", "lora_scale", "top_k",
    "capture_logprobs", "approx_top_k", "prompt_fanout", "page_size",
)
_VERIFY_STATIC = (
    "config", "Tp", "max_tokens", "eos_token_id", "pad_token_id", "spec_k",
    "temperature", "top_p", "greedy", "lora_scale", "top_k",
    "capture_logprobs", "approx_top_k", "page_size",
)


def ngram_propose(buf, end, valid_start, *, k, m, pad_token_id):
    """Prompt-lookup drafting, static shapes, pure gather.

    buf: [B, S] per-row token buffer (left-padded prompt at
    [valid_start, Tp), generated tokens at [Tp, end), pads elsewhere).
    end / valid_start: [B] int32. Proposes the k tokens that followed the
    MOST RECENT earlier occurrence of the row's last m tokens; rows with
    no match get `pad_token_id` drafts (verification rejects them).
    Returns (drafts [B, k] int32, has_match [B] bool).
    """
    B, S = buf.shape
    # context: the last m tokens of each row, buf[end-m .. end-1]
    ctx_pos = jnp.clip(end[:, None] - m + jnp.arange(m)[None, :], 0, S - 1)
    ctx = jnp.take_along_axis(buf, ctx_pos, axis=1)          # [B, m]
    # match[b, j]: the window ENDING at j equals ctx. shifted_d[b, j] =
    # buf[b, j-d] (zero-filled below j=d; those j fail the range check)
    match = jnp.ones((B, S), bool)
    for d in range(m):
        shifted = jnp.pad(buf, ((0, 0), (d, 0)))[:, :S] if d else buf
        match = match & (shifted == ctx[:, m - 1 - d][:, None])
    j = jnp.arange(S)[None, :]
    in_range = (j - (m - 1) >= valid_start[:, None]) & (j <= end[:, None] - 2)
    j_star = jnp.max(jnp.where(match & in_range, j, -1), axis=1)  # [B]
    has = j_star >= 0
    d_pos = jnp.clip(j_star[:, None] + 1 + jnp.arange(k)[None, :], 0, S - 1)
    drafts = jnp.take_along_axis(buf, d_pos, axis=1)
    drafts = jnp.where(has[:, None], drafts, pad_token_id)
    return drafts.astype(jnp.int32), has


def accept_candidates(logits, drafts, step_key, *, temperature, top_p, top_k,
                      greedy, approx_top_k):
    """Exact acceptance rule over verify logits.

    logits: [B, k+1, V] — logits[:, i] is the model's next-token
    distribution after consuming candidate i (cur_tok, d_1..d_i).
    drafts: [B, k]. Returns (emitted [B, k+1], acc [B]): emitted[:, :acc]
    are the accepted drafts, emitted[:, acc] is the model's own token at
    the first mismatch (or a bonus token when all k drafts survive) —
    every iteration emits acc+1 tokens.

    Greedy: accept while d_i equals the argmax chain — bit-exact vs the
    monolithic loop. Sampled: deterministic-proposal rejection sampling
    (Leviathan et al. 2023 / Chen et al. 2023): accept d_i with
    probability p̃_i(d_i) under the SAME filtered distribution
    `_sample_token` uses; on rejection, sample from p̃_i with d_i removed,
    renormalized — the marginal at every position is exactly p̃_i
    (P(tok=d) = p̃(d); P(tok=v≠d) = (1-p̃(d))·p̃(v)/(1-p̃(d)) = p̃(v)).
    """
    B, K1, V = logits.shape
    k = K1 - 1
    if greedy:
        t_hat = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, k+1]
        ok = drafts == t_hat[:, :k]
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        final = jnp.take_along_axis(t_hat, acc[:, None], axis=1)[:, 0]
    else:
        filtered = filtered_logits_full(
            logits, temperature, top_p, top_k, approx_top_k
        )                                                       # [B, k+1, V]
        logp = jax.nn.log_softmax(filtered, axis=-1)
        p_draft = jnp.exp(jnp.take_along_axis(
            logp[:, :k], drafts[..., None], axis=-1
        )[..., 0])                                              # [B, k]
        key_u, key_r = jax.random.split(step_key)
        u = jax.random.uniform(key_u, (B, k))
        ok = u < p_draft
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        # residual/bonus draws for EVERY position, selected at `acc`:
        # positions i<k sample p̃ with the draft removed (the rejection
        # residual — the drafter is a point mass, so max(p̃-q, 0) ∝ p̃ minus
        # the drafted token); position k samples p̃ unmasked (bonus token)
        masked = filtered.at[
            jnp.arange(B)[:, None], jnp.arange(k)[None, :], drafts
        ].set(-jnp.inf)
        res = jax.random.categorical(key_r, masked, axis=-1).astype(jnp.int32)
        final = jnp.take_along_axis(res, acc[:, None], axis=1)[:, 0]
    arange = jnp.arange(K1)[None, :]
    drafts_ext = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    emitted = jnp.where(
        arange < acc[:, None], drafts_ext,
        jnp.where(arange == acc[:, None], final[:, None], 0),
    )
    return emitted, acc


@jax.named_scope("verify")
def _draft_fn(prompt_rep, state, *, Tp, spec_k, spec_ngram, pad_token_id,
              seed_rep=None, seed_len=None):
    """Draft step over the carry: build the prompt+output buffer and
    propose spec_k tokens per row.

    `seed_rep` ([R, W] int32, right-aligned) / `seed_len` ([R] int32),
    when given, prepend a per-row SEED window to the lookup buffer — the
    radix-matched cached continuation the decode session installs at
    admission (sampler/paged/session.py), which fixes the drafter's
    cold-start blind spot: without it the n-gram match only sees the
    row's OWN prompt+output, so prefix-heavy corpora draft nothing until
    the row has repeated itself. Rows with `seed_len == 0` keep exactly
    the unseeded valid range (shifted by the constant W, which the match
    positions are relative to, so proposals are unchanged). The pad gap
    between a row's seed tail and its first real prompt token stays
    INSIDE the valid range — a window straddling it only matches when
    the row's recent output equals pad runs, which live rows never emit,
    and a junk draft merely gets rejected by verification (greedy output
    is draft-independent either way)."""
    out, done, n_gen, prompt_len = state[1], state[5], state[7], state[8]
    if seed_rep is None:
        buf = jnp.concatenate([prompt_rep, out], axis=1)
        drafts, _ = ngram_propose(
            buf, Tp + n_gen, Tp - prompt_len, k=spec_k, m=spec_ngram,
            pad_token_id=pad_token_id,
        )
        return drafts
    W = seed_rep.shape[1]
    buf = jnp.concatenate([seed_rep, prompt_rep, out], axis=1)
    valid_start = jnp.where(seed_len > 0, W - seed_len,
                            W + Tp - prompt_len)
    drafts, _ = ngram_propose(
        buf, W + Tp + n_gen, valid_start, k=spec_k, m=spec_ngram,
        pad_token_id=pad_token_id,
    )
    return drafts


@jax.named_scope("verify")
def _verify_fn(params, config, state, drafts, *, Tp, max_tokens,
               eos_token_id, pad_token_id, spec_k, temperature, top_p,
               greedy, lora_scale, top_k, capture_logprobs, approx_top_k,
               page_size=0, page_table=None):
    """Verify + accept + per-row bookkeeping: one forward over the k+1
    candidates, the acceptance rule, then masked multi-token output
    writes, per-row cache-length/key_mask advance, EOS/budget termination,
    and the acceptance counters.

    `page_size` > 0 runs the verify forward against the paged cache; a
    `page_table` of None rebuilds the dense identity table from the pool
    shape (the monolithic paged path), while the continuous-batching
    scheduler passes its live recycled table."""
    (it, out, lp_out, caches, key_mask, done, cur_tok, n_gen, prompt_len,
     key, n_drafted, n_accepted, n_emitted, n_rowsteps, row_acc) = state
    B = cur_tok.shape[0]
    K1 = spec_k + 1
    arange = jnp.arange(K1)[None, :]

    paged_kw = {}
    if page_size > 0:
        if page_table is None:
            page_table = full_table(B, caches[0].shape[1] // B)
        paged_kw = dict(page_table=page_table, page_size=page_size)
    tokens = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
    positions = (prompt_len + n_gen - 1)[:, None] + jnp.arange(K1)[None, :]
    fill = Tp + n_gen - 1                                   # [B] slot of cur_tok
    logits, caches = decode_verify(
        params, config, tokens, positions, fill, key_mask, caches,
        lora_scale=lora_scale, **paged_kw,
    )
    emitted, acc = accept_candidates(
        logits, drafts, jax.random.fold_in(key, it),
        temperature=temperature, top_p=top_p, top_k=top_k, greedy=greedy,
        approx_top_k=approx_top_k,
    )

    # emission length: acc drafts + 1 model token, truncated at the first
    # EOS inside the accepted block and at the response budget; 0 for rows
    # that were already done (their verify output is discarded wholesale)
    n_emit = acc + 1
    is_eos = (emitted == eos_token_id) & (arange < n_emit[:, None])
    any_eos = jnp.any(is_eos, axis=1)
    n_emit = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) + 1, n_emit)
    n_emit = jnp.minimum(n_emit, max_tokens - n_gen)
    n_emit = jnp.where(done, 0, n_emit)

    # masked multi-token output writes: row b writes emitted[b, :n_emit[b]]
    # at out[b, n_gen[b]:]; invalid lanes get an out-of-range index and drop
    wpos = jnp.where(arange < n_emit[:, None], n_gen[:, None] + arange,
                     max_tokens)
    rows = jnp.arange(B)[:, None]
    out = out.at[rows, wpos].set(emitted, mode="drop")
    if capture_logprobs:
        # full-distribution logprobs straight from the verify logits — the
        # same quantity (and guard_temperature floor) _token_logprob gives
        # the monolithic loop
        scaled = logits.astype(jnp.float32) / guard_temperature(temperature)
        lse = jax.nn.logsumexp(scaled, axis=-1)
        lp_mat = jnp.take_along_axis(
            scaled, emitted[..., None], axis=-1
        )[..., 0] - lse
        lp_out = lp_out.at[rows, wpos].set(lp_mat, mode="drop")

    # advance: the last emitted token becomes cur_tok; its KV slot stays
    # outside key_mask (the invariant — it is (re)written next iteration)
    last = jnp.take_along_axis(
        emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
    )[:, 0]
    cur_tok = jnp.where(n_emit > 0, last, cur_tok)
    slot = jnp.arange(key_mask.shape[1])[None, :]
    key_mask = key_mask | (
        (slot >= fill[:, None]) & (slot < (fill + n_emit)[:, None])
    )
    n_gen = n_gen + n_emit
    eos_emitted = jnp.any(
        (emitted == eos_token_id) & (arange < n_emit[:, None]), axis=1
    )
    live = ~done
    done = done | eos_emitted | (n_gen >= max_tokens)

    liv = live.astype(jnp.int32)
    acc_row = liv * jnp.minimum(acc, jnp.maximum(n_emit - 1, 0))  # [B]
    n_drafted = n_drafted + jnp.sum(liv) * spec_k
    n_accepted = n_accepted + jnp.sum(acc_row)
    n_emitted = n_emitted + jnp.sum(n_emit)
    n_rowsteps = n_rowsteps + jnp.sum(liv)     # live (row, verify-step) pairs
    row_acc = row_acc + acc_row  # per-row accepted drafts (lineage ledger)
    return (it + 1, out, lp_out, caches, key_mask, done, cur_tok, n_gen,
            prompt_len, key, n_drafted, n_accepted, n_emitted, n_rowsteps,
            row_acc)


def _spec_state(base_state):
    """Prefill carry → speculative carry: the scalar step counter becomes a
    per-row generated-token count (accepted rows advance at different
    rates) plus the acceptance counters."""
    (_step, out, lp_out, caches, key_mask, done, tok, prompt_len,
     key) = base_state
    B = tok.shape[0]
    zero = jnp.int32(0)
    return (jnp.int32(1), out, lp_out, caches, key_mask, done, tok,
            jnp.ones((B,), jnp.int32), prompt_len, key, zero, zero, zero,
            zero, jnp.zeros((B,), jnp.int32))


@partial(jax.jit, static_argnames=_GEN_STATIC)
def generate_tokens_spec(
    params: dict,
    config: ModelConfig,
    prompt_ids: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    key: jax.Array,
    *,
    max_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    spec_k: int,
    spec_ngram: int = 3,
    temperature: float = 1.0,
    top_p: float = 0.95,
    greedy: bool = False,
    lora_scale: float = 1.0,
    top_k: int = 64,
    capture_logprobs: bool = False,
    approx_top_k: bool = True,
    prompt_fanout: int = 1,
    page_size: int = 0,
):
    """Jitted speculative decode loop (the async default). Same output
    contract as `generate_tokens` plus a stats tuple:
    (tokens [B*fanout, max_tokens], logprobs f32, (verify_steps, drafted,
    accepted, emitted, row_steps, accepted_rows) — int32 device scalars
    plus a per-row [B*fanout] accepted-draft vector). `verify_steps` is
    the decode dispatch count — the number the monolithic loop pays once
    per token; `row_steps` counts live (row, verify-step) pairs, so
    emitted/row_steps is mean tokens per row per dispatch (monolithic:
    identically 1)."""
    config.require(f"speculative decode (spec_k={spec_k})",
                   "speculative decode")
    Tp = prompt_ids.shape[1]
    base = _prefill_state(
        params, config, prompt_ids, prompt_mask, key,
        max_tokens=max_tokens, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, temperature=temperature, top_p=top_p,
        greedy=greedy, lora_scale=lora_scale, top_k=top_k,
        capture_logprobs=capture_logprobs, approx_top_k=approx_top_k,
        prompt_fanout=prompt_fanout, cache_extra=spec_k,
        page_size=page_size,
    )
    prompt_rep = (
        jnp.repeat(prompt_ids, prompt_fanout, axis=0)
        if prompt_fanout > 1 else prompt_ids
    )
    state = _spec_state(base)
    statics = dict(
        Tp=Tp, max_tokens=max_tokens, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, spec_k=spec_k, temperature=temperature,
        top_p=top_p, greedy=greedy, lora_scale=lora_scale, top_k=top_k,
        capture_logprobs=capture_logprobs, approx_top_k=approx_top_k,
        page_size=page_size,
    )

    def cond(s):
        # every live row emits >= 1 token/iteration, so max_tokens bounds
        # the trip count; the done check is the real exit
        return (s[0] <= max_tokens) & ~jnp.all(s[5])

    def body(s):
        drafts = _draft_fn(prompt_rep, s, Tp=Tp, spec_k=spec_k,
                           spec_ngram=spec_ngram, pad_token_id=pad_token_id)
        return _verify_fn(params, config, s, drafts, **statics)

    state = jax.lax.while_loop(cond, body, state)
    stats = (state[0] - 1, state[10], state[11], state[12], state[13],
             state[14])
    return state[1], state[2], stats


_draft_jit = partial(
    jax.jit, static_argnames=("Tp", "spec_k", "spec_ngram", "pad_token_id")
)(_draft_fn)
_verify_jit = partial(jax.jit, static_argnames=_VERIFY_STATIC)(_verify_fn)
_prefill_jit = partial(
    jax.jit,
    static_argnames=("config", "max_tokens", "eos_token_id", "pad_token_id",
                     "temperature", "top_p", "greedy", "lora_scale", "top_k",
                     "capture_logprobs", "approx_top_k", "prompt_fanout",
                     "cache_extra", "page_size"),
)(_prefill_state)


def _generate_spec_instrumented(params, config, prompt_ids, prompt_mask, key,
                                tracer, **kw):
    """Host-driven variant for telemetry runs: the same jitted draft/verify
    pieces, one iteration per host step, with real per-iteration
    "rollout.draft"/"rollout.verify" spans on the "rollout" track
    (docs/OBSERVABILITY.md). Costs one device sync per verify step — the
    observability trade; the
    default (tracer off) path is the fully-async jitted while_loop."""
    Tp = prompt_ids.shape[1]
    spec_k, spec_ngram = kw["spec_k"], kw["spec_ngram"]
    prompt_fanout = kw["prompt_fanout"]
    pre_kw = {k: v for k, v in kw.items()
              if k not in ("spec_k", "spec_ngram", "prompt_fanout")}
    # page_size rides through pre_kw (prefill allocates the pool and gates
    # the cache_extra slack) and ver_kw (table-routed verify writes)
    base = _prefill_jit(params, config, prompt_ids, prompt_mask, key,
                        prompt_fanout=prompt_fanout, cache_extra=spec_k,
                        **pre_kw)
    prompt_rep = (
        jnp.repeat(prompt_ids, prompt_fanout, axis=0)
        if prompt_fanout > 1 else prompt_ids
    )
    state = _spec_state(base)
    ver_kw = {k: v for k, v in kw.items()
              if k not in ("spec_ngram", "prompt_fanout")}
    max_tokens = kw["max_tokens"]
    for it in range(max_tokens):
        if bool(np.asarray(state[5]).all()):
            break
        with tracer.span("rollout.draft", track="rollout", iteration=it):
            drafts = _draft_jit(prompt_rep, state, Tp=Tp, spec_k=spec_k,
                                spec_ngram=spec_ngram,
                                pad_token_id=kw["pad_token_id"])
            jax.block_until_ready(drafts)
        with tracer.span("rollout.verify", track="rollout", iteration=it):
            state = _verify_jit(params, config, state, drafts, Tp=Tp,
                                **ver_kw)
            jax.block_until_ready(state[5])
    stats = (state[0] - 1, state[10], state[11], state[12], state[13],
             state[14])
    return state[1], state[2], stats


def generate_spec(
    params: dict,
    config: ModelConfig,
    prompt_ids: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    key: jax.Array,
    *,
    max_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    spec_k: int,
    spec_ngram: int = 3,
    temperature: float = 1.0,
    top_p: float = 0.95,
    greedy: bool = False,
    lora_scale: float = 1.0,
    top_k: int = 64,
    capture_logprobs: bool = False,
    approx_top_k: bool = True,
    prompt_fanout: int = 1,
    spec_stats_out: list | None = None,
    tracer=None,
    page_size: int = 0,
):
    """`generate`-contract entry for the speculative path: returns tokens
    (or (tokens, logprobs) with capture), appending the stats dict to
    `spec_stats_out` when provided. Stats stay device scalars until the
    caller fetches them — reading after the tokens are ready costs no
    extra sync."""
    kw = dict(
        max_tokens=max_tokens, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, spec_k=spec_k, spec_ngram=spec_ngram,
        temperature=temperature, top_p=top_p, greedy=greedy,
        lora_scale=lora_scale, top_k=top_k,
        capture_logprobs=capture_logprobs, approx_top_k=approx_top_k,
        prompt_fanout=prompt_fanout, page_size=page_size,
    )
    if tracer is not None and getattr(tracer, "enabled", False):
        out, lp, stats = _generate_spec_instrumented(
            params, config, prompt_ids, prompt_mask, key, tracer, **kw
        )
    else:
        out, lp, stats = generate_tokens_spec(
            params, config, prompt_ids, prompt_mask, key, **kw
        )
    if spec_stats_out is not None:
        steps, drafted, accepted, emitted, row_steps, accepted_rows = stats
        spec_stats_out.append({
            "verify_steps": steps, "drafted": drafted,
            "accepted": accepted, "emitted": emitted,
            "row_steps": row_steps,
            # per-row accepted-draft counts [B]: the lineage ledger's
            # generation events attribute draft acceptance per sample
            "accepted_rows": accepted_rows,
        })
    return (out, lp) if capture_logprobs else out
