"""Jitted autoregressive sampler — the TPU-native replacement for vLLM rollouts.

The reference hands weights to vLLM through a disk round-trip every update
(`/root/reference/GRPO/grpo_trainer.py:122-166`): model→CPU, (merge LoRA),
save_pretrained, rebuild an `LLM` engine, generate, delete engine, model→GPU.
On TPU the policy params already live sharded in HBM, so generation is just
another jitted function over the same tree — the entire handoff disappears.

Output contract is identical to `vllm_generate` (`grpo_trainer.py:152-160`):
`[B*N, max_tokens]` int32, N consecutive samples per prompt (prompt-major),
each row = generated tokens including the terminating EOS, right-padded with
`pad_token_id`. Capability parity with `SamplingParams(temperature, top_p=0.95,
n=N, seed=randint)` (`grpo_trainer.py:127`) — the per-call changing seed
becomes a per-call PRNG key. Greedy mode covers the ReMax baseline rollout
(`ReMax/remax_trainer.py:166-185`) and the r1 accuracy eval
(`examples/r1-v0/grpo_r1.py:291-318`).

Decode is a `lax.while_loop` over single-token steps with a shared KV cache;
it exits early once every sequence has emitted EOS (rollouts are offline-batch,
so big batches keep the MXU busy; early exit claws back the static-shape tax).
The loop lays its own cache out (`_loop_page_size`): in pages of 128 slots
under the identity table wherever a step then reads each row's own slots in
place (a TPU without a mesh, `core/model.decode_loop_page_size`), and
elsewhere contiguous, where past one 128-slot block it is a few such loops in
a row inside the one jit, each reading the cache up to a static extent that
no row's write has passed (`_read_loops`): XLA's attention masks, it does
not bound.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from nanorlhf_tpu.core.config import ModelConfig
from nanorlhf_tpu.core.model import (
    decode_loop_page_size, decode_read_extents, decode_step, init_kv_cache,
    init_paged_kv_cache, prefill, use_paged_decode_kernel,
)
from nanorlhf_tpu.ops.decode_attention import (
    paged_item_counts, paged_pages_per_item,
)
from nanorlhf_tpu.ops.masking import guard_temperature
from nanorlhf_tpu.ops.top_select import take_at, top_k_select
from nanorlhf_tpu.sampler.paged.pages import full_table

# rows a call scores from which `_nucleus_candidates` takes its k best of
# `approx_max_k`'s candidates by selection (`ops/top_select.py`); below, XLA's
# own aggregation, a sort of every candidate, is the cheaper
# (tools/bench_sample_pick.py; PERF.md PR 60 has the table)
_PICK_ROWS = 8


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 0.95
    n: int = 1
    max_tokens: int = 256
    greedy: bool = False
    # top-k pre-trim for nucleus sampling: the per-step full-vocab sort (the
    # round-1 decode hot spot at 152k vocab) becomes one lax.top_k + a
    # k-sized categorical. Exact nucleus sampling whenever the 0.95-nucleus
    # fits in the top-k — true for trained models at production temperatures;
    # NOT true for random-init/high-entropy policies, where this truncates
    # the tail to the k best tokens (the combined top-k/top-p semantics vLLM
    # exposes as `SamplingParams(top_k=...)`). Set top_k=0 to disable the
    # pre-trim and recover the exact full-vocab nucleus at full-sort cost.
    # Ignored when top_p >= 1.0 (that path is always exact full-vocab).
    top_k: int = 64
    # capture the FULL-distribution logprob of each sampled token during
    # decode (one extra logsumexp per step — the logits are already there).
    # `generate` then returns (tokens, logprobs), letting the trainer skip
    # the policy half of the scoring pass (ROADMAP #5b). The captured values
    # equal `logprobs_from_logits(logits, tokens, temperature)` up to
    # decode-vs-scoring numerics; the trainer logs the residual ratio drift.
    capture_logprobs: bool = False
    # use jax.lax.approx_max_k for the top-k pre-trim: XLA lowers exact
    # lax.top_k to a FULL VOCAB SORT on TPU, which at LLM vocabularies can
    # dominate the decode step; ApproxTopK is the hardware-native O(V) path
    # (exact on CPU): a partial reduce of the vocabulary to C candidates
    # (9,600 of 151,936), of which the k best are kept in descending order.
    # XLA's own aggregation SORTS all C for that; from `_PICK_ROWS` rows on
    # `_nucleus_candidates` takes the unaggregated candidates and picks the
    # k by selection instead (`ops/top_select.py`: the same set and values,
    # equal values the lower candidate first). The candidate SET is
    # approximate (recall 0.99 per candidate, NOT rank-restricted): a missed
    # in-nucleus token cannot be sampled that step, and the exclusive-cumsum
    # keep rule then undercounts, letting the boundary widen slightly past
    # top_p. The sampling distribution therefore deviates from the exact
    # truncated nucleus — acceptable for RL rollouts, where the ratio math
    # scores the SAMPLED token's full-distribution logprob (exact either
    # way; the truncated-vs-full mismatch is inherent to nucleus sampling
    # and present in the reference's vLLM path too). Set False for the exact
    # candidate set (full-sort cost on TPU).
    approx_top_k: bool = True
    # >0 switches the KV cache to the PAGED layout (sampler/paged/,
    # docs/PAGED_CACHE.md): K/V live in a global pool of page_size-token
    # pages addressed through a per-row block table instead of a per-row
    # [T_max] slab. On its own (decode_rows == 0) this is a pure re-layout —
    # greedy token streams are bit-identical to the contiguous cache on the
    # CPU mesh (test-pinned) — and it composes with spec_k (paged verify
    # writes) and kv_cache_quant="int8" (paged scale pools). Pick
    # page_size >= 128 on real TPUs (lane-tile alignment for the paged
    # kernels' int8 scale blocks); CPU tests run any size via interpret
    # mode. 0 = the loop's own choice: the queued and speculative loops keep
    # contiguous slabs, the monolithic one-jit loop lays its private cache
    # out whichever way its decode read is cheaper (`_loop_page_size`: the
    # same tokens either way).
    page_size: int = 0
    # page_size > 0 only: >0 enables CONTINUOUS BATCHING — the decode loop
    # runs `decode_rows` resident rows over a page pool sized for exactly
    # that many rows, and when a row EOSes mid-loop its pages are released
    # and the next queued prompt is prefilled into the freed pool
    # (sampler/paged/scheduler.py): the long-tail win, and it works with
    # spec_k.
    # Host-driven (one sync per chunk of decode iterations); row streams
    # are NOT bit-identical to the monolithic loop (admission re-keys the
    # PRNG per row). n > 1 fanout falls back to repeated-prompt prefill on
    # this path. 0 (or >= the total row count) = monolithic paged loop.
    decode_rows: int = 0
    # >0 enables draft-free speculative decode (sampler/speculative.py): a
    # jitted n-gram/prompt-lookup drafter proposes spec_k tokens per row
    # from the row's own prompt+output buffer, and ONE `decode_verify`
    # forward scores all k+1 candidates against the cache — amortizing the
    # dominant per-step weight/cache HBM stream over every accepted token
    # (docs/DECODE_ANALYSIS.md). Greedy rows accept the matched prefix
    # bit-exactly vs this monolithic loop; sampled rows use Leviathan/Chen
    # rejection sampling against the SAME filtered distribution
    # `_sample_token` draws from, so the output distribution is provably
    # unchanged (different PRNG stream, though — spec draws accept/residual
    # variates instead of one categorical per step). capture_logprobs
    # reuses the verify logits, so accepted tokens still carry
    # full-distribution logprobs. 0 = this loop, bit-for-bit untouched.
    # Composes with page_size > 0 (paged verify writes) including the
    # continuous-batching decode_rows path.
    spec_k: int = 0
    # n-gram context length the drafter matches on (spec_k > 0 only):
    # smaller = more matches (higher draft rate, lower precision), larger =
    # fewer but better drafts. 3 suits R1-style self-repetitive math
    # rollouts (restated problem text, \boxed{} scaffolding).
    spec_ngram: int = 3
    # queued paged path only (page_size > 0 with decode_rows > 0): >0 splits
    # any admission whose real prompt suffix exceeds this many tokens into
    # KV-only chunk forwards interleaved with the resident rows' decode
    # chunks (sampler/paged/session.py) — a long cold prompt no longer
    # stalls every live stream for its full prefill, bounding the p95
    # inter-token gap (tests/test_session.py). GREEDY streams are
    # bit-identical to prefill_chunk=0 (the final chunk runs the same
    # bucketed suffix forward and samples from the same admission PRNG
    # fold, test-pinned); sampled streams are equal in distribution only
    # — a chunk-delayed row decodes at later global fold_in(key, it)
    # iterations than it would unchunked. 0 = whole-suffix admission.
    prefill_chunk: int = 0
    # n>1: prefill each prompt ONCE and fan the prompt KV out to its N
    # samples inside the jit, instead of repeating the prompt rows before
    # prefill — ÷N prefill FLOPs and prompt activation memory, the
    # TPU-static analogue of vLLM's prefix sharing for `n=4` requests
    # (`/root/reference/GRPO/grpo_trainer.py:127`). Token streams are
    # bit-identical to the repeat path on the CPU test mesh (test-pinned:
    # the fanned-out first logits and caches match the repeated rows', and
    # decode runs on the same [B*N] shapes either way); on real silicon the
    # fan-out can change XLA reduction/layout choices enough to flip
    # near-tie sampling decisions, so streams there are distributionally
    # equivalent rather than bit-identical (ADVICE r5). Quantify on a given
    # chip with `tools/ablate_decode.py` (the n4_shared vs n4_repeat
    # configs measure both the speedup and any stream divergence).
    shared_prompt_prefill: bool = True


def compose_check(sampling: SamplingParams, *,
                  prefix_cache: bool = False, config=None) -> None:
    """THE decode-feature composition gate. Every entry point that assembles
    decode features (generate() below, the trainer's config validation)
    routes through this one function.

    Which mechanism takes which kind of model is one table,
    `core/config.MECHANISMS`: a call here for each mechanism the
    `SamplingParams` and the config select (`ModelConfig.require`, which
    raises NotImplementedError by the model's name). Option against option,
    the features compose by default since the decode session
    (sampler/paged/session.py; docs/PAGED_CACHE.md has the matrix). What
    remains illegal raises ValueError:

      * prefix_cache without continuous batching (page_size > 0 AND
        decode_rows > 0) — the radix cache lives at the ADMISSION point;
        the monolithic one-jit paths prefill the whole batch at trace
        time and have no admission to cache across.
      * prefill_chunk > 0 without continuous batching — chunked prefill
        exists to protect RESIDENT rows' inter-token cadence during a
        long admission; the monolithic paths have neither residents nor
        admissions.

    Per-row serving constraints (spec requires static greedy, no logprob
    capture) are enforced by DecodeSession's constructor — they depend on
    the per_row flag the engine sets, not on SamplingParams."""
    if config is not None:
        config.require("the rollout sampler", "a rollout")
        if sampling.spec_k > 0:
            config.require(f"speculative decode (spec_k={sampling.spec_k})",
                           "speculative decode")
        if sampling.page_size > 0:
            config.require(
                f"the paged rollout paths (page_size={sampling.page_size})",
                "a page pool of one kind")
        if config.kv_cache_quant == "int8":
            config.require("kv_cache_quant='int8'")
        if config.spmd_mesh is not None:
            config.require("a rollout under a mesh")
    queued_capable = sampling.page_size > 0 and sampling.decode_rows > 0
    if prefix_cache and not queued_capable:
        raise ValueError(
            "prefix_cache requires continuous batching: set page_size > 0 "
            "and decode_rows > 0 (rollout_page_size / rollout_decode_rows "
            "on the trainer) — the monolithic paths have no admission "
            "point to cache across."
        )
    if sampling.prefill_chunk > 0 and not queued_capable:
        raise ValueError(
            "prefill_chunk > 0 requires continuous batching: set "
            "page_size > 0 and decode_rows > 0 — chunked prefill "
            "interleaves a long admission with RESIDENT rows' decode "
            "chunks, and the monolithic paths have neither residents nor "
            "mid-loop admissions to protect."
        )


def top_p_filter(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Mask logits outside the top-p nucleus (smallest set with cum prob ≥ p).

    Sort-based exact variant — the reference/oracle for the sort-free
    bisection filter below and for the fused top-k path in the decode loop.
    """
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # keep tokens whose *exclusive* cumulative prob is < top_p (first always kept)
    keep_sorted = (cum - sorted_probs) < top_p
    # threshold = smallest kept logit
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits >= threshold, logits, -jnp.inf)


def top_p_filter_bisect(logits: jnp.ndarray, top_p: float,
                        iters: int = 26) -> jnp.ndarray:
    """Exact nucleus filter WITHOUT the full-vocab sort.

    XLA lowers `jnp.sort` over an LLM vocabulary to a slow multi-pass sort
    on TPU (the r2-measured decode hot spot), but the nucleus mask is a
    pure THRESHOLD set: sorted-descending, keep-while-exclusive-cum < p is
    exactly {i : p_i >= tau} where tau is the smallest probability in the
    minimal prefix reaching mass p (the sort-based filter keeps threshold
    ties the same way, `logits >= threshold`). The keep-set mass is a
    decreasing step function of tau, so tau comes from bisection over
    (0, p_max]: `iters` reduction passes over [B, V] (VPU-friendly
    elementwise+sum, no data movement) instead of a sort. 26 iterations
    leave an ABSOLUTE bracket of ~p_max·2^-26 ≈ 1.5e-8: near the top of
    the distribution that is inside f32 tie noise the sort cannot order
    stably either, but a token whose probability sits within ~1.5e-8
    BELOW the true cutoff can still be kept — for small-threshold tails
    at LLM vocab sizes this admits negligible extra tail mass rather
    than being bit-exact. Used by `_sample_token` for the `top_k=0`
    nucleus path (the r1-zero launcher default).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    p_max = jnp.max(probs, axis=-1, keepdims=True)

    def step(carry, _):
        lo, hi = carry                       # mass(lo) >= top_p > mass(hi)
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(probs >= mid, probs, 0.0), axis=-1,
                       keepdims=True)
        ok = mass >= top_p
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)), None

    # lo=0 keeps everything (mass 1 >= p); hi just above p_max keeps nothing
    (lo, _), _ = jax.lax.scan(
        step, (jnp.zeros_like(p_max), p_max * (1 + 1e-6)), None, length=iters
    )
    return jnp.where(probs >= lo, logits, -jnp.inf)


def sample_picks(shape, top_k: int, approx_top_k: bool) -> bool:
    """Whether `_nucleus_candidates` over logits of `shape` `[..., V]` takes
    its candidates by selection: where `approx_max_k` cuts the vocabulary to
    candidates at all and the call scores `_PICK_ROWS` rows or more. Static
    in the call's shape, as `sala._PICK_QUERIES` is."""
    V = shape[-1]
    rows = int(np.prod(shape[:-1]))
    return bool(approx_top_k and 0 < top_k < V and rows >= _PICK_ROWS)


def _nucleus_candidates(logits, top_p, top_k, approx_top_k):
    """(top_logits, top_idx, keep): the top-k candidate set plus the
    exclusive-cum nucleus keep rule over TRUE probabilities (full-vocab
    logsumexp normalization, so the keep set matches the exact filter).
    The single copy of the candidate-selection semantics, shared by
    `_sample_token`'s k-space categorical and the speculative verifier's
    full-vocab rejection filter (`filtered_logits_full`) — the two paths
    must agree on the keep set or spec decode would change the sampling
    distribution. `logits` arrive already temperature-scaled."""
    k = min(top_k, logits.shape[-1])
    if approx_top_k and k < logits.shape[-1]:
        # hardware-native approximate top-k (exact lax.top_k is a full-vocab
        # sort on TPU): a partial reduce to C candidates with their
        # vocabulary indices, and the k best of those in descending order
        if sample_picks(logits.shape, top_k, approx_top_k):
            # ... picked exactly out of the candidates as they are
            cand, cand_idx = jax.lax.approx_max_k(
                logits, k, recall_target=0.99, aggregate_to_topk=False)
            # (row-major, both: from 128 rows on the compiler lays the pick's
            # arrays out rows-minor and, unpinned, relaid the LOGITS to match)
            row_major = Layout(major_to_minor=tuple(range(cand.ndim)))
            cand, cand_idx = (with_layout_constraint(c, row_major)
                              for c in (cand, cand_idx))
            top_logits, pos = top_k_select(cand, k)
            top_idx = take_at(cand_idx, pos)
        else:
            # ... by XLA's aggregation (`aggregate_to_topk`, the default),
            # which sorts all C
            top_logits, top_idx = jax.lax.approx_max_k(
                logits, k, recall_target=0.99
            )
    else:
        top_logits, top_idx = jax.lax.top_k(logits, k)  # descending
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    probs = jnp.exp(top_logits - lse)                   # true (unrenormalized) probs
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p                        # exclusive-cum; first always kept
    return top_logits, top_idx, keep


def _categorical_rows(key, kept, draw=None):
    """`jax.random.categorical(key, kept)` over the last axis; with
    `draw = (idx [s], N)`, `kept` [s, K] holds the rows `idx` of an `[N, K]`
    batch and each draws what it would have drawn there: the Gumbel noise is
    drawn at the batch's shape from `key` and gathered, so a row's token
    does not depend on which other rows were scored beside it."""
    if draw is None:
        return jax.random.categorical(key, kept, axis=-1)
    idx, n = draw
    noise = jax.random.gumbel(key, (n, kept.shape[-1]), kept.dtype)
    return jnp.argmax(noise[idx] + kept, axis=-1)


def filtered_logits_full(logits, temperature, top_p, top_k, approx_top_k):
    """Full-vocab filtered/temperature-scaled logits whose softmax is
    EXACTLY the distribution `_sample_token` draws from (same candidate
    set + keep rule via `_nucleus_candidates`; -inf outside the keep set).
    The speculative verifier's rejection sampler needs the distribution as
    a dense vocab vector (accept prob of an arbitrary drafted token +
    residual sampling with that token removed), which the k-space
    categorical never materializes. Supports any leading batch shape."""
    scaled = logits.astype(jnp.float32) / guard_temperature(temperature)
    if top_p >= 1.0:
        return scaled
    if top_k <= 0:
        return top_p_filter_bisect(scaled, top_p)
    lead = scaled.shape[:-1]
    V = scaled.shape[-1]
    flat = scaled.reshape(-1, V)
    top_logits, top_idx, keep = _nucleus_candidates(
        flat, top_p, top_k, approx_top_k
    )
    kept = jnp.where(keep, top_logits, -jnp.inf)
    rows = jnp.arange(flat.shape[0])[:, None]
    full = jnp.full_like(flat, -jnp.inf).at[rows, top_idx].set(kept)
    return full.reshape(*lead, V)


@jax.named_scope("sample")
def _sample_token(key, logits, temperature, top_p, greedy, top_k=64,
                  approx_top_k=True):
    """Sample one token per row.

    `top_p >= 1.0` (no nucleus requested) stays an EXACT full-vocab
    categorical — truncating to top-k there would silently bias the sampling
    distribution away from the full-vocab logprobs the RL ratio math scores
    against. The nucleus path never sorts or draws Gumbel noise over the
    full vocabulary: candidates come from `lax.top_k`, the nucleus rule is
    applied over their TRUE probabilities (normalized by a full-vocab
    logsumexp, so the keep set matches the exact filter), and the
    categorical runs in k-space with indices mapped back through the top-k
    gather.
    """
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / guard_temperature(temperature)
    if top_p >= 1.0 or top_k <= 0:
        if top_p < 1.0:
            # exact full-vocab nucleus, sort-free (bisection threshold)
            logits = top_p_filter_bisect(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    top_logits, top_idx, keep = _nucleus_candidates(
        logits, top_p, top_k, approx_top_k
    )
    top_logits = jnp.where(keep, top_logits, -jnp.inf)
    choice = jax.random.categorical(key, top_logits, axis=-1)
    return jnp.take_along_axis(
        top_idx, choice[..., None], axis=-1
    )[..., 0].astype(jnp.int32)


@jax.named_scope("logprob")
def _token_logprob(logits, tok, temperature):
    """Full-distribution logprob of `tok` at the sampling temperature — the
    same quantity the scoring pass computes (`logprobs_from_logits`), through
    the SAME `guard_temperature` floor, so captured behavior logprobs and
    scoring logprobs agree bit-for-bit at small temperatures."""
    scaled = logits.astype(jnp.float32) / guard_temperature(temperature)
    lse = jax.nn.logsumexp(scaled, axis=-1)
    return jnp.take_along_axis(scaled, tok[..., None], axis=-1)[..., 0] - lse


@partial(
    jax.jit,
    static_argnames=("config", "max_tokens", "eos_token_id", "pad_token_id",
                     "temperature", "top_p", "greedy", "lora_scale", "top_k",
                     "capture_logprobs", "approx_top_k", "prompt_fanout",
                     "page_size"),
)
def generate_tokens(
    params: dict,
    config: ModelConfig,
    prompt_ids: jnp.ndarray,    # [B, Tp] left-padded
    prompt_mask: jnp.ndarray,   # [B, Tp] bool
    key: jax.Array,
    *,
    max_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    temperature: float = 1.0,
    top_p: float = 0.95,
    greedy: bool = False,
    lora_scale: float = 1.0,
    top_k: int = 64,
    capture_logprobs: bool = False,
    approx_top_k: bool = True,
    prompt_fanout: int = 1,
    page_size: int = 0,
) -> jnp.ndarray:
    """Core jitted loop: one sample per row. Returns [B*fanout, max_tokens]
    int32, or (tokens, logprobs f32) with capture_logprobs. `prompt_fanout`
    N prefills the [B] prompts once and decodes N samples per prompt
    (prompt-major rows), sharing the prompt KV. `page_size` > 0 runs the
    same loop over the paged KV layout (dense identity block table — no
    recycling here; see sampler/paged/scheduler.py for that); 0 leaves the
    layout to the loop (`_loop_page_size`)."""
    config.require("the one-jit rollout (generate_tokens)", "a rollout")
    Tp = prompt_ids.shape[1]
    page_size = _loop_page_size(config, page_size)
    state = _prefill_state(
        params, config, prompt_ids, prompt_mask, key,
        max_tokens=max_tokens, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, temperature=temperature, top_p=top_p,
        greedy=greedy, lora_scale=lora_scale, top_k=top_k,
        capture_logprobs=capture_logprobs, approx_top_k=approx_top_k,
        prompt_fanout=prompt_fanout, page_size=page_size,
    )

    # One loop an extent of the XLA cache read (one in all for a paged or a
    # one-block cache: the program as it was). Step `s` writes slot
    # Tp + s - 1, every row's at once, so a loop may keep the steps whose
    # slot lies under its extent; the condition is otherwise the same, and
    # a loop that ends on `done` leaves the later ones no step to take.
    for extent, stop in _read_loops(config, Tp, max_tokens, page_size):
        def cond(state, stop=stop):
            return (state[0] < stop) & ~jnp.all(state[5])

        def body(state, extent=extent):
            return _decode_body(
                params, config, state, Tp=Tp, max_tokens=max_tokens,
                eos_token_id=eos_token_id, pad_token_id=pad_token_id,
                temperature=temperature, top_p=top_p, greedy=greedy,
                lora_scale=lora_scale, top_k=top_k,
                capture_logprobs=capture_logprobs, approx_top_k=approx_top_k,
                page_size=page_size, extent=extent,
            )

        state = jax.lax.while_loop(cond, body, state)
    _, out, lp_out, _, _, _, _, _, _ = state
    return (out, lp_out) if capture_logprobs else out


def _loop_page_size(config, page_size: int) -> int:
    """The page size of the monolithic one-jit loop's cache: its caller's,
    or where the caller names none (0) the loop's own choice,
    `core/model.decode_loop_page_size`: pages where a decode step reads them
    in place, 0 = the contiguous cache. The one place the layout is decided:
    the loop, its extents (`_read_loops`) and what is reported of it
    (`attn_read_frac`, `kv_in_place`, `_monolithic_paged_stats`) all ask
    here."""
    return page_size if page_size > 0 else decode_loop_page_size(config)


def _queued(sampling: SamplingParams, rows: int) -> bool:
    """Whether `generate` hands a call of `rows` rows in all to the rollout
    scheduler: fewer resident rows than rows, over recycled pages."""
    return sampling.page_size > 0 and 0 < sampling.decode_rows < rows


def _monolithic(sampling: SamplingParams, rows: int) -> bool:
    """Whether `generate` runs a call of `rows` rows in all through the
    monolithic loop (`generate_tokens`), not the queued or the speculative
    one."""
    return not (_queued(sampling, rows) or sampling.spec_k > 0)


def kv_in_place(config, sampling: SamplingParams, rows: int) -> int:
    """1 where the monolithic loop of this `generate` call keeps its cache
    in pages and reads each row's own in place, else 0: the trainer's static
    `rollout/kv_in_place`, as `serving/kv_write_live_rows` is the
    session's."""
    return int(_monolithic(sampling, rows)
               and _loop_page_size(config, sampling.page_size) > 0
               and use_paged_decode_kernel(config))


def sample_pick(config, sampling: SamplingParams, rows: int) -> int:
    """1 where a decode step of this `generate` call of `rows` rows in all
    takes its sampler's candidates by selection (`sample_picks` at the rows
    the step scores: the resident ones of the queued loop, every candidate
    position of the speculative one), else 0: the trainer's static
    `rollout/sample_pick`."""
    if sampling.greedy or sampling.top_p >= 1.0:
        return 0
    if _queued(sampling, rows):
        rows = sampling.decode_rows
    return int(sample_picks((rows * (sampling.spec_k + 1), config.vocab_size),
                            sampling.top_k, sampling.approx_top_k))


def _read_loops(config, Tp, max_tokens, page_size=0):
    """`[(extent, stop)]` of the monolithic loop: the extents of
    `decode_read_extents` over its contiguous cache, each with the first
    step it leaves to the next, the one whose write slot `Tp + step - 1` is
    the first outside it. The last step, `max_tokens - 1`, writes slot
    `T_max - 2` (the last sampled token is never fed back). One loop over
    the whole cache (to `decode_step` the same as no extent) where there is
    one extent only, and over a paged cache (`_loop_page_size`), whose read
    goes by the table."""
    T_max = Tp + max_tokens
    extents = (T_max,) if _loop_page_size(config, page_size) > 0 else (
        decode_read_extents(config, Tp, T_max - 2, T_max))
    loops = [(e, e - Tp + 1) for e in extents[:-1]] + [(T_max, max_tokens)]
    # no slot that holds a key is skipped: each loop's last write slot is
    # inside its extent
    assert all(Tp + stop - 2 < e for e, stop in loops)
    return loops


def _decode_steps(sampling, responses, eos_token_id: int) -> int:
    """Decode steps the monolithic loop of one `generate` call ran, from its
    [rows, max_tokens] result on the HOST: it ran until its longest row
    ended, one step a token after the prefill's."""
    ends = responses == eos_token_id
    return int(np.where(ends.any(axis=1), ends.argmax(axis=1) + 1,
                        responses.shape[1]).max()) - 1


def _in_place_pages(config, sampling, prompt_width: int, steps: int,
                    rows: int, prompt_lens):
    """[rows, steps] pages the in-place read of the monolithic loop copies:
    each row's blocks `[start // P, (filled - 1) // P]` from the end of its
    left pad, `start = prompt_width - prompt_lens[row]`, to the step's slot;
    `prompt_lens` is a real length a PROMPT (each N consecutive rows), and
    every row counts at every step (the loop marks none dead)."""
    P = _loop_page_size(config, sampling.page_size)
    lens = np.asarray(prompt_lens)
    first = (prompt_width - np.repeat(lens, rows // lens.shape[0])) // P
    # step s writes slot Tp + s - 1, the last of its `filled`
    last = (prompt_width + np.arange(steps)) // P
    return last[None, :] - first[:, None] + 1


def attn_read_frac(config, sampling, prompt_width: int, responses,
                   eos_token_id: int, prompt_lens=None) -> float:
    """Share of the cache the decode attention of one `generate` call read
    (the trainer's `rollout/attn_read_frac`), summed over its decode steps.
    Over the contiguous cache: the step's extent over the `T_max` slots.
    Over pages read in place (`kv_in_place`): the pages the kernel copies
    (`_in_place_pages`) over the table's `rows x ceil(T_max / P)`. 1.0
    wherever the loop names no bound: a cache of one block, the queued and
    speculative loops, a paged cache read as the gathered view, and a call
    that took no step. `responses` is the call's [rows, max_tokens] result
    on the HOST: the static layout, the prompts' lengths and the longest
    row's length say it all: no device read."""
    steps = _decode_steps(sampling, responses, eos_token_id)
    rows = responses.shape[0]
    if not _monolithic(sampling, rows) or steps <= 0:
        return 1.0
    T_max = prompt_width + sampling.max_tokens
    P = _loop_page_size(config, sampling.page_size)
    if P > 0:
        if not use_paged_decode_kernel(config):     # the gathered view
            return 1.0
        read = int(_in_place_pages(config, sampling, prompt_width, steps,
                                   rows, prompt_lens).sum())
        return read / (steps * rows * -(-T_max // P))
    read, start = 0, 1
    for extent, stop in _read_loops(config, prompt_width,
                                    sampling.max_tokens):
        read += max(0, min(stop, steps + 1) - start) * extent
        start = stop
    return read / (steps * T_max)


def paged_read_items(config, sampling, prompt_width: int, responses,
                     eos_token_id: int, prompt_lens, cache_dtype):
    """`(items, short items)` of the in-place read's work lists over one
    `generate` call's decode steps, a layer (the trainer's
    `rollout/paged_items`, `rollout/paged_short_items`), or None where the
    loop does not read its pages in place (`kv_in_place` 0) or took no step.
    An item is up to `paged_pages_per_item` pages of one row; a short one
    holds fewer, and costs the kernel's loop what a whole one costs unless
    it shares its step (docs/PAGED_CACHE.md "The read's cost"). Counted on
    the host like `attn_read_frac`; `cache_dtype` is the cache's, the
    parameters'."""
    steps = _decode_steps(sampling, responses, eos_token_id)
    rows = responses.shape[0]
    if not kv_in_place(config, sampling, rows) or steps <= 0:
        return None
    pages = _in_place_pages(config, sampling, prompt_width, steps, rows,
                            prompt_lens)
    C = paged_pages_per_item(jax.ShapeDtypeStruct(
        (1, 1, config.num_key_value_heads,
         _loop_page_size(config, sampling.page_size), config.actual_head_dim),
        cache_dtype))
    return paged_item_counts(pages, C)


@jax.named_scope("prefill")
def _prefill_state(params, config, prompt_ids, prompt_mask, key, *,
                   max_tokens, eos_token_id, pad_token_id, temperature,
                   top_p, greedy, lora_scale, top_k, capture_logprobs,
                   approx_top_k, prompt_fanout=1, cache_extra=0,
                   page_size=0):
    """Prefill + first sampled token → the decode-loop carry state:
    (step, out, lp_out, caches, key_mask, done, cur_tok, prompt_len, key).
    Per-step sampling keys are fold_in(key, step).

    `prompt_fanout` N: the prompts arrive UN-repeated; prefill runs on the
    [B] rows once, then the first logits, prompt KV, and per-row metadata
    fan out ×N (prompt-major, matching `jnp.repeat(..., n, axis=0)` row
    order) before the first token is sampled. Everything downstream —
    including the [B*N]-shaped categorical draw — is then identical to
    prefilling N repeated copies, at 1/N the prefill FLOPs. The interleaved
    repeat is collective-free under a data-sharded batch: each device's row
    block fans out to its own contiguous output block.

    `cache_extra` pads the KV cache/key_mask past Tp + max_tokens — the
    speculative path (spec_k slack) needs room for a full k+1 candidate
    write when a row sits one token short of the budget; 0 (every other
    caller) keeps shapes bit-identical to before. GATED TO THE CONTIGUOUS
    LAYOUT: on the paged path (`page_size` > 0) the slack is forced to 0 —
    a row's page budget ceil(T_max/page_size) already rounds up past the
    logical width, and a verify write past the budget drops at the
    table-routed scatter instead of clobbering a neighbor row, so reserved
    slots buy nothing (the dropped candidates are beyond `max_tokens` and
    are truncated before emission either way — docs/PAGED_CACHE.md walks
    the bound).

    `page_size` > 0 allocates the paged layout instead of contiguous slabs:
    a pool of exactly B*ceil(T_max/page_size) pages with the dense identity
    table (`full_table`) — a pure re-layout of the contiguous cache, no
    recycling, so this state is interchangeable with the contiguous one
    token-for-token."""
    B, Tp = prompt_ids.shape
    if page_size > 0:
        cache_extra = 0
    T_max = Tp + max_tokens + cache_extra
    prompt_mask = prompt_mask.astype(bool)
    dtype = params["embed_tokens"].dtype

    if page_size > 0:
        nb = -(-T_max // page_size)
        caches = init_paged_kv_cache(config, B * nb, page_size, dtype)
        first_logits, caches = prefill(
            params, config, prompt_ids, prompt_mask, caches,
            lora_scale=lora_scale, page_table=full_table(B, nb),
            page_size=page_size, logical_len=T_max,
        )
    else:
        caches = init_kv_cache(config, B, T_max, dtype)
        first_logits, caches = prefill(params, config, prompt_ids, prompt_mask,
                                       caches, lora_scale=lora_scale)

    if prompt_fanout > 1:
        first_logits = jnp.repeat(first_logits, prompt_fanout, axis=0)
        if page_size > 0:
            # pools are stacked [L, B*nb, ...]: fan out whole page GROUPS so
            # row r of the fanned table (identity again) lands on a copy of
            # proto row r // N's pages — the same values the contiguous
            # repeat produces, page-major
            nb = -(-T_max // page_size)
            caches = jax.tree.map(
                lambda c: jnp.repeat(
                    c.reshape(c.shape[0], B, nb, *c.shape[2:]),
                    prompt_fanout, axis=1,
                ).reshape(c.shape[0], B * prompt_fanout * nb, *c.shape[2:]),
                caches,
            )
        else:
            # caches are stacked [L, B, KV, T, d] — batch on axis 1; a conv
            # state or tail [L, K - 1, B, D] (the cache's last group) has it
            # on axis 2, a recurrent state [L, B, H, P, N] on axis 1
            def fan(axis):
                return lambda c: jnp.repeat(c, prompt_fanout, axis=axis)

            if config.state_layers:
                *paged_groups, state_group = caches
                caches = (*jax.tree.map(fan(1), tuple(paged_groups)),
                          tuple(fan(2 if c.ndim == 4 else 1)(c)
                                for c in state_group))
            else:
                caches = jax.tree.map(fan(1), caches)
        prompt_mask = jnp.repeat(prompt_mask, prompt_fanout, axis=0)
        B = B * prompt_fanout

    prompt_len = jnp.sum(prompt_mask, axis=1).astype(jnp.int32)  # real prompt length
    key_mask0 = jnp.zeros((B, T_max), bool).at[:, :Tp].set(prompt_mask)

    out0 = jnp.full((B, max_tokens), pad_token_id, jnp.int32)
    lp0 = jnp.zeros((B, max_tokens), jnp.float32)
    tok0 = _sample_token(jax.random.fold_in(key, 0), first_logits, temperature,
                         top_p, greedy, top_k, approx_top_k)
    out0 = out0.at[:, 0].set(tok0)
    if capture_logprobs:
        lp0 = lp0.at[:, 0].set(_token_logprob(first_logits, tok0, temperature))
    done0 = tok0 == eos_token_id
    return (jnp.int32(1), out0, lp0, caches, key_mask0, done0, tok0,
            prompt_len, key)


@jax.named_scope("decode")
def _decode_body(params, config, state, *, Tp, max_tokens, eos_token_id,
                 pad_token_id, temperature, top_p, greedy, lora_scale, top_k,
                 capture_logprobs, approx_top_k, page_size=0, extent=None):
    """One decode step over the carry state. `page_size` > 0:
    the caches in the carry are paged pools; the dense identity table is a
    shape-derived constant (pool pages // batch rows), so the carry layout
    is unchanged. `extent` (the monolithic loop only): the static bound
    `decode_step` reads a contiguous cache up to."""
    step, out, lp_out, caches, key_mask, done, cur_tok, prompt_len, key = state
    read_kw = {} if extent is None else dict(extent=extent)
    if page_size > 0:
        B = key_mask.shape[0]
        read_kw = dict(page_table=full_table(B, caches[0].shape[1] // B),
                       page_size=page_size, identity_table=True)
    # token t was sampled from logits at position prompt_len + step - 1;
    # its KV lands in cache slot Tp + step - 1
    cache_slot = Tp + step - 1
    key_mask = key_mask.at[:, cache_slot].set(True)  # current slot becomes visible
    position = prompt_len + step - 1
    logits, caches = decode_step(
        params, config, cur_tok, position, cache_slot, key_mask, caches,
        lora_scale=lora_scale, **read_kw,
    )
    tok = _sample_token(jax.random.fold_in(key, step), logits, temperature,
                        top_p, greedy, top_k, approx_top_k)
    tok = jnp.where(done, pad_token_id, tok)
    write = (jnp.arange(max_tokens) == step)[None, :] & ~done[:, None]
    out = jnp.where(write, tok[:, None], out)
    if capture_logprobs:
        lp = _token_logprob(logits, tok, temperature)
        lp_out = jnp.where(write, lp[:, None], lp_out)
    done = done | (tok == eos_token_id)
    return (step + 1, out, lp_out, caches, key_mask, done, tok,
            prompt_len, key)


def generate(
    params: dict,
    config: ModelConfig,
    prompt_ids: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    key: jax.Array,
    sampling: SamplingParams,
    eos_token_id: int,
    pad_token_id: int,
    lora_scale: float = 1.0,
    spec_stats_out: list | None = None,
    tracer=None,
    paged_stats_out: list | None = None,
    latency=None,
    prefix_cache=None,
    weight_refresh=None,
) -> jnp.ndarray:
    """vllm_generate-contract entry: [B*N, max_tokens], N consecutive per
    prompt; (tokens, logprobs) when `sampling.capture_logprobs`.

    `spec_stats_out` (spec_k > 0 only): a caller-provided list the
    speculative path appends its per-call stats dict to (device scalars:
    verify steps, drafted/accepted/emitted token counts) — the trainer's
    rollout/draft_acceptance metrics read it without changing the return
    contract. `tracer` (an enabled
    telemetry.SpanTracer) switches the speculative path to its
    host-driven loop with real per-iteration "rollout.draft"/
    "rollout.verify" spans (one sync per verify step — observability
    mode, not the fully-async default).

    `paged_stats_out` (page_size > 0 only): same pattern for the paged
    cache — a dict with page_utilization / pages_recycled /
    admitted_midloop (+ per-admission records on the continuous-batching
    path) feeding the trainer's rollout/page_* metrics, the /statusz
    `pages` section, and lineage lease events.

    `latency` (an enabled telemetry.LatencyHub): the queued paged path
    records true per-request TTFT and per-sync-chunk inter-token gaps
    into it (hist.py); the monolithic one-jit paths ignore it — their
    dispatch→ready wall is recorded by the orchestrator instead.

    `prefix_cache` (an enabled serving.RadixCache): the queued paged path
    admits rows through the cross-request radix prefix cache — matched
    prompt prefixes install refcount-shared pages with zero prefill FLOPs
    and only the suffix is prefilled (serving/radix.py). The cache resets
    per call (KV is tied to params), so within a rollout the win comes
    from the n>1 fanout and repeated dataset prompts. Ignored by the
    non-queued paths; COMPOSES with spec_k > 0 (the drafter seeds its
    lookup window from the cached continuation — see compose_check for
    the full legality matrix).

    `weight_refresh` (optional `() -> (version, tree|None)`): in-flight
    mid-sequence weight swaps on the QUEUED paged path only — polled at
    every host sync chunk, a newer tree replaces the session params before
    the next decode chunk and the paged-stats entry grows per-request
    `segments` (docs/ORCHESTRATOR.md §in-flight swaps). The monolithic
    one-jit paths have no host sync point to swap at and ignore it (the
    trainer's `rollout_inflight_swaps` validation requires the queued
    path)."""
    compose_check(sampling, prefix_cache=(
        prefix_cache is not None
        and getattr(prefix_cache, "enabled", False)), config=config)
    total_rows = prompt_ids.shape[0] * sampling.n
    queued = _queued(sampling, total_rows)
    fanout = 1
    if sampling.n > 1:
        if sampling.shared_prompt_prefill and not queued:
            # prompts stay [B]; prefill-once-fan-out happens inside the jit
            fanout = sampling.n
        else:
            # queued admission prefills one row at a time — no shared-prefill
            # fan-out there, each logical row becomes its own queue entry
            prompt_ids = jnp.repeat(prompt_ids, sampling.n, axis=0)
            prompt_mask = jnp.repeat(prompt_mask, sampling.n, axis=0)
    if queued:
        from nanorlhf_tpu.sampler.paged.scheduler import generate_tokens_queued

        return generate_tokens_queued(
            params, config, prompt_ids, prompt_mask, key,
            max_tokens=sampling.max_tokens, eos_token_id=eos_token_id,
            pad_token_id=pad_token_id, page_size=sampling.page_size,
            decode_rows=sampling.decode_rows, spec_k=sampling.spec_k,
            spec_ngram=sampling.spec_ngram,
            temperature=sampling.temperature, top_p=sampling.top_p,
            greedy=sampling.greedy, lora_scale=lora_scale,
            top_k=sampling.top_k, capture_logprobs=sampling.capture_logprobs,
            approx_top_k=sampling.approx_top_k,
            prefill_chunk=sampling.prefill_chunk,
            spec_stats_out=spec_stats_out, paged_stats_out=paged_stats_out,
            latency=latency, prefix_cache=prefix_cache,
            weight_refresh=weight_refresh,
        )
    if sampling.spec_k > 0:
        from nanorlhf_tpu.sampler.speculative import generate_spec

        result = generate_spec(
            params, config, prompt_ids, prompt_mask, key,
            max_tokens=sampling.max_tokens, eos_token_id=eos_token_id,
            pad_token_id=pad_token_id, spec_k=sampling.spec_k,
            spec_ngram=sampling.spec_ngram,
            temperature=sampling.temperature, top_p=sampling.top_p,
            greedy=sampling.greedy, lora_scale=lora_scale,
            top_k=sampling.top_k, capture_logprobs=sampling.capture_logprobs,
            approx_top_k=sampling.approx_top_k, prompt_fanout=fanout,
            spec_stats_out=spec_stats_out, tracer=tracer,
            page_size=sampling.page_size,
        )
        _monolithic_paged_stats(result, sampling, prompt_mask, fanout,
                                pad_token_id, paged_stats_out,
                                sampling.page_size)
        return result
    result = generate_tokens(
        params,
        config,
        prompt_ids,
        prompt_mask,
        key,
        max_tokens=sampling.max_tokens,
        eos_token_id=eos_token_id,
        pad_token_id=pad_token_id,
        temperature=sampling.temperature,
        top_p=sampling.top_p,
        greedy=sampling.greedy,
        lora_scale=lora_scale,
        top_k=sampling.top_k,
        capture_logprobs=sampling.capture_logprobs,
        approx_top_k=sampling.approx_top_k,
        prompt_fanout=fanout,
        page_size=sampling.page_size,
    )
    _monolithic_paged_stats(result, sampling, prompt_mask, fanout,
                            pad_token_id, paged_stats_out,
                            _loop_page_size(config, sampling.page_size))
    return result


def _monolithic_paged_stats(result, sampling, prompt_mask, fanout,
                            pad_token_id, paged_stats_out, page_size):
    """Fill `paged_stats_out` for the monolithic (non-queued) paged paths,
    whose cache has pages of `page_size` (the caller's or the loop's own
    choice; 0: a contiguous cache, nothing to say): no recycling, no
    admissions — utilization is just final cache occupancy over the
    fully-provisioned pool. Device scalars only (no sync; the trainer
    materializes them at metrics time like spec_stats)."""
    if paged_stats_out is None or page_size <= 0:
        return
    toks = result[0] if sampling.capture_logprobs else result
    rows, Tp = toks.shape[0], prompt_mask.shape[1]
    P = page_size
    nb = -(-(Tp + sampling.max_tokens) // P)
    used = (jnp.sum(prompt_mask) * fanout
            + jnp.sum(toks != pad_token_id)).astype(jnp.float32)
    paged_stats_out.append({
        "page_utilization": used / jnp.float32(rows * nb * P),
        "pages_recycled": jnp.int32(0),
        "admitted_midloop": jnp.int32(0),
        "decode_iterations": None,
        "rows": rows,
        "num_pages": rows * nb,
        "page_size": P,
        "admissions": [],
    })
