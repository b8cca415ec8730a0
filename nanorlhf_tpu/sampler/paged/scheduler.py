"""Continuous batching over the paged KV cache: queued prompts, resident rows.

The monolithic rollout loop sizes its batch to the WHOLE prompt set and runs
until the slowest row finishes — a long-tail length distribution leaves most
rows idle (emitting pads) for most of the loop. This module recycles a
finished row's pages to a queued prompt, the way continuous-batching
servers (vLLM-style) do, but host-driven and offline-batch shaped.

Since the decode-session refactor the mechanism lives in
`sampler/paged/session.py` (`DecodeSession` owns the carry, the page
table, admission/step/release, the speculative draft seeds, and the
chunked-prefill backlog); this module is the QUEUE-POLICY driver: it maps
queue indices onto resident rows, collects finished rows' outputs in
queue order, and assembles the paged/spec stats surfaces. The serving
engine (serving/engine.py) drives the same session with open-loop
traffic — one scheduler code path for rollout and gateway streams,
test-pinned.

Scheduling shape (unchanged by the refactor):

  * `decode_rows` rows are RESIDENT in a fixed-shape jitted decode loop over
    a page pool sized for exactly those rows
    (`decode_rows * ceil((Tp + max_tokens)/page_size)` pages).
  * The loop runs in chunks of `sync_every` iterations. At each host sync,
    rows that emitted EOS are flushed to the output buffer, their pages
    handed back (free list or radix refcount), and the next queued prompt
    is admitted mid-loop. Batch shape, pool shape, and compiled code never
    change.
  * Decode iterations are counted (the carry's global counter only advances
    while at least one row is live), which is what the long-tail test
    (tests/test_paged_cache.py) compares against the fixed-batch schedule.

Feature composition (the session's reason to exist — see
`sampler.compose_check` for the full matrix):

  * `spec_k > 0` runs draft+verify chunks over the speculative carry.
  * `prefix_cache` routes admissions through the radix tree; COMPOSES
    with spec decode — the drafter seeds its lookup window from the
    cached continuation of the matched prefix, so overlapping corpora
    accept drafts from the first generated token.
  * `prefill_chunk > 0` splits long cold admissions into KV-only chunk
    forwards interleaved with decode chunks (resident rows keep
    emitting while a long prompt prefills). Chunked-on/off streams are
    bit-identical; the initial non-radix batch stays batched-unchunked
    (there are no resident rows to protect yet).

Determinism: row streams are NOT bit-identical to the monolithic loop. The
per-iteration sampling key is `fold_in(key, it)` over the GLOBAL iteration
counter (rows admitted later see different folds than a monolithic run
would), and admitted rows draw their first token from
`fold_in(key, _ADMIT_BASE + queue_index)`. Greedy streams differ only
through chunk boundaries being invisible (they are: the carry is exact), so
greedy queued output EQUALS greedy monolithic output row-for-row — pinned by
tests/test_paged_cache.py — while sampled streams are merely equal in
distribution.

Safety of the recycled pool: a released row's table resets to the sentinel,
so a still-resident-but-done row's writes DROP at the table-routed scatter
(`core/model._paged_pages`) and its reads clamp to an arbitrary live page —
finite garbage feeding a discarded logit. An admitted row's prefill
overwrites every logical slot it will ever read, so stale page contents from
the previous owner never leak through the masked attention.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from nanorlhf_tpu.sampler.paged.pages import blocks_per_row
# the jitted primitives and the session live in session.py; the names are
# re-exported here because envs/rollout.py's episode driver and older
# callers import them from the scheduler module
from nanorlhf_tpu.sampler.paged.session import (  # noqa: F401
    _ADMIT_BASE,
    _admit_one,
    _admit_sample,
    _alloc_jit,
    _decode_chunk,
    _install_row,
    _prefill_state_jit,
    _release_jit,
    _spec_chunk,
    DecodeSession,
)


def _finalize_segments(bounds: list, total: int) -> list:
    """Close one request's `{policy_version, tok_range}` list.

    `bounds` is the chronological [(version, start_tok), ...] recorded at
    admission and at each swap; each segment ends where the next begins,
    the last at `total` generated tokens. Empty spans (a swap landing
    before the row's first token, or after it finished) are dropped, so
    the survivors exactly tile [0, total) with strictly increasing
    versions."""
    segs = [
        {"policy_version": bounds[i][0],
         "tok_range": [bounds[i][1],
                       bounds[i + 1][1] if i + 1 < len(bounds) else total]}
        for i in range(len(bounds))
        if (bounds[i + 1][1] if i + 1 < len(bounds) else total) > bounds[i][1]
    ]
    if not segs:
        segs = [{"policy_version": bounds[-1][0] if bounds else None,
                 "tok_range": [0, total]}]
    return segs


def generate_tokens_queued(
    params: dict,
    config,
    prompt_ids: jnp.ndarray,    # [Q, Tp] — ALL queued prompts, left-padded
    prompt_mask: jnp.ndarray,   # [Q, Tp]
    key,
    *,
    max_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    page_size: int,
    decode_rows: int,
    spec_k: int = 0,
    spec_ngram: int = 3,
    temperature: float = 1.0,
    top_p: float = 0.95,
    greedy: bool = False,
    lora_scale: float = 1.0,
    top_k: int = 64,
    capture_logprobs: bool = False,
    approx_top_k: bool = True,
    sync_every: int = 8,
    prefill_chunk: int = 0,
    spec_stats_out: list | None = None,
    paged_stats_out: list | None = None,
    latency=None,
    prefix_cache=None,
    weight_refresh=None,
):
    """Host-driven continuous-batching generation: `generate_tokens`
    contract over the whole queue ([Q, max_tokens] int32 in queue order, or
    (tokens, logprobs) with capture), with only `decode_rows` rows resident
    at a time and finished rows' pages recycled to the next queued prompt
    mid-loop. See the module docstring for scheduling/determinism notes.

    `latency` (telemetry.LatencyHub, optional): records TRUE per-request
    TTFT — admission-start → first-token-ready, blocking on the admission
    prefill's sampled token — for the initial batch and every mid-loop
    admission, plus the mean inter-token gap per sync chunk (chunk wall /
    iterations advanced). The extra device syncs happen ONLY when a hub is
    attached; the default path's async chunk pipeline is untouched.

    `prefix_cache` (serving.radix.RadixCache, optional): admissions route
    through the cross-request radix prefix cache instead of the device
    free-stack allocator — a request whose padded prompt prefix is already
    cached installs the matched full pages by refcount inc alone (zero
    prefill FLOPs), COW-splits a mid-page straddler, and prefills only the
    suffix through `suffix_logits`. The cache RESETS at the start of every
    call (cached KV is tied to the params that wrote it — docs/SERVING.md),
    so the win here is intra-call: the n>1 queued fanout and dataset-level
    prompt repeats. Greedy streams stay bit-identical to the uncached path
    (test-pinned); sampled streams are equal in distribution only (cold
    initial rows draw tok0 from the per-queue-index admission fold instead
    of the batched fold_in(key, 0)). COMPOSES with `spec_k > 0`: finished
    rows' generated text extends the radix tree, seeding the drafter of
    later overlapping admissions.

    `prefill_chunk > 0` splits every per-row admission whose real suffix
    exceeds the chunk width into KV-only forwards, one per sync chunk —
    greedy/sampled streams are bit-identical to `prefill_chunk=0` (the
    final chunk samples from the same admission fold).

    `weight_refresh` (optional `() -> (version, tree|None)`, built by
    `orchestrator.weight_store.make_swap_refresh`): in-flight mid-sequence
    weight swaps (docs/ORCHESTRATOR.md §in-flight swaps). Polled once
    pre-loop (a returned tree is the BASE install — not counted as a swap)
    and once per host sync chunk; a newer tree is installed as
    `sess.params` before the next decode chunk — params is a traced
    argument of the jitted chunk fns, so the install never recompiles —
    and every live row gets a segment boundary at its current generated
    length. The paged-stats entry then carries `segments` (queue-order
    per-request `{policy_version, tok_range}` lists that exactly tile
    `[0, n_generated)` with strictly increasing versions),
    `swap_installs`, and `swap_wait_s`. With no mid-rollout publish the
    poll returns None every chunk and the token stream is bit-identical
    to `weight_refresh=None` (the PRNG stream never sees the callback)."""
    config.require("the paged rollout scheduler", "a page pool of one kind")
    Q, Tp = prompt_ids.shape
    R = min(int(decode_rows), Q)
    P = int(page_size)
    T_max = Tp + max_tokens
    nb = blocks_per_row(T_max, P)
    spec = spec_k > 0

    radix = prefix_cache if (prefix_cache is not None
                             and getattr(prefix_cache, "enabled", False)) \
        else None

    sess = DecodeSession(
        params, config, rows=R, prompt_len=Tp, max_tokens=max_tokens,
        page_size=P, eos_token_id=eos_token_id, pad_token_id=pad_token_id,
        key=key, temperature=temperature, top_p=top_p, greedy=greedy,
        top_k=top_k, approx_top_k=approx_top_k,
        capture_logprobs=capture_logprobs, lora_scale=lora_scale,
        spec_k=spec_k, spec_ngram=spec_ngram, prefix_cache=radix,
        prefill_chunk=int(prefill_chunk), sync_every=int(sync_every),
        latency=latency)
    N = sess.num_pages
    stats0 = dict(radix.stats) if radix is not None else None

    prompt_np = np.asarray(prompt_ids)
    pmask_np = np.asarray(prompt_mask)

    # host bookkeeping
    out_all = np.full((Q, max_tokens), pad_token_id, np.int32)
    lp_all = np.zeros((Q, max_tokens), np.float32)
    acc_all = np.zeros((Q,), np.int64)            # spec: accepted drafts/row
    owner = [-1] * R                              # resident row → queue index
    next_q = 0
    recycled = 0
    admissions: list[dict] = []
    util_samples: list[float] = []
    shared_peak = 0

    # in-flight weight swaps: per-queue-index (version, start_tok) bounds
    swaps = weight_refresh is not None
    cur_version = None
    swap_installs = 0
    swap_wait_s = 0.0
    seg_bounds: dict[int, list] = {}
    seg_final: dict[int, list] = {}
    if swaps:
        t0 = time.perf_counter()
        cur_version, fresh = weight_refresh()
        if fresh is not None:
            # base install: a publish raced the dispatch — start the whole
            # stream on the newer tree (single segment, newer version)
            sess.params = fresh
            swap_wait_s += time.perf_counter() - t0

    if radix is not None:
        # initial batch admits row-by-row through the radix path (the
        # same path mid-loop admissions use)
        for r in range(R):
            sess.admit(r, prompt_np[next_q], pmask_np[next_q], next_q)
            owner[r] = next_q
            if swaps:
                seg_bounds[next_q] = [(cur_version, 0)]
            next_q += 1
    else:
        sess.bootstrap(prompt_ids, prompt_mask)
        owner = list(range(R))
        next_q = R
        if swaps:
            for q in range(R):
                seg_bounds[q] = [(cur_version, 0)]

    while True:
        done_h, installed = sess.step()
        it_now = sess.iterations()
        if installed is not None:
            admissions.append({"row": installed[0],
                               "queue_index": owner[installed[0]],
                               "iteration": it_now, "chunked": True})
        if spec:
            row_acc_h = np.asarray(sess.state[14])
            n_gen_h = np.asarray(sess.state[7])

        pending = sess.pending_rows()
        finished = [r for r in range(R)
                    if done_h[r] and owner[r] >= 0 and r not in pending]
        if swaps and finished and not spec:
            # generated-length sync only when a row actually flushes — the
            # no-publish steady state stays free of extra device syncs
            n_gen_h = np.asarray(sess.state[7])
        for r in finished:
            q = owner[r]
            out_all[q] = np.asarray(sess.state[1][r])
            if capture_logprobs:
                lp_all[q] = np.asarray(sess.state[2][r])
            gen = None
            if spec:
                acc_all[q] = int(row_acc_h[r])
                gen = out_all[q][:int(n_gen_h[r])]
            if swaps:
                seg_final[q] = _finalize_segments(
                    seg_bounds.pop(q), int(n_gen_h[r]))
            owner[r] = -1
            # radix: drop the REQUEST's refs; pages the tree still holds
            # survive as cached prefix KV (and, with spec, the generated
            # text extends the tree for the drafter seed)
            recycled += sess.release(r, gen_tokens=gen)
        for r in finished:
            if next_q >= Q:
                continue
            q = next_q
            next_q += 1
            sess.admit(r, prompt_np[q], pmask_np[q], q)
            owner[r] = q
            if swaps:
                seg_bounds[q] = [(cur_version, 0)]
            if not sess.is_pending(r):
                admissions.append({"row": r, "queue_index": q,
                                   "iteration": it_now})
        if swaps:
            # THE host sync point (ISSUE 20): poll the store once per
            # chunk; a newer tree is installed before the next decode
            # chunk and every live row's segment list gets a boundary at
            # its current generated length
            t0 = time.perf_counter()
            version, fresh = weight_refresh()
            if fresh is not None:
                # post-churn snapshot: rows admitted THIS sync read 0 here,
                # so their boundary collapses to a dropped empty segment
                n_gen_now = np.asarray(sess.state[7])
                for r in range(R):
                    if owner[r] >= 0:
                        seg_bounds[owner[r]].append(
                            (version, int(n_gen_now[r])))
                sess.params = fresh
                cur_version = version
                swap_installs += 1
                swap_wait_s += time.perf_counter() - t0
        # pool occupancy AFTER this sync's churn: allocated / total pages
        util_samples.append(sess.utilization())
        shared_peak = max(shared_peak, sess.shared_pages())
        if next_q >= Q and all(o < 0 for o in owner) \
                and not sess.has_pending():
            break

    n_iter = sess.iterations()
    if paged_stats_out is not None:
        entry = {
            "page_utilization": float(np.mean(util_samples)),
            "pages_recycled": recycled,
            "admitted_midloop": len(admissions),
            "decode_iterations": n_iter,
            "rows": R,
            "num_pages": N,
            "page_size": P,
            "admissions": admissions,
            "prefill_token_dispatch": sess.dispatch_tokens,
            "dispatch_events": sess.dispatch_events(),
            "chunked_admissions": sess.chunked_admissions,
            "prefill_backlog_peak": sess.backlog_peak,
            # end-of-call session snapshot for /statusz "session" (row
            # feature flags, pending-prefill backlog, dispatch counters)
            "session": sess.status(),
        }
        if swaps:
            entry.update({
                "segments": [seg_final[q] for q in range(Q)],
                "swap_installs": swap_installs,
                "swap_wait_s": swap_wait_s,
            })
        if radix is not None:
            lookup_tok = radix.stats["lookup_tokens"] - stats0["lookup_tokens"]
            entry.update({
                "prefix_hit_tokens": sess.hit_tokens,
                "prefix_hit_frac": (sess.hit_tokens / lookup_tok
                                    if lookup_tok else 0.0),
                "cow_splits": radix.stats["cow_splits"] - stats0["cow_splits"],
                "evicted_pages": (radix.stats["evicted_pages"]
                                  - stats0["evicted_pages"]),
                "shared_pages": shared_peak,
            })
        paged_stats_out.append(entry)
    if spec and spec_stats_out is not None:
        state = sess.state
        spec_stats_out.append({
            "verify_steps": n_iter,
            "drafted": state[10], "accepted": state[11],
            "emitted": state[12], "row_steps": state[13],
            "accepted_rows": jnp.asarray(acc_all.astype(np.int32)),
        })
    toks = jnp.asarray(out_all)
    if capture_logprobs:
        return toks, jnp.asarray(lp_all)
    return toks
