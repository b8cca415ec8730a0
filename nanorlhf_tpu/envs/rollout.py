"""Multi-turn rollout driver over the paged continuous-batching scheduler.

One episode interleaves model turns and environment observations in a
single token stream:

    prompt | turn-1 tokens .. EOS | obs tokens | turn-2 tokens .. EOS | ...

Turn 1 is the EXISTING ``generate()`` call, bit-for-bit — the whole batch
prefills and decodes exactly as the non-env pipeline does, so a
single-turn environment never enters this module's continuation loop and
the degenerate-case parity pin holds by construction.

Continuation turns reuse the queued paged scheduler's admission path (PR
10) nearly verbatim: when a row hits EOS-of-turn its pages are released
back to the pool IMMEDIATELY (``release_row``) and the turn text goes to
the environment on a tool thread; when the observation arrives, the
extended context — real prompt + prior turns + observation tokens,
left-padded to the fixed episode width — is admitted into a recycled row
through the same single-row bucketed prefill (``_admit_one``) and
carry re-init (``_install_row``) mid-loop admissions use, writing KV
through the row's freshly allocated block table. A slow tool therefore
never holds pages: the rows it would have occupied decode OTHER episodes'
turns, and ``env/stalled_rows`` counts the scheduler waits where decode
sat fully idle on tool results.

Loss masking: observation tokens are environment actions, not policy
actions. The driver records every span and returns a per-token
``loss_mask`` (False on observation tokens) plus per-turn reward/end
positions; the trainer threads the mask through ``algos/losses.py``'s
existing ``mask`` argument and attributes advantages per turn
(``algos.advantages.per_turn_terminal_rewards``). docs/ENVIRONMENTS.md
walks the full lifecycle.

Fault sites: ``env.hang`` (default ``action=delay`` — the tool call
stalls ``delay=S`` seconds first, driving the page-release-while-stalled
path) and ``env.crash`` (default raise — absorbed here as an error-text
observation, never a dead rollout) fire per tool dispatch with
``worker=<episode index>`` scoping (docs/RESILIENCE.md).
"""

from __future__ import annotations

import time
from collections import deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait

import jax
import jax.numpy as jnp
import numpy as np

from nanorlhf_tpu.core.model import init_paged_kv_cache
from nanorlhf_tpu.envs.base import Environment
from nanorlhf_tpu.sampler import generate
from nanorlhf_tpu.sampler.paged.pages import blocks_per_row, init_page_state
from nanorlhf_tpu.sampler.paged.scheduler import _finalize_segments
from nanorlhf_tpu.sampler.paged.session import (
    _ADMIT_BASE,
    _admit_one,
    _alloc_jit,
    _decode_chunk,
    _install_row,
    _release_jit,
    _sans_pool,
    _with_pool,
)


def _trim_turn(tok_row: np.ndarray, eos_token_id: int,
               pad_token_id: int) -> np.ndarray:
    """Real tokens of one generated turn: through the first EOS inclusive,
    else through the last non-pad token (budget exhausted without EOS)."""
    eos = np.nonzero(tok_row == eos_token_id)[0]
    if eos.size:
        return tok_row[: int(eos[0]) + 1]
    real = np.nonzero(tok_row != pad_token_id)[0]
    return tok_row[: int(real[-1]) + 1] if real.size else tok_row[:0]


def run_env_episodes(
    params: dict,
    config,
    prompt_ids: jnp.ndarray,   # [B, Tp] left-padded prompts
    prompt_mask: jnp.ndarray,  # [B, Tp]
    key: jax.Array,
    sampling,                  # SamplingParams with max_tokens == turn_tokens
    env: Environment,
    *,
    eos_token_id: int,
    pad_token_id: int,
    tokenizer,
    max_turns: int,
    turn_tokens: int,
    obs_budget: int,
    response_length: int,
    page_size: int,
    decode_rows: int,
    lora_scale: float = 1.0,
    sync_every: int = 8,
    faults=None,
    tool_threads: int = 4,
    weight_refresh=None,
) -> dict:
    """Run one vectorized batch of multi-turn episodes; returns a payload:

    - ``tokens``      [B*n, response_length] int32 — packed episode streams
    - ``loss_mask``   [B*n, response_length] bool — False on observation tokens
    - ``scores``      [B*n] float32 — per-episode total reward (Σ turns)
    - ``turn_rewards``/``turn_ends`` [B*n, max_turns] — per-turn credit inputs
      (``turn_ends`` = final model-token position of each turn, −1 absent)
    - ``turns``       per-turn lineage records (row, turn, tool_wall_s,
      obs_range, reward, tok_range)
    - ``stats``       the ``env/*`` metric rows (docs/METRICS.md)
    - ``pages_recycled``/``admissions`` — continuation-loop paged evidence

    ``weight_refresh`` (optional ``() -> (version, tree|None)``): in-flight
    mid-sequence weight swaps (docs/ORCHESTRATOR.md §in-flight swaps). The
    callback is polled once per main-loop iteration — the driver's host
    sync point, which also covers every multi-turn re-admission — and a
    newer tree replaces ``params`` for all subsequent prefills and decode
    chunks. The payload then ALSO carries ``segments`` (per-episode
    ``[{policy_version, tok_range}]`` in packed response-token coordinates,
    the same space as ``turns``' tok_range), ``swap_installs`` and
    ``swap_wait_s``. With no mid-rollout publish the poll returns
    ``(version, None)`` every time and the episode streams are bit-identical
    to ``weight_refresh=None``.
    """
    if sampling.max_tokens != turn_tokens:
        raise ValueError(
            f"sampling.max_tokens={sampling.max_tokens} != "
            f"turn_tokens={turn_tokens}: the per-turn generation budget and "
            "the first-turn sampling params must agree")
    if sampling.capture_logprobs:
        raise ValueError(
            "multi-turn episodes recompute logprobs in the scoring pass "
            "(observation tokens have no sampler logprob) — capture off")
    B, Tp = prompt_ids.shape
    n = sampling.n
    rows_total = B * n
    P = int(page_size)

    # ---- in-flight weight swaps (docs/ORCHESTRATOR.md §in-flight swaps) -
    swaps = weight_refresh is not None
    swap_installs = 0
    swap_wait_s = 0.0
    cur_version = None
    seg_bounds: list[list] = [[] for _ in range(rows_total)]
    if swaps:
        # base install: the serial refresh's first call (have_version=None)
        # returns the store's latest outright — installed before turn 1 and
        # NOT counted as a swap
        t0_sw = time.perf_counter()
        cur_version, fresh = weight_refresh()
        swap_wait_s += time.perf_counter() - t0_sw
        if fresh is not None:
            params = fresh
        seg_bounds = [[(cur_version, 0)] for _ in range(rows_total)]

    # ---- turn 1: the existing pipeline, bit-for-bit --------------------
    first = generate(
        params, config, prompt_ids, prompt_mask, key, sampling,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id,
        lora_scale=lora_scale,
    )
    toks1 = np.asarray(first)

    prompt_np = np.asarray(prompt_ids)
    pmask_np = np.asarray(prompt_mask).astype(bool)
    prompt_rows = np.repeat(prompt_np, n, axis=0)
    pmask_rows = np.repeat(pmask_np, n, axis=0)
    pad_tok = getattr(tokenizer, "pad_token", "")
    prompt_texts = [
        t.replace(pad_tok, "") if pad_tok else t
        for t in tokenizer.batch_decode(prompt_np)
    ]
    prompt_texts = [t for t in prompt_texts for _ in range(n)]

    state = env.reset(prompt_texts)

    # per-episode records
    spans: list[list[tuple[str, np.ndarray]]] = [[] for _ in range(rows_total)]
    turn_walls: list[list[float]] = [[] for _ in range(rows_total)]
    turn_rewards = np.zeros((rows_total, max_turns), np.float32)
    cur_turn = [0] * rows_total
    completed = 0
    tool_wall_total = 0.0
    obs_tokens_total = 0
    stall_events = 0
    decode_chunks = 0
    overlap_chunks = 0
    pages_recycled = 0
    admissions = 0
    tool_errors = 0

    pool = ThreadPoolExecutor(max_workers=max(1, tool_threads))
    futures: dict = {}
    pending: deque = deque()

    def tool_step(ep: int, text: str):
        """One env.step on a tool thread; injected faults are absorbed —
        env.crash becomes an error observation, env.hang a pre-step stall."""
        t0 = time.perf_counter()
        try:
            if faults is not None:
                act = faults.fire("env.hang", worker=ep)
                if act and act.startswith("delay:"):
                    time.sleep(float(act.split(":", 1)[1]))
                faults.fire("env.crash", worker=ep)
            obs, rew, done = env.step(state, [text], indices=[ep])
            return obs[0], float(rew[0]), bool(done[0]), \
                time.perf_counter() - t0, False
        except Exception as e:  # noqa: BLE001 — a crashed tool, injected or
            # organic, must not kill the rollout: the error text IS the
            # observation and the episode keeps its remaining turns
            state.transcripts[ep] += text
            state.turn[ep] += 1
            obs = f" ```output {type(e).__name__}: {e} ``` "
            state.transcripts[ep] += obs
            return obs, 0.0, False, time.perf_counter() - t0, True

    def finish_turn(ep: int, toks: np.ndarray):
        """EOS-of-turn: record the model span and hand the turn text to the
        environment on a tool thread (the row's pages are already released
        by the caller — a slow tool holds no pool capacity)."""
        spans[ep].append(("model", toks))
        cur_turn[ep] += 1
        fut = pool.submit(tool_step, ep, tokenizer.decode(toks))
        futures[fut] = ep

    # ---- continuation machinery (lazy: only when a turn-2 exists) ------
    Tp_ep = Tp + (max_turns - 1) * (turn_tokens + obs_budget)
    T_max = Tp_ep + turn_tokens
    R = max(1, min(int(decode_rows) if decode_rows > 0 else rows_total,
                   rows_total))
    nb = blocks_per_row(T_max, P)
    N = R * nb
    carry = None
    pstate = None
    owner = [-1] * R
    statics = dict(
        Tp=Tp_ep, max_tokens=turn_tokens, page_size=P,
        sync_every=int(sync_every), eos_token_id=eos_token_id,
        pad_token_id=pad_token_id, temperature=sampling.temperature,
        top_p=sampling.top_p, greedy=sampling.greedy,
        lora_scale=lora_scale, top_k=sampling.top_k,
        capture_logprobs=False, approx_top_k=sampling.approx_top_k,
    )

    def ensure_carry():
        nonlocal carry, pstate
        if carry is not None:
            return
        caches0 = init_paged_kv_cache(config, N, P,
                                      params["embed_tokens"].dtype)
        # radix-pattern empty carry: every row starts done; admissions
        # install episodes through the same path mid-loop recycling uses
        carry = (jnp.int32(1),
                 jnp.full((R, turn_tokens), pad_token_id, jnp.int32),
                 jnp.zeros((R, turn_tokens), jnp.float32),
                 caches0,
                 jnp.zeros((R, T_max), bool),
                 jnp.ones((R,), bool),
                 jnp.zeros((R,), jnp.int32),
                 jnp.ones((R,), jnp.int32),
                 jnp.zeros((R,), jnp.int32),
                 # the chunk donates the carry whole, and `key` is folded
                 # for every admission: the carry gets its own
                 jnp.copy(key))
        pstate = init_page_state(N, R, nb)

    def harvest(fut):
        """A tool result landed: either the episode ended (terminal reward)
        or its extended context joins the admission queue."""
        nonlocal completed, tool_wall_total, obs_tokens_total, tool_errors
        ep = futures.pop(fut)
        obs_text, reward, done, wall, err = fut.result()
        tool_errors += int(err)
        t = cur_turn[ep]
        turn_walls[ep].append(wall)
        tool_wall_total += wall
        turn_rewards[ep, t - 1] = reward
        if done or t >= max_turns:
            completed += 1
            return
        obs_toks = np.asarray(tokenizer.encode(obs_text),
                              np.int32)[:obs_budget]
        spans[ep].append(("obs", obs_toks))
        obs_tokens_total += int(obs_toks.size)
        ctx = np.concatenate(
            [prompt_rows[ep][pmask_rows[ep]]]
            + [s for _, s in spans[ep]]
        ).astype(np.int32)
        assert ctx.size <= Tp_ep, (ctx.size, Tp_ep)
        ids = np.full(Tp_ep, pad_token_id, np.int32)
        ids[Tp_ep - ctx.size:] = ctx
        mask = np.zeros(Tp_ep, bool)
        mask[Tp_ep - ctx.size:] = True
        pending.append((ep, ids, mask))

    # turn 1 goes through the same EOS-of-turn path as every later turn
    for ep in range(rows_total):
        finish_turn(ep, _trim_turn(toks1[ep], eos_token_id, pad_token_id))

    while completed < rows_total:
        for fut in [f for f in list(futures) if f.done()]:
            harvest(fut)
        if swaps:
            # host sync point: one non-blocking poll per loop iteration —
            # BEFORE admissions, so a re-admitted turn prefills under the
            # freshly installed params and its tokens sit past the boundary
            t0_sw = time.perf_counter()
            version, fresh = weight_refresh()
            if fresh is not None:
                # swap boundary in packed response coordinates: committed
                # span tokens + the live row's generated-so-far count. The
                # EOS trim at finish_turn can only shorten a live span, so
                # finalize clamps bounds monotonically into [0, total].
                n_gen_h = (np.asarray(carry[7])
                           if carry is not None else None)
                committed = [sum(int(t.size) for _, t in spans[ep])
                             for ep in range(rows_total)]
                if n_gen_h is not None:
                    for r in range(R):
                        if owner[r] >= 0:
                            committed[owner[r]] += int(n_gen_h[r])
                for ep in range(rows_total):
                    seg_bounds[ep].append((version, committed[ep]))
                params = fresh
                cur_version = version
                swap_installs += 1
            swap_wait_s += time.perf_counter() - t0_sw
        while pending and any(o < 0 for o in owner):
            r = next(i for i, o in enumerate(owner) if o < 0)
            ep, ids, mask = pending.popleft()
            ensure_carry()
            pstate, ok = _alloc_jit(pstate, r, nb)
            assert bool(ok), "env pool underflow: uniform page budget rows"
            # deterministic per-(episode, turn) admission key — completion
            # ORDER must not steer the PRNG stream
            admit_key = jax.random.fold_in(
                key, _ADMIT_BASE + ep * max_turns + cur_turn[ep])
            caches, t0, l0, pl = _admit_one(
                params, config, jnp.asarray(ids)[None, :],
                jnp.asarray(mask)[None, :], carry[3], pstate.table[r],
                admit_key, page_size=P, T_max=T_max,
                temperature=sampling.temperature, top_p=sampling.top_p,
                greedy=sampling.greedy, top_k=sampling.top_k,
                approx_top_k=sampling.approx_top_k, lora_scale=lora_scale,
            )
            # _admit_one consumed carry[3] (session.py: the pool is
            # donated); the carry goes on with the pool it returned
            carry = _with_pool(_install_row(
                _sans_pool(carry), r, t0, l0, jnp.asarray(mask), pl,
                Tp=Tp_ep, max_tokens=turn_tokens,
                eos_token_id=eos_token_id, pad_token_id=pad_token_id,
                spec=False,
            ), caches)
            owner[r] = ep
            admissions += 1
        if any(o >= 0 for o in owner):
            decode_chunks += 1
            if futures:
                overlap_chunks += 1
            carry = _decode_chunk(params, config, carry, pstate.table,
                                  **statics)
            done_h = np.asarray(carry[5])
            for r in range(R):
                if owner[r] >= 0 and done_h[r]:
                    ep = owner[r]
                    n_gen = int(np.asarray(carry[7])[r])
                    toks = np.asarray(carry[1])[r][:n_gen]
                    owner[r] = -1
                    # pages back to the pool BEFORE the tool runs: a
                    # stalled episode holds zero KV capacity
                    pstate, m = _release_jit(pstate, r)
                    pages_recycled += int(m)
                    finish_turn(
                        ep, _trim_turn(toks, eos_token_id, pad_token_id))
        elif futures:
            # decode fully idle on tool results — the stalled-rows signal
            stall_events += 1
            wait(list(futures), timeout=0.2, return_when=FIRST_COMPLETED)
        elif not pending:
            break
    pool.shutdown(wait=False)

    # ---- pack episodes + per-token loss mask ---------------------------
    out = np.full((rows_total, response_length), pad_token_id, np.int32)
    loss_mask = np.ones((rows_total, response_length), bool)
    turn_ends = np.full((rows_total, max_turns), -1, np.int64)
    turns_records: list[dict] = []
    totals = [0] * rows_total
    for ep in range(rows_total):
        cur, t_idx = 0, 0
        rec_by_turn: list[dict] = []
        for kind, toks in spans[ep]:
            L = min(int(toks.size), response_length - cur)
            out[ep, cur:cur + L] = toks[:L]
            if kind == "model":
                turn_ends[ep, t_idx] = cur + L - 1
                rec_by_turn.append({
                    "row": ep, "turn": t_idx + 1,
                    "tok_range": [cur, cur + L],
                    "reward": round(float(turn_rewards[ep, t_idx]), 6),
                    "tool_wall_s": round(
                        turn_walls[ep][t_idx], 6
                    ) if t_idx < len(turn_walls[ep]) else None,
                    "obs_range": None, "obs_tokens": 0,
                })
                t_idx += 1
            else:
                loss_mask[ep, cur:cur + L] = False
                rec_by_turn[-1]["obs_range"] = [cur, cur + L]
                rec_by_turn[-1]["obs_tokens"] = L
            cur += L
        turns_records.extend(rec_by_turn)
        totals[ep] = cur

    segments_out = None
    if swaps:
        segments_out = []
        for ep in range(rows_total):
            total = totals[ep]
            bounds, hi = [], 0
            for v, pos in seg_bounds[ep]:
                # running max + clip: the EOS trim and the response_length
                # clip can only shorten spans, so bounds stay a monotone
                # tiling of [0, total]; empty trailing segments (a swap
                # after this episode finished) are dropped by finalize
                hi = max(hi, min(int(pos), total))
                bounds.append((v, hi))
            segments_out.append(_finalize_segments(bounds, total))

    turns_count = np.asarray(cur_turn, np.float32)
    stats = {
        "env/turns_per_episode": float(turns_count.mean()),
        "env/tool_wall_s": round(tool_wall_total, 6),
        "env/obs_tokens": float(obs_tokens_total),
        "env/stalled_rows": float(stall_events),
        "env/tool_stall_overlap": (
            overlap_chunks / decode_chunks if decode_chunks else 0.0),
        "env/tool_errors": float(tool_errors),
    }
    payload = {
        "tokens": out,
        "loss_mask": loss_mask,
        "scores": turn_rewards.sum(axis=1).astype(np.float32),
        "turn_rewards": turn_rewards,
        "turn_ends": turn_ends,
        "turns": turns_records,
        "stats": stats,
        "pages_recycled": pages_recycled,
        "admissions": admissions,
    }
    if swaps:
        # conditional keys (the loss_mask pattern): present only when the
        # in-flight swap path is live, so swaps-off payloads are unchanged
        payload["segments"] = segments_out
        payload["swap_installs"] = swap_installs
        payload["swap_wait_s"] = round(swap_wait_s, 6)
    return payload
