"""The one general traffic generator: a mix is a file of parameters.

jax-free (numpy only), so the load-generator child never touches the chip.
The same seed and parameters give the bit-identical sequence: every segment
draws from its own streams `default_rng([seed, stream, segment])` and no draw
depends on the wall clock.

Copied in idea from `nanorlhf_tpu/loadgen/workload.py` (seeded, jax-free,
open loop, tenants with shared prefixes); what changed: lengths are
log-normal and clipped instead of uniform 4-12, a tenant's user turn has a
length distribution of its own, the schedule covers segments of time with a
fixed amount of work in each (so that runs with different seeds offer the same
load), and token ids span the model's vocabulary.

Two kinds of mix:

- `rl`: a prompt corpus for a closed-loop RL job: `dataset_prompts` prompts
  of `prompt_len_min..prompt_len_max` tokens (uniform), left-padded to
  `prompt_len_max`.
- `serve`: an open-loop request schedule: Poisson arrivals at `rate_rps`
  given their count (or a 2-state burst process), log-normal prompt and
  output lengths, stratified,
  tenants that put a shared system prompt before the user's turn, a greedy
  share, sampling parameters per request.
"""

from __future__ import annotations

import hashlib
import math
import statistics

import numpy as np

# stream ids: one independent family of draws each
S_PROMPTS, S_ARRIVAL, S_TENANT, S_REQUEST, S_BURST, S_TOKENS = 1, 2, 3, 4, 5, 6
FIRST_TOKEN_ID = 3          # 0 pad, 1 eos, 2 unk (the program's toy tokenizer)


def _rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream), int(index)])


def rl_prompts(mix: dict, seed: int, vocab_size: int, pad_id: int = 0) -> np.ndarray:
    """[dataset_prompts, prompt_len_max] int32, left-padded."""
    n, lo, hi = (int(mix["dataset_prompts"]), int(mix["prompt_len_min"]),
                 int(mix["prompt_len_max"]))
    rng = _rng(seed, S_PROMPTS)
    lengths = rng.integers(lo, hi + 1, n)
    tokens = rng.integers(FIRST_TOKEN_ID, vocab_size, (n, hi), dtype=np.int64)
    keep = np.arange(hi)[None, :] >= (hi - lengths)[:, None]
    return np.where(keep, tokens, pad_id).astype(np.int32)


NORMAL = statistics.NormalDist()


def _lognormal_at(u: float, spec: dict) -> int:
    """The `u`-quantile of a log-normal (median, sigma), clipped to min-max."""
    x = spec["median"] * math.exp(spec["sigma"] * NORMAL.inv_cdf(min(max(u, 1e-9), 1 - 1e-9)))
    return int(min(spec["max"], max(spec["min"], round(x))))


def _strata(rng, n: int) -> np.ndarray:
    """n quantiles, one from each of n equal strata of (0, 1), in random
    order: the sample's totals barely move with the seed, its order does."""
    return (rng.permutation(n) + rng.random(n)) / n


def arrival_offsets(mix: dict, seed: int, start: float, length: float,
                    segment: int = 0, rate_rps: float | None = None) -> np.ndarray:
    """Arrival offsets of one segment [start, start + length): exactly
    round(rate x length) of them, placed as a Poisson process places its
    points GIVEN their count (sorted uniforms through the inverse of the
    cumulative intensity). `poisson`: constant intensity. `bursty`: a
    two-state intensity, `burst_factor` times the calm rate for `burst_frac`
    of the time, holding times exponential (`mean_burst_s` in the burst)."""
    rate = float(mix["rate_rps"] if rate_rps is None else rate_rps)
    n = int(round(rate * length))
    rng = _rng(seed, S_ARRIVAL, segment)
    u = np.sort(rng.random(n))
    if mix.get("arrival", "poisson") == "poisson":
        return start + u * length
    factor, frac = float(mix["burst_factor"]), float(mix["burst_frac"])
    hold = {0: float(mix.get("mean_burst_s", 1.0)) * (1.0 - frac) / frac,
            1: float(mix.get("mean_burst_s", 1.0))}
    brng = _rng(seed, S_BURST, segment)
    edges, weights, t, state = [0.0], [], 0.0, 0
    while t < length:
        t = min(length, t + brng.exponential(hold[state]))
        edges.append(t)
        weights.append(factor if state else 1.0)
        state = 1 - state
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * np.asarray(weights))])
    return start + np.interp(u * cum[-1], cum, edges)


def tenant_prompts(mix: dict, seed: int, vocab_size: int) -> list:
    n, length = int(mix.get("tenants", 0)), int(mix.get("tenant_prompt_len", 0))
    return [_rng(seed, S_TENANT, g).integers(
        FIRST_TOKEN_ID, vocab_size, length).tolist() for g in range(n)]


def serve_requests(mix: dict, seed: int, segments, vocab_size: int,
                   rate_rps: float | None = None) -> list:
    """The materialised schedule over consecutive `segments` (their lengths
    in seconds: ramp, window, drain): one dict per request with `t` (offset
    from the start of the first segment), `tokens`, `max_tokens`, `greedy`,
    `temperature`, `top_p`, `tenant` (-1 = cold).

    Each segment holds a FIXED amount of work: exactly rate x length
    requests, their prompt and output lengths one from each stratum of the
    mix's distributions, the tenants' and the greedy share exact, so runs
    with different seeds offer the same load. The schedule's SHAPE (instants,
    lengths, pairing, sampling parameters) is drawn from the mix's
    `schedule_seed` where it has one, and is then part of the mix (another
    shape is another mix file); the token ids, like the weights, always come
    from `seed`. A tail over a window of some hundred requests moves more
    with the arrival pattern than with anything a PR changes: a mix that
    fixes its pattern takes that out of the comparison."""
    tenants = tenant_prompts(mix, seed, vocab_size)
    shape_seed = int(mix.get("schedule_seed", seed))
    p, o, s = mix["prompt_len"], mix["max_tokens"], mix["sampling"]
    reqs, start = [], 0.0
    for k, length in enumerate(segments):
        offsets = arrival_offsets(mix, shape_seed, start, float(length), k,
                                  rate_rps)
        start += float(length)
        n = len(offsets)
        rng, content = _rng(shape_seed, S_REQUEST, k), _rng(seed, S_TOKENS, k)
        joined = rng.permutation(n) < round(n * float(mix.get("tenant_frac", 0))) \
            if tenants else np.zeros(n, bool)
        which = rng.permutation(n) % max(len(tenants), 1)
        u_out, u_len = _strata(rng, n), np.empty(n)     # strata within each group
        u_len[joined] = _strata(rng, int(joined.sum()))
        u_len[~joined] = _strata(rng, int((~joined).sum()))
        greedy = rng.permutation(n) < round(n * float(s["greedy_frac"]))
        temperature = rng.uniform(*s["temperature"], n)
        top_p = rng.uniform(*s["top_p"], n)
        for i, t in enumerate(offsets):
            tenant = int(which[i]) if joined[i] else -1
            prefix = tenants[tenant] if tenant >= 0 else []
            if tenant >= 0:
                # the user's turn has a length distribution of its own (a
                # floor on the total would pile the tenant requests onto ONE
                # length, and equal lengths are what the radix cache shares)
                total = min(p["max"], len(prefix) + _lognormal_at(
                    u_len[i], mix["tenant_turn"]))
            else:
                total = _lognormal_at(u_len[i], p)
            turn = content.integers(FIRST_TOKEN_ID, vocab_size, total - len(prefix))
            reqs.append({
                "index": len(reqs), "t": float(t), "tokens": prefix + turn.tolist(),
                "max_tokens": _lognormal_at(u_out[i], o), "greedy": bool(greedy[i]),
                "temperature": round(float(temperature[i]), 6),
                "top_p": round(float(top_p[i]), 6), "tenant": tenant})
    return reqs


def digest(reqs) -> str:
    """Replay identity of a schedule (or of a prompt corpus)."""
    h = hashlib.sha256()
    if isinstance(reqs, np.ndarray):
        h.update(reqs.tobytes())
    else:
        for r in reqs:
            h.update(repr(sorted(r.items())).encode())
    return h.hexdigest()[:16]
