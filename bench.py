"""Benchmark: GRPO episodes/sec/chip on the flagship-shaped policy.

Measures one full GRPO update — rollout (N samples/prompt, jitted KV-cache
decode), reward, group advantage + keep-1-of-N, chunked policy+ref logprob
pass, and the jitted minibatch update — end to end, and reports
episodes/sec/chip against the reference baseline of ~1 s/episode on one
A100 40G (`BASELINE.md`; reference runtime print
`/root/reference/GRPO/grpo_trainer.py:726`).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "episodes/s/chip", "vs_baseline": N,
   "detail": {..., "mfu": ..., "tokens_per_sec": ..., "phase_split_s": ...}}

On failure it STILL prints one parseable JSON line with an "error" field,
and exits non-zero. Architecture: the PARENT process never imports jax. It
spawns the whole measurement as a child subprocess with a hard timeout and
retries with backoff. Only one jax process ever exists at a time: a chip
belongs to one process, and a parent that had touched jax would hold it
while its child hangs. There is no CPU fallback — the child runs on
whatever backend jax gives it and the payload names it (`detail.backend`).
The kernels' on-chip correctness checks live in chip_smoke.py, where a
mismatch is fatal.

Env overrides: BENCH_PROMPTS (default 32), BENCH_SAMPLE_N (4),
BENCH_RESPONSE (1500 — the reference's operating point, so `value` and
`vs_baseline` compare like with like; a resp-256 secondary point is
measured into detail.short_response when the budget allows),
BENCH_MODEL (1_5b | tiny), BENCH_UPDATES (2),
BENCH_ATTENTION (xla | pallas | auto), BENCH_LORA (1 | 0),
BENCH_QUANT (0 | 1: int8 rollout weights), BENCH_AHEAD (0 | 1: overlap),
BENCH_ORCH (0 | 1: async rollout orchestrator, docs/ORCHESTRATOR.md),
BENCH_STALENESS (2: orchestrator max_staleness),
BENCH_KV_QUANT (0 | 1: int8 KV cache),
BENCH_SPEC_K (0: speculative rollout decode draft length, cfg.rollout_spec_k
— the n-gram draft + batched-verify lever, sampler/speculative.py; the
always-run detail.spec_decode A/B additionally reports its acceptance /
dispatch-count win on a repetitive synthetic corpus),
BENCH_SENTINEL (1: also measure the training sentinel disabled and report
detail.sentinel.sentinel_overhead_frac — the resilience guard's cost on
the step wall, docs/RESILIENCE.md),
BENCH_TELEMETRY (1: also measure with the span tracer enabled and report
detail.telemetry.telemetry_overhead_frac — the observability acceptance
gate is < 1% of step wall, docs/OBSERVABILITY.md),
BENCH_HEALTH (1: also measure with the run-health plane disabled and report
detail.health.health_overhead_frac — the streaming-aggregator + rule-eval
cost of the default-on health monitor; acceptance < 1% of step wall,
docs/OBSERVABILITY.md §5),
BENCH_LINEAGE (1: also measure with the sample-lineage ledger enabled and
report detail.lineage.lineage_overhead_frac — the per-rollout provenance
JSONL appends' cost on the step wall; acceptance < 1%,
docs/OBSERVABILITY.md §6),
BENCH_FLEET_WORKERS (0: >1 also measures the elastic rollout fleet at that
worker count against the single-producer pipeline at the SAME staleness
and reports detail.fleet.coordinator_overhead_frac — the lease/reorder
machinery's cost on the step wall; acceptance < 2%, docs/FLEET.md — plus,
budget permitting, the same fleet over the loopback RpcTransport and
detail.fleet.rpc_transport_overhead_frac, the socket framing/codec cost;
acceptance < 5% at 2 workers, docs/FLEET.md §multi-host),
BENCH_PAGED (1: also run the continuous-batching A/B and report
detail.paged — queued-paged vs contiguous fixed-batch at equal resident
batch on a long-tail corpus, docs/PAGED_CACHE.md),
BENCH_SERVING (1: also run the radix prefix-cache A/B and report
detail.serving — radix on vs off at equal resident batch on a >= 50%
prompt-overlap corpus; acceptance prefix_hit_frac > 0.4 with strictly
fewer dispatched prefill tokens, greedy bit-identical, docs/SERVING.md),
BENCH_SESSION (1: also run the decode-session composition A/B and
report detail.session — spec+radix combined vs each feature alone at
equal resident batch on an 87.5%-overlap corpus, acceptance combined
dispatch EVENTS strictly below min(each alone) with greedy output
bit-identical 4-way and combined prefill tokens below spec-alone's,
plus the chunked-prefill p95 inter-token-gap gate at <= 1.2x the
no-long-prompt baseline on a live engine stream,
docs/PAGED_CACHE.md §session),
BENCH_SWAP (1: also run the in-flight weight-swap A/B and report
detail.swap — in-flight mid-sequence swaps vs drain-and-wait at the SAME
mid-decode publish offset (one staleness bound, met two ways), reporting
generator idle fraction, swap installs, and episodes/s; acceptance
in-flight idle strictly below drain-and-wait's with >= 1 install and
segments stamped on the live rows, plus swap_overhead_frac < 1% for an
armed-but-silent refresh vs weight_refresh=None, greedy bit-identical
throughout, docs/ORCHESTRATOR.md §in-flight swaps),
BENCH_ENV (1: also run the multi-turn environment A/B and report
detail.env — 2-turn python-tool episodes vs the single-turn degenerate
case at EQUAL resident batch, reporting turns/episode and the tool-stall
overlap fraction; acceptance turns_per_episode >= 2 with observation
tokens loss-masked and pages recycled mid-episode while single-turn
stays at exactly 1 turn with zero continuation admissions,
docs/ENVIRONMENTS.md),
BENCH_TRAFFIC (1: also run the open-loop offered-load sweep and report
detail.traffic — the SAME deterministic workload spec replayed against a
fresh in-process ServingEngine at each rate on the BENCH_TRAFFIC_RATES
grid ("4,16,64" rps); acceptance >= 3 points with goodput, shed-rate,
and p95-TTFT columns, requests conserved at every point and the top rate
shedding at least as much as the bottom, docs/TRAFFIC.md),
BENCH_ATTEMPTS (2), BENCH_ATTEMPT_TIMEOUT (2100 s per attempt — sized for
a baseline + int8-lever sweep; the sweep auto-skips when the baseline ate
>40% of the budget), BENCH_SWEEP (1 on TPU: also measure the int8 levers,
report the faster config).
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_EPS_PER_SEC = 1.0  # reference: ~1 s/episode on one A100 40G

_T0 = time.time()  # child-process start (budget accounting for secondaries)

# Peak-FLOPs table and the napkin model-FLOPs/MFU formula live in
# nanorlhf_tpu/telemetry/mfu.py — ONE accounting shared with the trainer's
# per-update `perf/mfu` series, imported in the measurement child
# (mfu.py is jax-free at module level, so the import is safe there).


def _emit(payload: dict) -> None:
    print(json.dumps(payload))


def _error_payload(msg: str, **detail) -> dict:
    return {
        "metric": "grpo_episodes_per_sec_per_chip",
        "value": 0.0,
        "unit": "episodes/s/chip",
        "vs_baseline": 0.0,
        "error": msg[-2000:],
        "detail": detail,
    }


def _run_child(timeout_s: float) -> tuple[dict | None, str]:
    """Run the measurement child; return (payload_or_None, error_tail).

    The child is this same script with BENCH_CHILD=1. Its last stdout line
    that parses as JSON with a "metric" key is the payload. On timeout the
    child is killed — the parent interpreter stays clean for a retry.
    """
    env = {**os.environ, "BENCH_CHILD": "1"}
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            _, stderr = proc.communicate(timeout=10)
        except Exception:
            stderr = ""
        tail = (stderr or "")[-500:]
        return None, f"child timed out after {timeout_s:.0f}s; stderr: {tail}"
    for line in reversed(stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            if isinstance(payload, dict) and "metric" in payload:
                if payload.get("error") and payload.get("value", 0) == 0:
                    # the child emitted an error payload (e.g. fast-raising
                    # TPU init failure): that is a FAILED attempt — the
                    # retry must still run. Hand the payload up so the
                    # final failure can emit the most informative one.
                    return None, json.dumps(payload)
                return payload, ""
        except json.JSONDecodeError:
            continue
    return None, (stderr or stdout).strip()[-800:]


def orchestrate() -> int:
    """Parent entry: spawn children with retry/backoff, emit ONE JSON line.
    Exit code 0 only when a child measured; an error payload exits 1."""
    attempts = int(os.environ.get("BENCH_ATTEMPTS", 2))
    # generous: the child may measure TWO configs (baseline + int8 sweep)
    timeout_s = float(os.environ.get("BENCH_ATTEMPT_TIMEOUT", 2100))

    errors = []
    for attempt in range(attempts):
        payload, err = _run_child(timeout_s)
        if payload is not None:
            _emit(payload)
            return 0
        errors.append(f"attempt {attempt + 1}: {err}")
        print(f"[bench] attempt {attempt + 1}/{attempts} failed: {err[:300]}",
              file=sys.stderr)
        if attempt < attempts - 1:
            time.sleep(min(20 * (attempt + 1), 60))

    # prefer the last structured child error payload over a generic one
    for err in reversed(errors):
        tail = err.split(": ", 1)[-1]
        try:
            payload = json.loads(tail)
            if isinstance(payload, dict) and "metric" in payload:
                _emit(payload)
                return 1
        except json.JSONDecodeError:
            continue
    _emit(_error_payload(" | ".join(errors)))
    return 1


def count_params(tree) -> int:
    import jax

    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def _spec_decode_check(jax) -> dict:
    """Speculative-decode lever A/B on a REPETITIVE synthetic corpus — the
    deterministic Markov "cycle model" (layers zeroed, untied one-hot head:
    token t always yields sigma(t)) emits a period-4 stream, the n-gram
    drafter's best case. Reports acceptance rate, tokens emitted per verify
    dispatch, and the dispatch-count ratio vs the monolithic loop (which
    pays one dispatch per token) — the ISSUE-5 acceptance gate is >= 2x
    fewer dispatches at spec_k=4. Runs on every backend (tiny model).
    spec_k=0 routes through the untouched monolithic jit (zero cost when
    the lever is off); its wall is reported for reference."""
    import dataclasses

    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.sampler import SamplingParams, generate

    V, rows, resp, spec_k = 32, 8, 128, 4
    mcfg = dataclasses.replace(
        ModelConfig.qwen2_tiny(vocab_size=V), tie_word_embeddings=False
    )
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    sigma[[5, 6, 7, 8]] = [6, 7, 8, 5]                  # 4-cycle, no EOS
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    ids = jnp.asarray(np.tile([5, 6, 7, 8, 5], (rows, 1)), jnp.int32)
    mask = jnp.ones_like(ids, bool)
    kw = dict(eos_token_id=3, pad_token_id=0)

    def wall(sp, stats_out=None):
        ts = []
        for rep in range(2):                            # compile + 1 timed
            t0 = time.time()
            out = generate(params, mcfg, ids, mask, jax.random.PRNGKey(rep),
                           sp, spec_stats_out=stats_out, **kw)
            np.asarray(out)
            ts.append(time.time() - t0)
        return out, ts[-1]

    out0, sec0 = wall(SamplingParams(greedy=True, max_tokens=resp))
    stats: list = []
    out1, sec1 = wall(
        SamplingParams(greedy=True, max_tokens=resp, spec_k=spec_k),
        stats_out=stats,
    )
    st = {k: int(np.asarray(v)) for k, v in stats[-1].items()
          if np.asarray(v).ndim == 0}  # scalars only (accepted_rows is [B])
    mono_steps = resp - 1                               # one dispatch/token after prefill
    identical = bool(np.array_equal(np.asarray(out0), np.asarray(out1)))
    return {
        "spec_k": spec_k,
        "response_length": resp,
        "acceptance_rate": round(st["accepted"] / max(st["drafted"], 1), 4),
        "accepted_per_step": round(st["emitted"] / max(st["row_steps"], 1), 3),
        "dispatch_steps_spec": st["verify_steps"],
        "dispatch_steps_monolithic": mono_steps,
        "dispatch_ratio": round(mono_steps / max(st["verify_steps"], 1), 2),
        "greedy_bit_identical": identical,
        "sec_spec": round(sec1, 3),
        "sec_spec_off": round(sec0, 3),
        "spec_check": "ok" if (
            identical and st["verify_steps"] * 2 <= mono_steps
        ) else "MISMATCH",
    }


def _paged_check(jax) -> dict:
    """Paged-KV continuous-batching A/B on a LONG-TAIL synthetic corpus
    (ISSUE 10, docs/PAGED_CACHE.md). Same deterministic Markov machine as
    the spec check, extended with CHAIN states (v -> v+1 -> ... -> EOS) so
    each prompt's greedy length is chosen by hand: a queue of mostly-short
    chain rows plus a few max-length 4-cycle stragglers (the n-gram
    drafter's best case, so spec_k pays on both sides). The queued paged
    scheduler (decode_rows=R, pages recycled to waiting prompts mid-loop)
    races the contiguous FIXED-BATCH schedule (waves of R, each wave
    paying its longest row) at the same resident batch and spec_k=4 on
    both sides. The ISSUE-10 acceptance gate: bit-identical greedy rows,
    strictly fewer verify dispatches, higher tokens/s. Runs on every
    backend (tiny model); gate with BENCH_PAGED=0."""
    import dataclasses

    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.sampler import SamplingParams, generate

    V, R, resp, spec_k, P = 64, 4, 40, 4, 4
    EOS, PAD = 3, 0
    # wider than qwen2_tiny ON PURPOSE: the queued scheduler trades host
    # syncs for fewer device dispatches, so the A/B only measures the
    # mechanism when per-step compute dominates dispatch overhead (on a
    # 64-wide model the CPU jit-call floor would swamp the win)
    mcfg = dataclasses.replace(
        ModelConfig.qwen2_tiny(vocab_size=V), tie_word_embeddings=False,
        hidden_size=256, intermediate_size=512, num_hidden_layers=4,
    )
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    sigma[[5, 6, 7, 8]] = [6, 7, 8, 5]                  # 4-cycle, no EOS
    for t in range(10, 50):                             # chains -> EOS
        sigma[t] = t + 1
    sigma[50] = EOS
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    # start v emits min((50 - v) + 1, resp) tokens; 5/6 start the cycle
    # (resp tokens, but HIGH spec acceptance). Queue order: the four
    # length-40 chain stragglers first (they decode concurrently in the
    # R=4 resident rows — non-repetitive, so spec can't compress them),
    # then the short-chain/cycle tail backfills recycled rows. The fixed
    # schedule is dealt ONE straggler per wave — each wave pays ~39
    # dispatches for rows that mostly finished after 3.
    starts = ([11, 11, 11, 11]
              + [47, 48, 5, 47, 48, 46, 48, 6, 47, 48, 46, 47, 48, 46, 48, 47])
    fixed_waves = [[0, 4, 5, 6], [1, 7, 8, 9], [2, 10, 11, 12],
                   [3, 13, 14, 15], [16, 17, 18, 19]]
    prompts = np.full((len(starts), 5), PAD, np.int32)
    prompts[:, 3] = 9                                   # inert filler state
    prompts[:, 4] = starts
    ids, mask = jnp.asarray(prompts), jnp.asarray(prompts != PAD)
    kw = dict(eos_token_id=EOS, pad_token_id=PAD)

    def run_fixed():
        out, stats = np.zeros((len(starts), resp), np.int32), []
        for wave in fixed_waves:
            st: list = []
            idx = jnp.asarray(wave)
            out[wave] = np.asarray(generate(
                params, mcfg, ids[idx], mask[idx], jax.random.PRNGKey(0),
                SamplingParams(greedy=True, max_tokens=resp, spec_k=spec_k),
                spec_stats_out=st, **kw))
            stats.append(st[-1])
        return out, stats

    def run_queued(latency=None):
        pst: list = []
        out = np.asarray(generate(
            params, mcfg, ids, mask, jax.random.PRNGKey(0),
            SamplingParams(greedy=True, max_tokens=resp, spec_k=spec_k,
                           page_size=P, decode_rows=R),
            paged_stats_out=pst, latency=latency, **kw))
        return out, pst[-1]

    walls = {}
    for name, fn in (("fixed", run_fixed), ("queued", run_queued)):
        for rep in range(2):                            # compile + 1 timed
            t0 = time.time()
            out, stats = fn()
            walls[name] = (out, stats, time.time() - t0)

    # per-request TTFT + inter-token percentiles (telemetry/hist.py): one
    # extra queued run with a hub attached — its admission-prefill syncs
    # would perturb the timed A/B above, so it is deliberately untimed
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    hub = LatencyHub()
    run_queued(latency=hub)
    lat_cols = {}
    for col, key in (("ttft", "latency/ttft_s"),
                     ("intertoken", "latency/intertoken_s")):
        if hub.count(key):
            lat_cols[f"{col}_p50_s"] = round(hub.quantile(key, 0.50), 5)
            lat_cols[f"{col}_p95_s"] = round(hub.quantile(key, 0.95), 5)
            lat_cols[f"{col}_count"] = hub.count(key)

    out_f, stats_f, sec_f = walls["fixed"]
    out_q, stats_q, sec_q = walls["queued"]
    tokens = int((out_f != PAD).sum())
    fixed_dispatches = sum(int(np.asarray(s["verify_steps"]))
                           for s in stats_f)
    queued_dispatches = int(np.asarray(stats_q["decode_iterations"]))
    identical = bool(np.array_equal(out_f, out_q))
    return {
        "queue_length": len(starts),
        "decode_rows": R,
        "page_size": P,
        "spec_k": spec_k,
        "response_length": resp,
        "tokens_emitted": tokens,
        "page_utilization": round(
            float(np.asarray(stats_q["page_utilization"])), 4),
        "pages_recycled": int(np.asarray(stats_q["pages_recycled"])),
        "admitted_midloop": int(np.asarray(stats_q["admitted_midloop"])),
        "dispatch_steps_fixed": fixed_dispatches,
        "dispatch_steps_queued": queued_dispatches,
        "tokens_per_sec_fixed": round(tokens / sec_f, 1),
        "tokens_per_sec_queued": round(tokens / sec_q, 1),
        "sec_fixed": round(sec_f, 3),
        "sec_queued": round(sec_q, 3),
        **lat_cols,
        "greedy_bit_identical": identical,
        "paged_check": "ok" if (
            identical and queued_dispatches < fixed_dispatches
            and sec_q < sec_f
        ) else "MISMATCH",
    }


def _swap_check(jax) -> dict:
    """In-flight mid-sequence weight swaps vs drain-and-wait A/B
    (ISSUE 20, docs/ORCHESTRATOR.md §in-flight swaps) on a deterministic
    chain machine, queued paged scheduler on both sides. A publisher
    thread publishes a fresh (numerically identical, so outputs stay
    comparable) weight version at the SAME wall-clock offset in both
    modes — one staleness bound, met two ways: drain-and-wait finishes
    its in-flight half, sits IDLE until the publish lands, then runs the
    second half on the new version; in-flight queues everything at once
    and installs the publish at a host-sync chunk boundary mid-stream.
    Reports generator idle fraction (drain: measured publish wait;
    in-flight: the cumulative install stall `swap_wait_s`), swap
    installs, and episodes/s — the ISSUE-20 gate is strictly lower idle
    in-flight. Plus the no-publish overhead gate: an armed-but-silent
    refresh callback (store never republishes) must cost < 1% wall vs
    `weight_refresh=None` (`swap_overhead_frac`). Runs on every backend
    (tiny model); gate with BENCH_SWAP=0."""
    import dataclasses
    import threading

    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.orchestrator.weight_store import (
        VersionedWeightStore, make_swap_refresh, store_poll)
    from nanorlhf_tpu.sampler import SamplingParams, generate

    V, R, resp, P = 64, 4, 40, 4
    EOS, PAD = 3, 0
    # same compute-dominant sizing rationale as _paged_check: the swap
    # poll trades a lock+compare per chunk, measurable only when chunk
    # compute dominates the jit-call floor
    mcfg = dataclasses.replace(
        ModelConfig.qwen2_tiny(vocab_size=V), tie_word_embeddings=False,
        hidden_size=256, intermediate_size=512, num_hidden_layers=4,
    )
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    for t in range(10, 50):                             # chains -> EOS
        sigma[t] = t + 1
    sigma[50] = EOS
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    # start v emits min(50 - v + 1, resp) tokens; two interleaved halves
    # with matched length mixes, so drain's first half costs ~half the
    # full-queue wall
    starts = [11, 16, 21, 26, 31, 36, 41, 46,
              13, 18, 23, 28, 33, 38, 43, 48]
    Q = len(starts) // 2
    prompts = np.full((len(starts), 5), PAD, np.int32)
    prompts[:, 3] = 9                                   # inert filler state
    prompts[:, 4] = starts
    ids, mask = jnp.asarray(prompts), jnp.asarray(prompts != PAD)
    sp = SamplingParams(greedy=True, max_tokens=resp, page_size=P,
                        decode_rows=R)
    kw = dict(eos_token_id=EOS, pad_token_id=PAD)

    def run(ids_, mask_, refresh=None, stats=None):
        return np.asarray(generate(
            params, mcfg, ids_, mask_, jax.random.PRNGKey(0), sp,
            paged_stats_out=stats, weight_refresh=refresh, **kw))

    run(ids, mask)                                      # compile: full queue
    run(ids[:Q], mask[:Q])                              # compile: half queue
    sec_plain = float("inf")
    for _ in range(2):
        t0 = time.time()
        ref_out = run(ids, mask)
        sec_plain = min(sec_plain, time.time() - t0)

    # ---- no-publish overhead: armed-but-silent refresh vs None --------
    store = VersionedWeightStore()
    store.publish(params)                               # v0, never again
    sec_armed = float("inf")
    for _ in range(2):
        st: list = []
        t0 = time.time()
        out_silent = run(ids, mask, stats=st,
                         refresh=make_swap_refresh(store_poll(store),
                                                   have_version=0))
        sec_armed = min(sec_armed, time.time() - t0)
    silent_identical = bool(np.array_equal(out_silent, ref_out))
    silent_installs = int(st[-1]["swap_installs"])
    overhead = max(0.0, (sec_armed - sec_plain) / sec_plain)

    t_pub = 0.75 * sec_plain                            # mid-decode publish

    # ---- in-flight: one queue, install at a chunk boundary ------------
    store = VersionedWeightStore()
    store.publish(params)
    timer = threading.Timer(t_pub, lambda: store.publish(params))
    st = []
    t0 = time.time()
    timer.start()
    out_if = run(ids, mask, stats=st,
                 refresh=make_swap_refresh(store_poll(store),
                                           have_version=0))
    wall_if = time.time() - t0
    timer.cancel()
    installs = int(st[-1]["swap_installs"])
    idle_if = float(st[-1]["swap_wait_s"])
    segments = st[-1]["segments"]

    # ---- drain-and-wait: half, idle until the publish, half -----------
    store = VersionedWeightStore()
    store.publish(params)
    poll = store_poll(store)
    timer = threading.Timer(t_pub, lambda: store.publish(params))
    t0 = time.time()
    timer.start()
    out_a = run(ids[:Q], mask[:Q])
    t_idle0 = time.time()
    while poll(0)[1] is None:                           # the drained idle
        time.sleep(0.001)
    idle_dw = time.time() - t_idle0
    out_b = run(ids[Q:], mask[Q:])
    wall_dw = time.time() - t0
    timer.cancel()
    out_dw = np.concatenate([out_a, out_b])

    identical = bool(np.array_equal(out_if, ref_out)
                     and np.array_equal(out_dw, ref_out))
    return {
        "queue_length": len(starts),
        "decode_rows": R,
        "response_length": resp,
        "publish_at_s": round(t_pub, 3),
        "swap_installs": installs,
        "rows_multi_segment": sum(1 for s in segments if len(s) > 1),
        "idle_frac_inflight": round(idle_if / wall_if, 4),
        "idle_frac_drain": round(idle_dw / wall_dw, 4),
        "episodes_per_sec_inflight": round(len(starts) / wall_if, 2),
        "episodes_per_sec_drain": round(len(starts) / wall_dw, 2),
        "sec_inflight": round(wall_if, 3),
        "sec_drain": round(wall_dw, 3),
        "swap_overhead_frac": round(overhead, 4),
        "silent_poll_installs": silent_installs,
        "greedy_bit_identical": identical,
        "swap_check": "ok" if (
            identical and silent_identical and silent_installs == 0
            and installs >= 1 and idle_if < idle_dw
            and overhead < 0.01
        ) else "MISMATCH",
    }


def _serving_check(jax) -> dict:
    """Cross-request radix prefix-cache A/B (ISSUE 14, docs/SERVING.md):
    the SAME queued paged scheduler at the SAME resident batch, radix
    cache on vs off, over a corpus where >= 50% of prompts share an
    8-real-token prefix with an earlier prompt (two prefix families x 8
    prompts, distinct 2-token tails). With the cache on, every repeat
    admission installs the matched prefix's pages by refcount and
    prefills only its suffix, so `prefill_token_dispatch` (tokens
    actually pushed through prefill/suffix forwards — the FLOPs proxy)
    must be STRICTLY lower and `prefix_hit_frac` must clear 0.4; greedy
    output must stay bit-identical (the rollout-parity pin from
    tests/test_serving.py, re-checked here at bench scale). TTFT
    percentiles come from untimed hub-attached re-runs — admission
    syncs would perturb the timed A/B. Gate with BENCH_SERVING=0."""
    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.sampler import SamplingParams, generate
    from nanorlhf_tpu.serving.radix import RadixCache
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    V, R, P, Tp, resp = 64, 2, 4, 12, 24
    EOS, PAD = 3, 0
    mcfg = ModelConfig.qwen2_tiny(vocab_size=V)
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    # same deterministic machine as the paged check: zeroed layers +
    # identity embedding make greedy generation a pure token permutation,
    # so each prompt's length is chosen by its last real token
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    for t in range(10, 50):                             # chains -> EOS
        sigma[t] = t + 1
    sigma[50] = EOS
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    # two 8-token prefix families, 8 prompts each, distinct 2-token
    # tails (tail state sets the greedy length): after each family's
    # first (cold) admission the other 7 are 8-real-token prefix hits —
    # 14/16 prompts overlap an earlier one
    fam_a, fam_b = [9] * 8, list(range(21, 29))
    tails = [(51 + i % 4, s) for i, s in enumerate(
        [44, 46, 40, 47, 42, 45, 41, 48])]
    reals = ([fam_a + list(t) for t in tails]
             + [fam_b + list(t) for t in tails])
    prompts = np.full((len(reals), Tp), PAD, np.int32)
    for i, rtoks in enumerate(reals):
        prompts[i, Tp - len(rtoks):] = rtoks
    ids, mask = jnp.asarray(prompts), jnp.asarray(prompts != PAD)
    sp = SamplingParams(greedy=True, max_tokens=resp,
                        page_size=P, decode_rows=R)
    kw = dict(eos_token_id=EOS, pad_token_id=PAD)

    def run(cache, latency=None):
        pst: list = []
        out = np.asarray(generate(
            params, mcfg, ids, mask, jax.random.PRNGKey(0), sp,
            paged_stats_out=pst, latency=latency, prefix_cache=cache,
            **kw))
        return out, pst[-1]

    walls = {}
    for name, cache in (("off", None), ("on", RadixCache())):
        for rep in range(2):                            # compile + 1 timed
            t0 = time.time()
            out, stats = run(cache)
            walls[name] = (out, stats, time.time() - t0)

    lat_cols = {}
    for name, cache in (("off", None), ("on", RadixCache())):
        hub = LatencyHub()
        run(cache, latency=hub)
        if hub.count("latency/ttft_s"):
            lat_cols[f"ttft_p50_s_{name}"] = round(
                hub.quantile("latency/ttft_s", 0.50), 5)
            lat_cols[f"ttft_p95_s_{name}"] = round(
                hub.quantile("latency/ttft_s", 0.95), 5)

    out_off, st_off, sec_off = walls["off"]
    out_on, st_on, sec_on = walls["on"]
    tokens = int((out_off != PAD).sum())
    disp_off = int(st_off["prefill_token_dispatch"])
    disp_on = int(st_on["prefill_token_dispatch"])
    hit_frac = float(st_on["prefix_hit_frac"])
    identical = bool(np.array_equal(out_off, out_on))
    return {
        "queue_length": len(reals),
        "decode_rows": R,
        "page_size": P,
        "prompt_len": Tp,
        "overlap_frac": round(14 / 16, 3),
        "tokens_emitted": tokens,
        "prefix_hit_frac": round(hit_frac, 4),
        "prefix_hit_tokens": int(st_on["prefix_hit_tokens"]),
        "cow_splits": int(st_on["cow_splits"]),
        "evicted_pages": int(st_on["evicted_pages"]),
        "shared_pages_peak": int(st_on["shared_pages"]),
        "prefill_token_dispatch_off": disp_off,
        "prefill_token_dispatch_on": disp_on,
        "tokens_per_sec_off": round(tokens / sec_off, 1),
        "tokens_per_sec_on": round(tokens / sec_on, 1),
        "sec_off": round(sec_off, 3),
        "sec_on": round(sec_on, 3),
        **lat_cols,
        "greedy_bit_identical": identical,
        "serving_check": "ok" if (
            identical and disp_on < disp_off and hit_frac > 0.4
        ) else "MISMATCH",
    }


def _session_check(jax) -> dict:
    """Decode-session composition A/B (ISSUE 18, docs/PAGED_CACHE.md
    §session): two gates.

    SPEC-UNDER-RADIX — the SAME queued scheduler at the SAME resident
    batch on an 87.5%-overlap corpus (one σ-chain prompt repeated 8
    times: the deterministic permutation machine makes every repeat's
    greedy continuation identical, so after the first row finishes the
    radix tree holds the exact text later admissions will generate and
    the drafter seed covers it). Combined spec+radix must issue STRICTLY
    fewer dispatch EVENTS (admission launches + decode/verify chunk
    iterations) than either feature alone — events, not tokens, because
    a verify dispatch carries k+1 tokens where plain decode carries one
    (docs/DECODE_ANALYSIS.md §dispatch accounting); the token-
    denominated half of the win (combined prefill tokens < spec-alone's)
    is gated separately. Greedy output must be bit-identical across all
    four corners.

    CHUNKED PREFILL — client-observed p95 inter-token gap on a live
    ServingEngine stream while long cold prompts admit mid-decode, with
    `prefill_chunk` on, must stay within 1.2x the no-long-prompt
    baseline (same engine, no interfering traffic). The unchunked column
    is reported for contrast but not gated — it pays each long prompt's
    whole suffix forward inside one gap. Client-side arrival timestamps,
    not the hub's chunk-wall metric, because the admission stall happens
    BETWEEN decode chunks and only the stream sees it. Gate with
    BENCH_SESSION=0."""
    import dataclasses

    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.sampler import SamplingParams, generate
    from nanorlhf_tpu.serving.radix import RadixCache

    V, R, P, Tp, resp = 64, 2, 4, 12, 12
    EOS, PAD = 3, 0
    # the σ-chain needs an UNTIED lm_head: with tie_word_embeddings the
    # unembedding is embed_tokensᵀ, logits collapse to token similarity
    # and greedy re-emits the input token forever — a constant stream the
    # unseeded drafter matches trivially, which voids the A/B
    mcfg = dataclasses.replace(
        ModelConfig.qwen2_tiny(vocab_size=V), tie_word_embeddings=False
    )
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    # the deterministic permutation machine (as in _serving_check):
    # zeroed layers + identity embedding + σ-chain lm_head make greedy
    # generation follow σ from the last real token
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    for t in range(10, 50):
        sigma[t] = t + 1
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    # 8 identical prompts: 7/8 = 87.5% overlap an earlier admission;
    # chain start 30 → every row greedily emits 31..42
    real = [9] * 6 + [30]
    Q = 8
    prompts = np.full((Q, Tp), PAD, np.int32)
    prompts[:, Tp - len(real):] = real
    ids, mask = jnp.asarray(prompts), jnp.asarray(prompts != PAD)
    kw = dict(eos_token_id=EOS, pad_token_id=PAD)

    def run(spec_k, cache):
        sp = SamplingParams(greedy=True, max_tokens=resp, page_size=P,
                            decode_rows=R, spec_k=spec_k)
        pst: list = []
        out = np.asarray(generate(
            params, mcfg, ids, mask, jax.random.PRNGKey(0), sp,
            paged_stats_out=pst, prefix_cache=cache, **kw))
        return out, pst[-1]

    out_plain, _ = run(0, None)
    out_radix, st_radix = run(0, RadixCache())
    out_spec, st_spec = run(3, None)
    out_both, st_both = run(3, RadixCache())

    identical = (np.array_equal(out_plain, out_radix)
                 and np.array_equal(out_plain, out_spec)
                 and np.array_equal(out_plain, out_both))
    ev = {k: int(s["dispatch_events"]) for k, s in
          (("radix", st_radix), ("spec", st_spec), ("both", st_both))}
    pf = {k: int(s["prefill_token_dispatch"]) for k, s in
          (("radix", st_radix), ("spec", st_spec), ("both", st_both))}
    spec_radix = {
        "queue_length": Q,
        "decode_rows": R,
        "overlap_frac": round((Q - 1) / Q, 3),
        "dispatch_events_radix": ev["radix"],
        "dispatch_events_spec": ev["spec"],
        "dispatch_events_both": ev["both"],
        "prefill_tokens_radix": pf["radix"],
        "prefill_tokens_spec": pf["spec"],
        "prefill_tokens_both": pf["both"],
        "prefix_hit_tokens": int(st_both["prefix_hit_tokens"]),
        "drafter_seed_window": st_both["session"]["features"][
            "drafter_seed_window"],
        "greedy_bit_identical": bool(identical),
        "gate": "ok" if (
            identical and ev["both"] < min(ev["radix"], ev["spec"])
            and pf["both"] < pf["spec"]
        ) else "MISMATCH",
    }

    # ---- chunked prefill: client-observed p95 inter-token gap -------- #
    from nanorlhf_tpu.serving.engine import ServingEngine

    Tp_l, MN, CH = 48, 24, 8
    long_real = list(range(4, 52))                      # 48-token cold
    victim_real = [9] * 3 + [10]

    def gaps(prefill_chunk, n_long):
        eng = ServingEngine(params, mcfg, eos_token_id=EOS,
                            pad_token_id=PAD, page_size=P,
                            prompt_len=Tp_l, max_new_tokens=MN, rows=R,
                            sync_every=4, seed=0,
                            prefill_chunk=prefill_chunk)
        try:
            # warm every compile path (victim admission, long-prompt
            # suffix bucket / chunk forward, decode chunk) before timing
            for warm in (victim_real, long_real):
                wreq, _ = eng.submit(warm, greedy=True)
                list(eng.stream(wreq))
            req, _ = eng.submit(victim_real, greedy=True)
            it = eng.stream(req)
            next(it)
            stamps = [time.perf_counter()]
            submitted = 0
            for _ in it:
                stamps.append(time.perf_counter())
                if submitted < n_long:                  # interfere mid-decode
                    submitted += 1
                    lreq, _ = eng.submit(long_real, greedy=True)
            deltas = np.diff(stamps)
            return float(np.quantile(deltas, 0.95)) if deltas.size else 0.0
        finally:
            eng.close()

    p95_base = gaps(CH, 0)
    p95_chunked = gaps(CH, 3)
    p95_unchunked = gaps(0, 3)
    ratio = p95_chunked / max(p95_base, 1e-9)
    chunked = {
        "prompt_len": Tp_l,
        "prefill_chunk": CH,
        "long_prompts": 3,
        "p95_intertoken_s_baseline": round(p95_base, 5),
        "p95_intertoken_s_chunked": round(p95_chunked, 5),
        "p95_intertoken_s_unchunked": round(p95_unchunked, 5),
        "p95_ratio_vs_baseline": round(ratio, 3),
        "gate": "ok" if ratio <= 1.2 else "MISMATCH",
    }
    return {
        "spec_under_radix": spec_radix,
        "chunked_prefill": chunked,
        "session_check": "ok" if (
            spec_radix["gate"] == "ok" and chunked["gate"] == "ok"
        ) else "MISMATCH",
    }


def _traffic_check(jax) -> dict:
    """Goodput-vs-offered-load curve (ISSUE 16, docs/TRAFFIC.md): replay
    the SAME deterministic workload spec (seed-folded prompts, greedy
    sampling, prefix-family overlap) against a FRESH in-process
    ServingEngine at each rate on a >= 3-point offered-load grid
    (BENCH_TRAFFIC_RATES, rps), via the open-loop TrafficDriver — offered
    load is the spec's, not the engine's, so past the knee the curve
    shows shedding and TTFT degradation instead of silently slowing the
    client. Checks: every point conserves requests (completed + shed +
    errors == offered, errors == 0), and the highest rate sheds at least
    as much as the lowest. Gate with BENCH_TRAFFIC=0."""
    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.loadgen import (
        TrafficDriver, WorkloadSpec, format_table, points_as_detail,
        run_sweep, spec_digest,
    )
    from nanorlhf_tpu.serving.engine import ServingEngine

    V, R, P, Tp, mx = 64, 2, 4, 12, 8
    EOS, PAD = 3, 0
    mcfg = ModelConfig.qwen2_tiny(vocab_size=V)
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    D = mcfg.hidden_size
    # the serving check's deterministic machine: zeroed layers + identity
    # embedding make greedy generation a pure token permutation
    layers = jax.tree.map(jnp.zeros_like, params["layers"])
    for ln in ("input_layernorm", "post_attention_layernorm"):
        layers[ln] = jnp.ones_like(layers[ln])
    params["layers"] = layers
    params["embed_tokens"] = jnp.zeros((V, D), jnp.float32).at[
        jnp.arange(V), jnp.arange(V)
    ].set(1.0)
    sigma = np.arange(V)
    for t in range(10, 50):
        sigma[t] = t + 1
    sigma[50] = EOS
    params["lm_head"] = jnp.zeros((D, V), jnp.float32).at[
        jnp.arange(V), jnp.asarray(sigma)
    ].set(12.0 / np.sqrt(D))

    spec = WorkloadSpec(
        seed=0, n_requests=24, arrival="poisson",
        prompt_len_min=4, prompt_len_max=Tp,
        token_lo=10, token_hi=50, prefix_groups=3, prefix_frac=0.5,
        prefix_len=4, greedy_frac=1.0,
        max_tokens_min=mx, max_tokens_max=mx,
    )
    rates = [float(r) for r in os.environ.get(
        "BENCH_TRAFFIC_RATES", "4,64,1024").split(",")]

    def make_engine():
        return ServingEngine(
            params, mcfg, eos_token_id=EOS, pad_token_id=PAD,
            page_size=P, prompt_len=Tp, max_new_tokens=mx, rows=R,
            max_queue=4, slo_warn_ttft_s=1e9)

    def run_point(point_spec):
        # fresh engine per point: shed state and radix contents must not
        # bleed across rates. slo_warn disabled so the only shed cause is
        # the queue bound — the deterministic knee. max_queue=4 on 2 rows
        # puts the knee inside the default grid.
        engine = make_engine()
        try:
            driver = TrafficDriver(engine=engine, stream_timeout_s=60.0)
            return driver.run(point_spec)
        finally:
            engine.close()

    # warm the jit cache OUTSIDE the measured sweep: one discarded run of
    # the same workload compiles every suffix-bucket/cow path the points
    # will touch — otherwise compile lands on the first point's arrivals,
    # backs up its queue, and inverts the curve (the LOWEST rate would
    # shed the most)
    run_point(dataclasses.replace(spec, rate_rps=16.0))

    points = run_sweep(run_point, spec, rates)
    print("offered-load sweep (in-process engine):", file=sys.stderr)
    print(format_table(points), file=sys.stderr)
    conserved = all(
        p.completed + p.shed + p.errors == spec.n_requests
        and p.errors == 0
        for p in points)
    monotone_knee = points[-1].shed >= points[0].shed
    return {
        "spec_digest": spec_digest(spec),
        "n_requests": spec.n_requests,
        "decode_rows": R,
        "max_queue": 4,
        "grid": points_as_detail(points),
        "traffic_check": "ok" if (
            len(points) >= 3 and conserved and monotone_knee
        ) else "MISMATCH",
    }


def _env_check(jax) -> dict:
    """Multi-turn environment A/B (ISSUE 15, docs/ENVIRONMENTS.md): the
    SAME episode driver at the SAME resident batch (decode_rows), a 2-turn
    python-tool corpus vs the single-turn degenerate case. The 2-turn side
    must average >= 2 turns/episode, loss-mask its observation tokens
    False, and recycle pages through the continuation admissions (a
    stalled tool holds zero KV capacity); tool_stall_overlap is the
    fraction of continuation decode chunks that ran while at least one
    tool call was still in flight — the latency-hiding signal. The
    single-turn side never enters the continuation loop: exactly 1
    turn/episode, mask all True, zero admissions. Tiny model + toy
    tokenizer, runs on every backend; gate with BENCH_ENV=0."""
    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.data import ToyTokenizer
    from nanorlhf_tpu.envs import (
        PythonToolEnv,
        SingleTurnEnv,
        run_env_episodes,
    )
    from nanorlhf_tpu.sampler import SamplingParams

    tok = ToyTokenizer(vocab_size=256)
    mcfg = ModelConfig.qwen2_tiny(vocab_size=tok.vocab_size)
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    B, n_samp, Tp = 4, 2, 8
    turn_tokens, obs_budget, resp, P = 16, 8, 48, 4
    rows = B * n_samp
    texts = [f"bench prompt {i} compute the answer" for i in range(B)]
    ids = np.full((B, Tp), tok.pad_token_id, np.int32)
    pmask = np.zeros((B, Tp), bool)
    for i, t in enumerate(texts):
        e = tok.encode(t)[:Tp]
        ids[i, Tp - len(e):] = e
        pmask[i, Tp - len(e):] = True
    sampling = SamplingParams(max_tokens=turn_tokens, temperature=1.0,
                              n=n_samp)
    kw = dict(eos_token_id=tok.eos_token_id, pad_token_id=tok.pad_token_id,
              tokenizer=tok, turn_tokens=turn_tokens, obs_budget=obs_budget,
              response_length=resp, page_size=P, decode_rows=rows // 2)

    def reward(pairs, eos):
        return [1.0] * len(pairs)

    env2 = PythonToolEnv(reward_func=reward, max_turns=2)
    # the toy tokenizer collapses whitespace, so fenced ```python blocks
    # don't survive a decode round-trip — pin the extracted program (same
    # move as tests/test_envs.py); the observation is still a REAL pooled
    # subprocess execution, so tool walls and stalls are genuine
    env2.extractor = lambda text: "print(6 * 7)"
    env1 = SingleTurnEnv(reward_func=reward)

    sides = {}
    try:
        for name, env, mt in (("multi", env2, 2), ("single", env1, 1)):
            t0 = time.time()
            out = run_env_episodes(
                params, mcfg, jnp.asarray(ids), jnp.asarray(pmask),
                jax.random.PRNGKey(7), sampling, env, max_turns=mt, **kw)
            sec = time.time() - t0
            st = out["stats"]
            sides[name] = {
                "turns_per_episode": round(st["env/turns_per_episode"], 3),
                "obs_tokens_masked": int((~out["loss_mask"]).sum()),
                "tool_wall_s": st["env/tool_wall_s"],
                "tool_stall_overlap": round(st["env/tool_stall_overlap"], 3),
                "stalled_rows": int(st["env/stalled_rows"]),
                "admissions": int(out["admissions"]),
                "pages_recycled": int(out["pages_recycled"]),
                "sec": round(sec, 3),
            }
    finally:
        env2.close()
    multi, single = sides["multi"], sides["single"]
    return {
        "episodes": rows,
        "decode_rows": rows // 2,
        "page_size": P,
        "turn_tokens": turn_tokens,
        "obs_budget": obs_budget,
        "response_length": resp,
        "multi_turn": multi,
        "single_turn": single,
        "env_check": "ok" if (
            multi["turns_per_episode"] >= 2.0
            and multi["obs_tokens_masked"] > 0
            and multi["admissions"] >= rows
            and multi["pages_recycled"] > 0
            and single["turns_per_episode"] == 1.0
            and single["obs_tokens_masked"] == 0
            and single["admissions"] == 0
        ) else "MISMATCH",
    }


def main():
    if os.environ.get("BENCH_CHILD") != "1":
        return orchestrate()
    # ---- measurement child: the only process that imports jax ----
    try:
        import jax

        from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

        # persistent compile cache: a warm-started run spends its time
        # measuring, not recompiling the bucket menu
        enable_compilation_cache()
        jax.devices()  # force backend init inside the bounded child
        return run_bench(jax)
    except Exception as e:  # one parseable line, never a bare stack trace
        import traceback

        _emit(_error_payload(
            f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-1500:],
        ))
        return 1


def run_bench(jax):
    import dataclasses

    import jax.numpy as jnp

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    backend = jax.default_backend()

    n_prompts = int(os.environ.get("BENCH_PROMPTS", 32))
    sample_n = int(os.environ.get("BENCH_SAMPLE_N", 4))
    # default = the reference's operating point (response_length 1500,
    # `/root/reference/README.md:36`): `value`/`vs_baseline` must compare
    # like with like (VERDICT r3 #8) — a resp-256 headline overstates parity
    # against a resp-1500 A100 baseline
    response_len = int(os.environ.get("BENCH_RESPONSE", 1500))
    model_name = os.environ.get("BENCH_MODEL", "1_5b")
    n_updates = int(os.environ.get("BENCH_UPDATES", 2))
    attention_impl = os.environ.get("BENCH_ATTENTION", "auto")
    use_lora = os.environ.get("BENCH_LORA", "1") == "1"
    rollout_quant = "int8" if os.environ.get("BENCH_QUANT", "0") == "1" else "none"
    rollout_ahead = os.environ.get("BENCH_AHEAD", "0") == "1"
    orchestrator = os.environ.get("BENCH_ORCH", "0") == "1"
    orch_staleness = int(os.environ.get("BENCH_STALENESS", "2"))
    kv_cache_quant = "int8" if os.environ.get("BENCH_KV_QUANT", "0") == "1" else "none"
    spec_k_env = int(os.environ.get("BENCH_SPEC_K", "0"))
    fleet_workers_env = int(os.environ.get("BENCH_FLEET_WORKERS", "0"))
    # BENCH_SWEEP=1 (default on real TPU): after the baseline, ALSO measure
    # the int8 rollout levers and report the faster config as the headline.
    # A lever failure (lowering, numerics) falls back to the already-measured
    # baseline instead of eating the round's only bench run.
    sweep = os.environ.get(
        "BENCH_SWEEP", "1" if backend == "tpu" else "0"
    ) == "1" and rollout_quant == "none" and kv_cache_quant == "none"

    from nanorlhf_tpu.telemetry.mfu import peak_flops_per_chip, update_flops

    n_dev = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    peak, peak_known = peak_flops_per_chip(device_kind, backend)

    mcfg = (
        ModelConfig.qwen2_1_5b() if model_name == "1_5b"
        else ModelConfig.qwen2_tiny(vocab_size=4096)
    )
    mcfg = dataclasses.replace(mcfg, attention_impl=attention_impl)
    tok = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.bfloat16)
    n_params = count_params({k: v for k, v in params.items() if k != "lora"})

    # batch hierarchy: one update consumes n_prompts episodes
    grad_accum = 2 if n_prompts % (2 * 2 * n_dev) == 0 else 1
    num_mini = 2 if n_prompts % (2 * grad_accum * n_dev) == 0 else 1
    per_dev = n_prompts // (grad_accum * num_mini * n_dev)
    assert per_dev >= 1, "BENCH_PROMPTS too small for device count"

    def reward(pmt_and_responses, eos_token):
        # cheap rule-based reward: keeps the bench focused on the TPU path
        return np.asarray(
            [(1.0 if eos_token in s else 0.0) - 0.001 * len(s.split())
             for s in pmt_and_responses],
            np.float32,
        )

    dataset = load_prompt_dataset(f"synthetic:{max(64, n_prompts * 2)}", tok,
                                  max_prompt_len=64)

    def measure(r_quant, kv_quant, ahead, resp=None, capture=False,
                orchestrator=False, staleness=2, sentinel=True,
                telemetry=False, spec_k=None, workers=1, health=True,
                lineage=False, transport="inprocess", latency=True):
        """One full config measurement: fresh trainer, warmup update
        (compile) + n_updates timed. Returns the timing dict.

        `orchestrator=True` runs the async rollout pipeline
        (docs/ORCHESTRATOR.md) at `max_staleness=staleness` with
        truncated-IS correction (capture forced on — it supplies the
        behavior logprobs). Note the bench's repeated train(num_updates=1)
        calls are exactly where the orchestrator's cross-call pipelining
        beats rollout_ahead, whose prefetch never fires inside a
        single-update train() call — the payload's
        rollout_train_overlap_frac rows make that visible.
        """
        resp = response_len if resp is None else resp
        spec_k = spec_k_env if spec_k is None else spec_k
        cfg = RLConfig(
            algo=AlgoName.GRPO,
            output_dir="/tmp/nanorlhf_tpu_bench",
            sampler_logprob_capture=capture or orchestrator,
            response_length=resp,
            temperature=0.9,
            sample_n=sample_n,
            per_device_train_batch_size=per_dev,
            gradient_accumulation_steps=grad_accum,
            num_mini_batches=num_mini,
            num_ppo_epochs=1,
            kl_coef=0.01,
            use_lora=use_lora,
            rollout_quant=r_quant,
            rollout_ahead=ahead and not orchestrator,
            rollout_orchestrator=orchestrator,
            rollout_workers=workers if orchestrator else 1,
            rollout_transport=transport,
            max_staleness=staleness,
            sentinel=sentinel,
            telemetry=telemetry,
            health=health,
            lineage=lineage,
            latency=latency,
            kv_cache_quant=kv_quant,
            rollout_spec_k=spec_k,
            gradient_checkpointing=True,
            mesh=MeshConfig(n_dev, 1, 1),
            save_steps=0,
            report_to="none",
            logging_steps=10**9,
        )
        cfg.total_episodes = n_prompts * (n_updates + 1)  # +1 warmup/compile
        trainer = RLTrainer(cfg, mcfg, tok, params, dataset, reward)
        times = []
        phase_snapshot = {}
        try:
            for i in range(n_updates + 1):
                t0 = time.time()
                trainer.train(num_updates=1)
                times.append(time.time() - t0)
                if i == 0:  # snapshot after warmup: phase split = steady-state
                    phase_snapshot = dict(trainer.timer.cumulative)
            overlap = trainer.rollout_overlap_frac()
        finally:
            trainer.close()  # join the orchestrator's producer thread
        steady = times[1:] if len(times) > 1 else times
        sec = float(np.mean(steady))
        return {
            "rollout_quant": r_quant,
            "kv_cache_quant": kv_quant,
            "fused_logprob": cfg.fused_logprob,
            "rollout_ahead": cfg.rollout_ahead,
            "rollout_orchestrator": orchestrator,
            "rollout_workers": workers if orchestrator else None,
            "max_staleness": staleness if orchestrator else None,
            "rollout_shared_prefill": cfg.rollout_shared_prefill,
            "rollout_spec_k": spec_k,
            "sampler_logprob_capture": cfg.sampler_logprob_capture,
            "response_length": resp,
            "sec_per_update_steady": round(sec, 3),
            "compile_update_sec": round(times[0], 3),
            # rollout/train overlap: fraction of generation wall-clock that
            # ran concurrently with trainer work (orchestrator.OverlapMeter)
            "rollout_train_overlap_frac": round(overlap, 4),
            # cfg.batch_size (set by finalize inside RLTrainer) is the TRUE
            # episode count per update
            "episodes_per_update": cfg.batch_size,
            "phase_split_s_per_update": {
                k: round((v - phase_snapshot.get(k, 0.0)) / max(len(steady), 1), 3)
                for k, v in sorted(trainer.timer.cumulative.items())
            },
            # latency surface (telemetry/hist.py): per-key count/mean/
            # p50/p95/p99 from this run's streaming histograms — the
            # fleet detail's TTFT/queue-wait percentile columns read it
            "latency_summary": trainer.latency.snapshot(),
        }

    t_baseline = time.time()
    chosen = measure(rollout_quant, kv_cache_quant, rollout_ahead,
                     orchestrator=orchestrator, staleness=orch_staleness)
    t_baseline = time.time() - t_baseline
    # peak HBM across the baseline config's updates (fused hidden→logprob
    # memory trajectory, BENCH_r06 onward; process-cumulative, so captured
    # BEFORE any sweep configs run). 0.0 on backends without memory stats.
    from nanorlhf_tpu.trainer.trainer import device_peak_bytes

    peak_bytes_in_use = device_peak_bytes()
    sweep_detail = None
    # the lever config recompiles everything (≈ another baseline's worth of
    # wall time) — skip when that would risk the parent's attempt timeout
    # eating the numbers we already have
    budget = float(os.environ.get("BENCH_ATTEMPT_TIMEOUT", 2100))
    if sweep and t_baseline > 0.4 * budget:
        sweep = False
        sweep_detail = {
            "skipped": f"baseline took {t_baseline:.0f}s of {budget:.0f}s budget"
        }
    if sweep:
        try:
            lever = measure("int8", "int8", rollout_ahead)
            sweep_detail = {
                "baseline_sec_per_update": chosen["sec_per_update_steady"],
                "int8_sec_per_update": lever["sec_per_update_steady"],
            }
            if lever["sec_per_update_steady"] < chosen["sec_per_update_steady"]:
                chosen = lever
        except Exception as e:  # lever failed: keep the measured baseline
            sweep_detail = {"int8_error": f"{type(e).__name__}: {e}"[:300]}
        # full stack: int8 + rollout-ahead overlap + sampler logprob capture
        # (capture halves the scoring forwards; its decode-vs-scoring drift
        # is logged by the trainer, and the ratio-clip tolerates it) — only
        # when the remaining budget can absorb another compile, and never
        # after an int8 failure (the stack reuses int8 and would just burn
        # ~a baseline's budget reproducing the same error)
        if ("int8_error" not in sweep_detail
                and budget - (time.time() - _T0) > 1.2 * t_baseline):
            try:
                stack = measure("int8", "int8", True, capture=True)
                sweep_detail["all_levers_sec_per_update"] = (
                    stack["sec_per_update_steady"]
                )
                if (stack["sec_per_update_steady"]
                        < chosen["sec_per_update_steady"]):
                    chosen = stack
            except Exception as e:
                sweep_detail["all_levers_error"] = (
                    f"{type(e).__name__}: {e}"[:300]
                )
        # async rollout orchestrator lever (docs/ORCHESTRATOR.md): depth-2
        # pipelined rollouts with truncated-IS correction. Its
        # rollout_train_overlap_frac row vs the baseline's (and vs a
        # BENCH_AHEAD run's) is the pipelining acceptance signal — the
        # bench's repeated train(num_updates=1) calls are exactly where
        # rollout_ahead's in-call prefetch never fires but the
        # orchestrator's producer thread keeps the pipeline warm.
        if (not orchestrator and isinstance(sweep_detail, dict)
                and budget - (time.time() - _T0) > 1.2 * t_baseline):
            try:
                orch = measure(
                    chosen["rollout_quant"], chosen["kv_cache_quant"], False,
                    orchestrator=True, staleness=orch_staleness,
                )
                sweep_detail["orchestrator_sec_per_update"] = (
                    orch["sec_per_update_steady"]
                )
                sweep_detail["orchestrator_overlap_frac"] = (
                    orch["rollout_train_overlap_frac"]
                )
                sweep_detail["baseline_overlap_frac"] = (
                    chosen["rollout_train_overlap_frac"]
                )
                if (orch["sec_per_update_steady"]
                        < chosen["sec_per_update_steady"]):
                    chosen = orch
            except Exception as e:
                sweep_detail["orchestrator_error"] = (
                    f"{type(e).__name__}: {e}"[:300]
                )
        # speculative-decode lever (sampler/speculative.py): draft-free
        # n-gram drafting + batched k-token verify at spec_k=4. Its win is
        # corpus-dependent (acceptance on the toy-tokenizer corpus is the
        # pessimistic floor; R1 math rollouts are the target) — the
        # detail.spec_decode synthetic A/B carries the mechanism's ceiling,
        # this sweep point carries the end-to-end wall on the bench corpus.
        if (spec_k_env == 0 and isinstance(sweep_detail, dict)
                and budget - (time.time() - _T0) > 1.2 * t_baseline):
            try:
                spec = measure(
                    chosen["rollout_quant"], chosen["kv_cache_quant"],
                    chosen["rollout_ahead"],
                    capture=chosen["sampler_logprob_capture"],
                    orchestrator=chosen["rollout_orchestrator"],
                    staleness=chosen["max_staleness"] or orch_staleness,
                    spec_k=4,
                )
                sweep_detail["spec_k4_sec_per_update"] = (
                    spec["sec_per_update_steady"]
                )
                if (spec["sec_per_update_steady"]
                        < chosen["sec_per_update_steady"]):
                    chosen = spec
            except Exception as e:
                sweep_detail["spec_k4_error"] = (
                    f"{type(e).__name__}: {e}"[:300]
                )

    # sentinel-overhead point (docs/RESILIENCE.md acceptance: the guard
    # costs <2% of the step wall): re-measure the chosen config with the
    # training sentinel disabled and report the relative delta. The
    # sentinel-off run reuses the chosen config's compiled executables
    # EXCEPT the update fn (whose grad-norm stat is emitted regardless of
    # the flag, so even that recompile is shape-identical) — cheap relative
    # to a full lever sweep, still gated on remaining budget.
    sentinel_detail = None
    if (os.environ.get("BENCH_SENTINEL", "1") == "1"
            and budget - (time.time() - _T0) > 0.9 * t_baseline):
        try:
            guard_off = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"],
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                sentinel=False,
            )
            off_sec = guard_off["sec_per_update_steady"]
            sentinel_detail = {
                "on_sec_per_update": chosen["sec_per_update_steady"],
                "off_sec_per_update": off_sec,
                "sentinel_overhead_frac": round(
                    (chosen["sec_per_update_steady"] - off_sec)
                    / max(off_sec, 1e-9), 4,
                ),
            }
        except Exception as e:
            sentinel_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # telemetry-overhead A/B (docs/OBSERVABILITY.md acceptance: the span
    # tracer + flight recorder + perf accounting cost < 1% of step wall
    # when enabled): re-measure the chosen config with cfg.telemetry on.
    # Compiled executables are config-identical, so the re-run is cheap
    # relative to a lever sweep; still gated on remaining budget.
    telemetry_detail = None
    if (os.environ.get("BENCH_TELEMETRY", "1") == "1"
            and budget - (time.time() - _T0) > 0.9 * t_baseline):
        try:
            tele_on = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"],
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                telemetry=True,
            )
            on_sec = tele_on["sec_per_update_steady"]
            telemetry_detail = {
                "off_sec_per_update": chosen["sec_per_update_steady"],
                "on_sec_per_update": on_sec,
                "telemetry_overhead_frac": round(
                    (on_sec - chosen["sec_per_update_steady"])
                    / max(chosen["sec_per_update_steady"], 1e-9), 4,
                ),
            }
        except Exception as e:
            telemetry_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # health-plane overhead A/B (docs/OBSERVABILITY.md §5 acceptance: the
    # default-ON streaming aggregators + rule evaluation cost < 1% of step
    # wall): the chosen config already ran with health on, so re-measure it
    # with the monitor disabled and report on-vs-off. Same budget gate as
    # the telemetry A/B.
    health_detail = None
    if (os.environ.get("BENCH_HEALTH", "1") == "1"
            and budget - (time.time() - _T0) > 0.9 * t_baseline):
        try:
            health_off = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"],
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                health=False,
            )
            off_sec = health_off["sec_per_update_steady"]
            health_detail = {
                "off_sec_per_update": off_sec,
                "on_sec_per_update": chosen["sec_per_update_steady"],
                "health_overhead_frac": round(
                    (chosen["sec_per_update_steady"] - off_sec)
                    / max(off_sec, 1e-9), 4,
                ),
            }
        except Exception as e:
            health_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # lineage-ledger overhead A/B (docs/OBSERVABILITY.md §6 acceptance: the
    # per-rollout provenance writes — lease/generation/queue/reward/outcome
    # JSONL appends — cost < 1% of step wall when cfg.lineage is on): the
    # chosen config ran with lineage OFF (the default), so re-measure with
    # the ledger enabled and report on-vs-off. Same budget gate as the
    # other observability A/Bs.
    lineage_detail = None
    if (os.environ.get("BENCH_LINEAGE", "1") == "1"
            and budget - (time.time() - _T0) > 0.9 * t_baseline):
        try:
            lineage_on = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"],
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                lineage=True,
            )
            on_sec = lineage_on["sec_per_update_steady"]
            lineage_detail = {
                "off_sec_per_update": chosen["sec_per_update_steady"],
                "on_sec_per_update": on_sec,
                "lineage_overhead_frac": round(
                    (on_sec - chosen["sec_per_update_steady"])
                    / max(chosen["sec_per_update_steady"], 1e-9), 4,
                ),
            }
        except Exception as e:
            lineage_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # latency-surface overhead A/B (docs/OBSERVABILITY.md §7 acceptance:
    # the default-ON streaming histograms — TTFT/queue-wait/reward/phase
    # recording plus SLO-rule quantile reads — cost < 1% of step wall):
    # the chosen config already ran with the hub on, so re-measure with
    # cfg.latency off and report on-vs-off. Same budget gate as the other
    # observability A/Bs.
    latency_detail = None
    if (os.environ.get("BENCH_LATENCY", "1") == "1"
            and budget - (time.time() - _T0) > 0.9 * t_baseline):
        try:
            latency_off = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"],
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                latency=False,
            )
            off_sec = latency_off["sec_per_update_steady"]
            latency_detail = {
                "off_sec_per_update": off_sec,
                "on_sec_per_update": chosen["sec_per_update_steady"],
                "latency_overhead_frac": round(
                    (chosen["sec_per_update_steady"] - off_sec)
                    / max(off_sec, 1e-9), 4,
                ),
            }
        except Exception as e:
            latency_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # fleet-coordinator overhead A/B (docs/FLEET.md acceptance: the lease /
    # reorder-buffer / liveness machinery costs < 2% of step wall): measure
    # the single-producer pipeline and the N-worker fleet at the SAME
    # staleness (>= N so every worker can hold a lease) and report the
    # relative delta. Generation work is identical — the delta isolates
    # coordination cost. Opt-in via BENCH_FLEET_WORKERS >= 2; two extra
    # measured configs, so gated on a wider budget margin.
    fleet_detail = None
    if (fleet_workers_env >= 2
            and budget - (time.time() - _T0) > 1.8 * t_baseline):
        fleet_staleness = max(orch_staleness, fleet_workers_env)
        try:
            single = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"], False,
                orchestrator=True, staleness=fleet_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
            )
            fleet = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"], False,
                orchestrator=True, staleness=fleet_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
                workers=fleet_workers_env,
            )
            single_sec = single["sec_per_update_steady"]
            fleet_sec = fleet["sec_per_update_steady"]
            fleet_detail = {
                "workers": fleet_workers_env,
                "max_staleness": fleet_staleness,
                "single_producer_sec_per_update": single_sec,
                "fleet_sec_per_update": fleet_sec,
                "single_producer_overlap_frac": single[
                    "rollout_train_overlap_frac"
                ],
                "fleet_overlap_frac": fleet["rollout_train_overlap_frac"],
                "coordinator_overhead_frac": round(
                    (fleet_sec - single_sec) / max(single_sec, 1e-9), 4,
                ),
            }
            # TTFT / queue-wait percentile columns (telemetry/hist.py):
            # the fleet run's own histograms — dispatch→device-ready TTFT
            # upper bound per generation, dequeue−ready queue wait per
            # consumed sample
            for col, key in (("ttft", "latency/ttft_s"),
                             ("queue_wait", "latency/queue_wait_s")):
                summ = fleet.get("latency_summary", {}).get(key)
                if summ and summ.get("count"):
                    fleet_detail[f"{col}_p50_s"] = round(summ["p50_s"], 4)
                    fleet_detail[f"{col}_p95_s"] = round(summ["p95_s"], 4)
                    fleet_detail[f"{col}_count"] = summ["count"]
            # loopback-RPC transport A/B (docs/FLEET.md §multi-host
            # acceptance: framing + codec + retry machinery costs < 5% of
            # step wall at 2 workers): same fleet config, the 3-call seam
            # now crosses a length-prefixed socket round trip per lease /
            # completion / weight fetch instead of direct method calls.
            if budget - (time.time() - _T0) > 1.3 * t_baseline:
                fleet_rpc = measure(
                    chosen["rollout_quant"], chosen["kv_cache_quant"], False,
                    orchestrator=True, staleness=fleet_staleness,
                    spec_k=chosen.get("rollout_spec_k", 0),
                    workers=fleet_workers_env, transport="rpc",
                )
                rpc_sec = fleet_rpc["sec_per_update_steady"]
                fleet_detail["rpc_sec_per_update"] = rpc_sec
                fleet_detail["rpc_overlap_frac"] = fleet_rpc[
                    "rollout_train_overlap_frac"
                ]
                fleet_detail["rpc_transport_overhead_frac"] = round(
                    (rpc_sec - fleet_sec) / max(fleet_sec, 1e-9), 4,
                )
        except Exception as e:
            fleet_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    # secondary short-response point (the r1/r2 rounds' resp-256 shape) so
    # the payload carries BOTH operating points — the resp-1500 headline
    # stays baseline-comparable and the short point tracks decode-lever
    # progress round over round. Skipped when the remaining budget can't
    # absorb another full compile, or when the caller pinned BENCH_RESPONSE
    # at/below the short width already.
    # reserve ~a baseline's worth of time for the short point itself (its
    # compile cost matches the baseline's even though its decode is shorter)
    # — launching it into insufficient budget would let the parent timeout
    # kill the child and lose the already-measured headline numbers
    short_detail = None
    if (
        backend == "tpu"
        and response_len > 256
        and budget - (time.time() - _T0) > 0.9 * t_baseline
    ):
        try:
            short = measure(
                chosen["rollout_quant"], chosen["kv_cache_quant"],
                chosen["rollout_ahead"], resp=256,
                capture=chosen["sampler_logprob_capture"],
                orchestrator=chosen["rollout_orchestrator"],
                staleness=chosen["max_staleness"] or orch_staleness,
                spec_k=chosen.get("rollout_spec_k", 0),
            )
            short_detail = {
                "response_length": 256,
                "sampler_logprob_capture": short["sampler_logprob_capture"],
                "sec_per_update_steady": short["sec_per_update_steady"],
                "episodes_per_sec_per_chip": round(
                    short["episodes_per_update"]
                    / short["sec_per_update_steady"] / n_dev, 4,
                ),
            }
        except Exception as e:
            short_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    sec_per_update = chosen["sec_per_update_steady"]
    episodes_per_update = chosen["episodes_per_update"]
    rollout_quant = chosen["rollout_quant"]
    kv_cache_quant = chosen["kv_cache_quant"]
    eps_per_sec_per_chip = episodes_per_update / sec_per_update / n_dev

    # ---- tokens/s + MFU (napkin model-FLOPs accounting) -------------------
    # decode runs until every row hits EOS; with a toy-tokenizer reward the
    # loop nearly always runs the full response_length — use it as the step
    # count. Rollout processes B·n rows per decode step.
    rollout_rows = episodes_per_update * sample_n
    ctx = min(64, dataset.input_ids.shape[1])
    seq_len = ctx + response_len
    decode_tokens = rollout_rows * response_len
    prefill_tokens = rollout_rows * ctx
    # GRPO keeps 1-of-N BEFORE the logprob pass, so only `episodes` rows are
    # scored (policy + ref) — counting all B·n rows would inflate MFU; with
    # sampler capture the policy half never runs, so only the ref forward
    # counts
    score_forwards = 1 if chosen["sampler_logprob_capture"] else 2
    score_tokens = score_forwards * episodes_per_update * seq_len
    train_tokens = 1 * episodes_per_update * seq_len    # num_ppo_epochs = 1
    # telemetry/mfu.py: forward-only tokens at 2N, trained at 3·2N — the
    # same formula behind the trainer's per-update perf/mfu metric
    flops_per_update = update_flops(
        n_params, decode_tokens=decode_tokens, prefill_tokens=prefill_tokens,
        score_tokens=score_tokens, train_tokens=train_tokens,
    )
    mfu = flops_per_update / sec_per_update / (peak * n_dev)
    tokens_per_sec = (
        (decode_tokens + prefill_tokens + score_tokens + train_tokens)
        / sec_per_update
    )

    try:
        # always-run A/B (tiny model, any backend): the lever's acceptance/
        # dispatch mechanics
        spec_decode_detail = _spec_decode_check(jax)
    except Exception as e:
        spec_decode_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    paged_detail = None
    if os.environ.get("BENCH_PAGED", "1") == "1":
        try:
            # continuous-batching A/B (tiny model, any backend) — the
            # ISSUE-10 gate: queued-paged beats fixed-batch tokens/s on a
            # long-tail corpus with spec_k=4 on both sides, bit-identical
            paged_detail = _paged_check(jax)
        except Exception as e:
            paged_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    serving_detail = None
    if os.environ.get("BENCH_SERVING", "1") == "1":
        try:
            # radix prefix-cache A/B (tiny model, any backend) — the
            # ISSUE-14 gate: >= 50% prompt overlap must clear
            # prefix_hit_frac 0.4 with strictly fewer dispatched prefill
            # tokens at equal resident batch, greedy bit-identical
            serving_detail = _serving_check(jax)
        except Exception as e:
            serving_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    session_detail = None
    if os.environ.get("BENCH_SESSION", "1") == "1":
        try:
            # decode-session composition A/B (tiny model, any backend) —
            # the ISSUE-18 gates: spec+radix combined < min(each alone)
            # in dispatch events at equal resident batch on an
            # 87.5%-overlap corpus, greedy bit-identical 4-way, and the
            # chunked-prefill p95 inter-token gap within 1.2x the
            # no-long-prompt baseline
            session_detail = _session_check(jax)
        except Exception as e:
            session_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    traffic_detail = None
    if os.environ.get("BENCH_TRAFFIC", "1") == "1":
        try:
            # goodput-vs-offered-load sweep (tiny model, any backend) —
            # the ISSUE-16 gate: >= 3 deterministic offered-load points
            # with goodput, shed-rate, and p95-TTFT columns
            traffic_detail = _traffic_check(jax)
        except Exception as e:
            traffic_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    swap_detail = None
    if os.environ.get("BENCH_SWAP", "1") == "1":
        try:
            # in-flight weight-swap A/B (tiny model, any backend) — the
            # ISSUE-20 gates: in-flight installs a mid-decode publish at a
            # chunk boundary with strictly lower generator idle than
            # drain-and-wait at the same publish offset, and an armed-but-
            # silent refresh costs < 1% wall vs weight_refresh=None
            swap_detail = _swap_check(jax)
        except Exception as e:
            swap_detail = {"error": f"{type(e).__name__}: {e}"[:300]}
    env_detail = None
    if os.environ.get("BENCH_ENV", "1") == "1":
        try:
            # multi-turn environment A/B (tiny model, any backend) — the
            # ISSUE-15 gate: 2-turn python-tool episodes average >= 2
            # turns/episode at the same resident batch as single-turn,
            # observation tokens loss-masked, pages recycled mid-episode
            env_detail = _env_check(jax)
        except Exception as e:
            env_detail = {"error": f"{type(e).__name__}: {e}"[:300]}

    detail = {
        "backend": backend,
        "device_kind": device_kind,
        "model": model_name,
        "n_params": n_params,
        "attention": attention_impl,
        "lora": use_lora,
        "rollout_quant": rollout_quant,
        "fused_logprob": chosen["fused_logprob"],
        "peak_bytes_in_use": peak_bytes_in_use,
        "rollout_ahead": chosen["rollout_ahead"],
        "rollout_orchestrator": chosen["rollout_orchestrator"],
        "max_staleness": chosen["max_staleness"],
        "rollout_train_overlap_frac": chosen["rollout_train_overlap_frac"],
        "rollout_shared_prefill": chosen["rollout_shared_prefill"],
        "rollout_spec_k": chosen.get("rollout_spec_k", 0),
        "sampler_logprob_capture": chosen["sampler_logprob_capture"],
        "kv_cache_quant": kv_cache_quant,
        "spec_decode": spec_decode_detail,
        **({"paged": paged_detail} if paged_detail is not None else {}),
        **({"serving": serving_detail} if serving_detail is not None else {}),
        **({"session": session_detail} if session_detail is not None else {}),
        **({"traffic": traffic_detail} if traffic_detail is not None else {}),
        **({"swap": swap_detail} if swap_detail is not None else {}),
        **({"env": env_detail} if env_detail is not None else {}),
        "prompts_per_update": episodes_per_update,
        "sample_n": sample_n,
        "response_length": response_len,
        "devices": n_dev,
        "sec_per_update_steady": round(sec_per_update, 3),
        "compile_update_sec": chosen["compile_update_sec"],
        "tokens_per_sec": round(tokens_per_sec, 1),
        "decode_tokens_per_sec": round(decode_tokens / sec_per_update, 1),
        "mfu": round(mfu, 4),
        "peak_flops_per_chip": peak,
        "peak_flops_known": peak_known,
        # the peak-FLOPs table fell back to a nominal constant for this
        # chip: the mfu number above is a placeholder ratio, not a real
        # utilization figure — don't read it bare
        **({} if peak_known else
           {"mfu_note": "untrusted: peak FLOPs unknown for this chip "
                        "(nominal constant used)"}),
        "phase_split_s_per_update": chosen["phase_split_s_per_update"],
    }
    if sweep_detail is not None:
        detail["sweep"] = sweep_detail
    if sentinel_detail is not None:
        detail["sentinel"] = sentinel_detail
    if telemetry_detail is not None:
        detail["telemetry"] = telemetry_detail
    if health_detail is not None:
        detail["health"] = health_detail
    if lineage_detail is not None:
        detail["lineage"] = lineage_detail
    if latency_detail is not None:
        detail["latency"] = latency_detail
    if fleet_detail is not None:
        detail["fleet"] = fleet_detail
    if short_detail is not None:
        detail["short_response"] = short_detail

    # vs_baseline only means something for the flagship model on real TPU
    # silicon AT the baseline's operating point (response_length 1500) — a
    # tiny-model, CPU-backend or short-response run must not claim a beat
    comparable = (
        backend == "tpu" and model_name == "1_5b" and response_len >= 1500
    )
    payload = {
        "metric": "grpo_episodes_per_sec_per_chip",
        "value": round(eps_per_sec_per_chip, 4),
        "unit": "episodes/s/chip",
        "vs_baseline": (
            round(eps_per_sec_per_chip / BASELINE_EPS_PER_SEC, 4)
            if comparable else 0.0
        ),
        "detail": detail,
    }
    if not comparable:
        detail["vs_baseline_note"] = (
            "0.0: run not comparable to the A100 baseline "
            f"(backend={backend}, model={model_name}, "
            f"response_length={response_len})"
        )
    elif chosen.get("sampler_logprob_capture"):
        # the sweep may promote a capture-mode run (approximate
        # old-logprobs, one fewer scoring forward) to the headline; keep
        # the comparability shift visible next to vs_baseline
        detail["vs_baseline_note"] = (
            "chosen config uses sampler_logprob_capture=True (decode-time "
            "old-logprobs, scoring forwards halved) — the A100 baseline "
            "rescores rollouts; see detail.sweep for the full-scoring time"
        )
    _emit(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
