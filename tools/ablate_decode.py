"""Decode-lever ablation on real hardware — one process, which holds the chip.

Measures rollout (generation) throughput of the flagship-shaped policy under
each decode lever shipped in r2, at short and long response lengths. The
levers (ROADMAP.md S3):

  exact_topk    — lax.top_k k=64 pre-trim (full-vocab sort on TPU)
  approx_topk   — lax.approx_max_k pre-trim (default since r2)
  full_nucleus  — top_k=0 exact full-vocab nucleus (r1-zero default, r4)
  int8_weights  — rollout_quant="int8" weight-only base projections
  int8_kv       — kv_cache_quant="int8" + q8 decode kernel
  int8_both     — both quantizations
  spec{2,4,8}   — speculative decode (sampler/speculative.py): n-gram draft
                  + batched k-token verify at spec_k ∈ {2,4,8}, nucleus
                  sampling (the spec_k=0 nucleus baseline IS approx_topk)
  greedy0       — greedy decode baseline (spec_k=0)
  greedy_spec{2,4,8} — greedy speculative decode; greedy accept is bit-exact
                  vs greedy0, so the sec_steady delta is pure mechanism cost
                  /win at the measured acceptance (printed per lever)
  n4_shared     — n=4 samples/prompt with shared-prompt-KV prefill (r5
                  default; vLLM prefix-sharing analogue)
  n4_repeat     — n=4 with the repeat-×N prefill (the pre-r5 path); the
                  sec_steady delta vs n4_shared is the measured prefill
                  dedup win at the GRPO operating point

Prints one JSON line per (lever, response_length) with decode tokens/s, and
a final summary line. Run on the chip as the only jax process
(`chiprun -- python tools/ablate_decode.py`):

  python tools/ablate_decode.py            # both lengths, all levers
  ABLATE_RESPONSE=2048 python tools/ablate_decode.py
  ABLATE_ROWS=32 ABLATE_LEVERS=approx_topk,int8_kv python tools/ablate_decode.py

Timings are end-to-end generate() walls (device sync via np.asarray fetch) —
whole-loop walls (chained dispatch + full fetch), not per-op microbenches.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()  # warm-start repeat runs

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core.quant import quantize_layers, rollout_view
    from nanorlhf_tpu.data import ToyTokenizer
    from nanorlhf_tpu.sampler import SamplingParams, generate

    rows = int(os.environ.get("ABLATE_ROWS", 32))
    lengths = (
        [int(os.environ.get("ABLATE_RESPONSE"))]
        if os.environ.get("ABLATE_RESPONSE")
        else [256, 2048]
    )
    lever_env = os.environ.get("ABLATE_LEVERS")
    model = os.environ.get("ABLATE_MODEL", "1_5b")

    mcfg = (
        ModelConfig.qwen2_1_5b() if model == "1_5b"
        else ModelConfig.qwen2_tiny(vocab_size=4096)
    )
    tok = ToyTokenizer(vocab_size=min(4096, mcfg.vocab_size))
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.bfloat16)
    dev = jax.devices()[0]
    print(f"[ablate] backend={jax.default_backend()} device={dev.device_kind}",
          file=sys.stderr)

    rng = np.random.default_rng(0)
    Tp = 64
    ids = rng.integers(4, tok.vocab_size, (rows, Tp)).astype(np.int32)
    ids[:, :8] = tok.pad_token_id  # a little left-padding
    ids_j = jnp.asarray(ids)
    mask_j = ids_j != tok.pad_token_id

    import dataclasses

    def make_levers():
        base = dict(params=params, mcfg=mcfg, sp_kw={}, note="")
        q_params = None
        kv_cfg = dataclasses.replace(mcfg, kv_cache_quant="int8")
        levers = {
            "exact_topk": dict(base, sp_kw={"approx_top_k": False}),
            "approx_topk": dict(base),
            # top_k=0: exact full-vocab nucleus (full sort) — the r1-zero
            # launcher default since r4 (base-model exploration must not be
            # top-k-truncated); its cost vs the k=64 pre-trim decides
            # whether other launchers follow
            "full_nucleus": dict(base, sp_kw={"top_k": 0}),
            "int8_weights": None,  # filled below (lazy quantize)
            "int8_kv": dict(base, mcfg=kv_cfg),
            "int8_both": None,
            "n4_shared": dict(base, sp_kw={"n": 4}),
            "n4_repeat": dict(base, sp_kw={"n": 4,
                                           "shared_prompt_prefill": False}),
            # speculative decode, spec_k x {greedy, nucleus} (ISSUE 5): the
            # spec_k=0 nucleus baseline is approx_topk above; greedy0 is the
            # greedy baseline. Acceptance on this random-prompt corpus is
            # the pessimistic floor — the roofline row in
            # docs/DECODE_ANALYSIS.md projects the repetitive-corpus case.
            "greedy0": dict(base, sp_kw={"greedy": True}),
        }
        for sk in (2, 4, 8):
            levers[f"spec{sk}"] = dict(base, sp_kw={"spec_k": sk})
            levers[f"greedy_spec{sk}"] = dict(
                base, sp_kw={"greedy": True, "spec_k": sk}
            )
        wanted = (lever_env.split(",") if lever_env else list(levers))
        if "int8_weights" in wanted or "int8_both" in wanted:
            q_params = rollout_view(params, quantize_layers(params["layers"]))
            levers["int8_weights"] = dict(base, params=q_params)
            levers["int8_both"] = dict(base, params=q_params, mcfg=kv_cfg)
        return {k: levers[k] for k in wanted if levers.get(k) is not None}

    results = {}
    for resp in lengths:
        for name, spec in make_levers().items():
            sp = SamplingParams(
                temperature=0.9, top_p=0.95, max_tokens=resp,
                **spec["sp_kw"],
            )
            # warmup (compile) + 2 timed reps
            times = []
            spec_stats: list = []
            for rep in range(3):
                t0 = time.time()
                out = generate(spec["params"], spec["mcfg"], ids_j, mask_j,
                               jax.random.PRNGKey(rep), sp,
                               eos_token_id=tok.eos_token_id,
                               pad_token_id=tok.pad_token_id,
                               spec_stats_out=spec_stats)
                np.asarray(out)  # full fetch = honest sync
                times.append(time.time() - t0)
            steady = float(np.mean(times[1:]))
            n_rows = out.shape[0]  # rows × n for the fanout levers
            toks = n_rows * resp / steady
            results[(name, resp)] = toks
            row = {
                "lever": name, "response_length": resp, "rows": n_rows,
                "sec_steady": round(steady, 3), "compile_sec": round(times[0], 1),
                "decode_tokens_per_sec": round(toks, 1),
            }
            if spec_stats:
                st = {k: int(np.asarray(v)) for k, v in spec_stats[-1].items()
                      if np.asarray(v).ndim == 0}  # accepted_rows is [B]
                row["spec_acceptance"] = round(
                    st["accepted"] / max(st["drafted"], 1), 4
                )
                row["spec_accepted_per_step"] = round(
                    st["emitted"] / max(st["row_steps"], 1), 3
                )
                row["spec_verify_steps"] = st["verify_steps"]
            print(json.dumps(row), flush=True)

    base_key = ("approx_topk", lengths[-1])
    # n4_* levers decode rows×4 physical rows — their raw tokens/s scales
    # with batch size, so they must not enter the cross-lever best/speedup
    # (which would crown them on a batch-size artifact). Their meaningful
    # number is the PAIRWISE shared-vs-repeat ratio, reported separately.
    # greedy* levers likewise: greedy decode skips the nucleus math the
    # headline pays, so they compare only within the greedy family (the
    # greedy_specK / greedy0 pairwise ratios below).
    same_batch = {k: v for k, v in results.items()
                  if not k[0].startswith(("n4_", "greedy"))}
    summary = {
        "metric": "decode_ablation",
        "device": dev.device_kind,
        "best": max(same_batch, key=same_batch.get) if same_batch else None,
        "tokens_per_sec": {f"{k[0]}@{k[1]}": round(v, 1)
                           for k, v in results.items()},
    }
    if base_key in same_batch:
        summary["speedup_vs_approx_topk"] = {
            f"{k[0]}@{k[1]}": round(v / results[base_key], 3)
            for k, v in same_batch.items() if k[1] == lengths[-1]
        }
    for resp in lengths:
        a, b = ("n4_shared", resp), ("n4_repeat", resp)
        if a in results and b in results:
            summary[f"n4_shared_speedup_vs_repeat@{resp}"] = round(
                results[a] / results[b], 3
            )
        for sk in (2, 4, 8):
            g, g0 = (f"greedy_spec{sk}", resp), ("greedy0", resp)
            if g in results and g0 in results:
                summary[f"greedy_spec{sk}_speedup@{resp}"] = round(
                    results[g] / results[g0], 3
                )
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
