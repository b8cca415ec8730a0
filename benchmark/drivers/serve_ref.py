"""Driver for mixes of kind `serve_ref`: `drivers/serve.py`'s open-loop
serving run, its numerics held to the float32 reference the CONFIGURATION
names, for a model whose prompts run to thousands of tokens.

`serve.py` imports `harness/reference.py` (the dense Qwen2 forward) by name
and builds its engine without chunked prefill. This driver runs the same
window (`measure`, `client_metrics`, `run` are `serve.py`'s, by import) and
makes the set-up its own:

- the greedy comparison takes the module under `harness/` that the
  configuration file's `reference` key names (`reference_axk1`), and computes
  the plain bf16 path a row at a time (four rows of 4,288 tokens do not fit
  beside a served model at once);
- the comparison gives two verdicts, each under `agreement`'s unchanged
  limits (`check_greedy`): the cell's own long prompts, judged alone, with
  `greedy_check.max_tokens` new tokens each, and `short_rows` short cold
  prompts served at once. In a chip's share a token's logits move by whole
  nats where bf16 tips a router decision onto or off a held expert: about
  one token in thirty, so the 192 tokens of four answers of 48 held ~6 such
  events on either path and the ratio of the two mean gaps swung between 0.6
  and 2.75 over nine seeds with equal averages (two of six runs fell on the
  wrong side of 1.5; my chip runs, PR 31). So a verdict takes 1,300-2,000
  tokens: the long rows decode 512 each (at 384 the ratio read 0.85-1.38 in
  eight runs);
- the engine takes `engine.prefill_chunk` from the mix, and the warm-up
  covers what chunked admissions use: every power-of-two suffix bucket up to
  the chunk (a chunked prompt's LAST piece can have any length), the KV-only
  chunk forward, and a shared-prefix pair that ends inside a page (the
  copy-on-write page copy);
- a mix may say `"eos_unreachable": true`, as `rl_ref`'s mixes may and for
  its reason: the output columns of the mix's EOS and pad ids are zeroed, so
  neither is ever the argmax nor among the 64 candidates the sampler keeps,
  and every request yields its budget. With a vocabulary slice of 20,480
  rows a random model ended a request early in four of six runs, and
  `tokens_per_s`, which is the offered load below the knee, swung by 3.2 %
  (spread of six runs, my chip run, PR 31) against the 0.5 % a cell is
  admitted at;
- it fails at once, with a non-zero exit and before any weights are built,
  when the program's `ModelConfig` does not carry the file's latent-attention
  and expert keys: a program that drops them would serve another model under
  the configuration's name for the whole window;
- the run's artefacts gain `moe` (the router's counters from the greedy
  check's scoring forward: `moe/routed_here_frac`, `moe/dropped_tokens`,
  `moe/absent_assignments`, `moe/held_experts`; and `moe/held_experts_hit`,
  the held experts a layer that a decode step's live rows reached in the
  window, OBSERVED: the program counts them on the device,
  `serving/held_experts_hit` over `serving/decode_steps`; traced, also
  `moe/held_experts_hit_traced`, the same between the profiler's start and
  stop) and, traced, `moe_trace` (harness/moe_trace.py: the grouped matmul's
  calls by shape). `correct` also needs `moe/dropped_tokens == 0` and, where
  the file has `kv_lora_rank`, a latent page pool (`serving/latent_cache`).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys

import numpy as np

from drivers import serve
from drivers.rl_ref import substituted
from harness import agreement, model, ops_bytes_axk1, trafficgen
from harness.window import Meter, TraceWindow, annotate

measure = serve.measure
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute, for the keys that make
# this another model than a dense decoder of the same widths
MODEL_KEYS = {
    "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "num_experts",
    "n_routed_experts_held": "experts_held",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_intermediate_size",
    "first_k_dense_replace": "first_k_dense_replace",
    "scoring_func": "scoring_func",
    "routed_scaling_factor": "routed_scaling_factor",
}


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    try:
        mcfg = model.model_config(cell.config)
        lacking = {k: (cell.config[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items() if k in cell.config
                   and getattr(mcfg, attr, None) != cell.config[k]}
        why = f"file against ModelConfig: {lacking}" if lacking else None
    except (ValueError, TypeError) as e:
        why = f"{type(e).__name__}: {e}"
    if why:
        print(f"benchmark: configuration {cell.config_name!r} is not a model "
              f"this program builds ({why}). Nothing was built.",
              file=sys.stderr)
        raise SystemExit(4)


def suffix_buckets(chunk: int) -> list:
    out, b = [], 1
    while b < chunk:
        out.append(b)
        b *= 2
    return out + [chunk]


def warm_up(port: int, mix: dict, seed: int, vocab: int) -> int:
    """Every shape the window's traffic can use: the suffix buckets, the
    chunk forward (a prompt of two chunks), the copy-on-write pair."""
    rng = np.random.default_rng([seed, 77])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, n).tolist()  # noqa: E731
    chunk = int(mix["engine"]["prefill_chunk"])
    lengths = suffix_buckets(chunk) + [2 * chunk]
    for length in lengths:
        serve.post(port, {"tokens": draw(length), "greedy": True, "max_tokens": 6})
    prefix = draw(int(mix["tenant_prompt_len"]))
    turn = int(mix["tenant_turn"]["min"]) + 7
    for _ in range(2):
        serve.post(port, {"tokens": prefix + draw(turn), "temperature": 0.8,
                          "top_p": 0.95, "max_tokens": 6})
    return len(lengths) + 2


def check_greedy(port: int, engine, params, mcfg, cell, seed: int,
                 keep: dict | None = None) -> tuple:
    """`serve.check_greedy` with the configuration's reference, in two
    verdicts, each `agreement.follows_greedy` under its unchanged limits.

    `long`: the cell's own lengths (`cold_lengths`: the second crosses prefill
    chunks; two prompts of `shared_prefix` + `turn` at equal length, so the
    second is a radix hit), `max_tokens` new tokens each: what the cold
    chunked prefill, the key-block reads at thousands of slots, the radix hit
    and the copy-on-write page did to the tokens is judged on these rows
    alone. The first three are asked at once, the radix hit after them.
    `short`: `short_rows` cold prompts of `short_len` tokens asked at once,
    `short_max_tokens` each: a decode step with that many live rows.

    The served tokens against the float32 reference, teacher-forced; the
    plain bf16 path goes a row at a time and hands back the router's counters
    of that very forward (the long rows'). `keep`, where given, takes what
    the verdicts were made of (benchmark/tools/greedy_control.py)."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    from nanorlhf_tpu.core.model import padded_forward_logits

    reference = importlib.import_module("harness." + cell.config["reference"])
    mix = cell.traffic
    chk = mix["greedy_check"]
    vocab, pad = mcfg.vocab_size, int(mix["pad_token_id"])
    rng = np.random.default_rng([seed, 78])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, n).tolist()  # noqa: E731
    shared = draw(int(chk["shared_prefix"]))
    prompts = [draw(int(n)) for n in chk["cold_lengths"]]
    prompts += [shared + draw(int(chk["turn"])), shared + draw(int(chk["turn"]))]
    n_new = int(chk["max_tokens"])
    n_short = int(chk.get("short_max_tokens", n_new))
    short = [draw(int(chk["short_len"])) for _ in range(int(chk.get("short_rows", 0)))]
    ask = lambda p, n: serve.post(port, {"tokens": p, "greedy": True,    # noqa: E731
                                         "max_tokens": n})
    with ThreadPoolExecutor(max(len(short), len(prompts))) as pool:
        served = list(pool.map(lambda p: ask(p, n_new), prompts[:-1]))
        before = engine.metrics()
        served.append(ask(prompts[-1], n_new))
        after = engine.metrics()
        # all at once: they fill the engine's rows for ~n_short steps
        served_short = list(pool.map(lambda p: ask(p, n_short), short))
    hit = after["serving/prefix_hit_tokens"] - before["serving/prefix_hit_tokens"]
    eos = int(mix["eos_token_id"])
    lengths = [len(s) for s in served + served_short]
    wanted = [n_new] * len(served) + [n_short] * len(served_short)
    if not all(n == w or (s and s[-1] == eos) for n, w, s in
               zip(lengths, wanted, served + served_short)):
        return False, {"error": "a greedy answer is short", "lengths": lengths}
    if lengths != wanted:       # an EOS: nothing to compare
        return True, {"skipped": "a greedy answer ended in EOS"}
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    experts = bool(mcfg.num_experts)

    def padded(batch, answers, n):
        """Rows left-padded to the batch's own width, and which slots are real."""
        width = max(len(p) for p in batch) + n
        seqs = np.full((len(batch), width), pad, np.int32)
        real = np.zeros(seqs.shape, bool)
        for i, (p, s) in enumerate(zip(batch, answers)):
            seqs[i, width - len(p) - n:] = p + s
            real[i, width - len(p) - n:] = True
        return jnp.asarray(seqs), jnp.asarray(real)

    def reference_logits(batch, answers, n):
        """The float32 reference's logits at the answers' positions."""
        seqs, real = padded(batch, answers, n)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(lambda p, x, m: reference.logits(
                p, cell.config, x, pad, last=n + 1, mask=m))(
                    params, seqs, real))[:, :-1]
        return ref.reshape(-1, ref.shape[-1])

    def plain_logits(weights, batch, answers, n):
        """(the plain bf16 path's logits there, router stats a row), a row at
        a time (four rows of 4,600 tokens do not fit beside a served model)."""
        seqs, _ = padded(batch, answers, n)

        def one_row(p, row):
            out = padded_forward_logits(
                p, plain_mcfg, row[None], pad,
                response_context_length=seqs.shape[1] - n, router_stats=experts)
            logits, stats = out if experts else (out, {})
            return logits[0].astype(jnp.float32), stats

        plain, stats = jax.jit(lambda p, x: jax.lax.map(
            lambda row: one_row(p, row), x))(weights, seqs)
        rows = [jax.tree.map(lambda a, i=i: np.asarray(a)[i], stats)
                for i in range(len(batch))]
        return np.asarray(plain).reshape(-1, plain.shape[-1]), rows

    def verdict(name, batch, answers, n):
        ref = reference_logits(batch, answers, n)
        plain, rows = plain_logits(params, batch, answers, n)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n}
        return agreement.follows_greedy(ref, tokens, plain) + (rows,)

    ok, detail, stats = verdict("long", prompts, served, n_new)
    if short:
        ok_short, detail["short"], _ = verdict("short", short, served_short, n_short)
        ok = ok and ok_short
    if keep is not None:
        keep.update(plain_logits=plain_logits, params=params)
    detail.update(radix_hit_tokens=int(hit),
                  chunked_admissions=engine.session.chunked_admissions)
    if hit < int(chk["shared_prefix"]) - 1:
        ok = False
        detail["error"] = "the equal-length repeat was not a radix hit"
    if experts:
        from nanorlhf_tpu.ops.moe import moe_counters

        held = ((mcfg.experts_held, mcfg.experts_offset)
                if mcfg.experts_held else None)
        detail["moe"] = moe_counters(stats, held=held)   # the long rows'

    return ok, detail


def init_weights(mcfg, seed: int, dtype, mix: dict):
    """`harness/model.init_weights`; with `eos_unreachable`, the output
    columns of the mix's EOS and pad ids zeroed (the embedding's rows where
    the head is tied)."""
    import jax.numpy as jnp

    params = model.init_weights(mcfg, seed, dtype)
    if mix.get("eos_unreachable"):
        ids = jnp.asarray([int(mix["eos_token_id"]), int(mix["pad_token_id"])])
        if "lm_head" in params:
            params["lm_head"] = params["lm_head"].at[:, ids].set(0)
        else:
            params["embed_tokens"] = params["embed_tokens"].at[ids].set(0)
    return params


def start(cell, opts, keep: dict | None = None) -> serve.Served:
    """`serve.start` with the mix's `prefill_chunk`, this module's warm-up
    and greedy comparison (`keep`: `check_greedy`'s)."""
    from nanorlhf_tpu.serving.engine import ServingEngine
    from nanorlhf_tpu.serving.gateway import ServingGateway
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    refuse_a_program_without_the_model(cell)
    mix, seed = cell.traffic, int(opts["seed"])
    meter = Meter()
    mark0 = meter.mark()
    mcfg = model.model_config(cell.config)
    params = init_weights(mcfg, seed, model.dtype_of(cell.config), mix)
    hub = LatencyHub(enabled=True)
    e = mix["engine"]
    engine = ServingEngine(
        params, mcfg, eos_token_id=int(mix["eos_token_id"]),
        pad_token_id=int(mix["pad_token_id"]), page_size=int(e["page_size"]),
        prompt_len=int(e["prompt_len"]), max_new_tokens=int(e["max_new_tokens"]),
        rows=int(e["rows"]), headroom=float(e["headroom"]),
        sync_every=int(e["sync_every"]), max_queue=int(e["max_queue"]),
        prefill_chunk=int(e["prefill_chunk"]), latency=hub, seed=seed)
    gateway = ServingGateway(engine, port=-1)
    try:
        with annotate("bench.warmup"):
            n_warm = warm_up(gateway.port, mix, seed, mcfg.vocab_size)
            greedy_ok, greedy = check_greedy(gateway.port, engine, params, mcfg,
                                             cell, seed, keep)
    except BaseException:
        gateway.close()
        engine.close()
        raise
    serve.reset_hub(hub)
    setup_compile = Meter.delta(mark0, meter.mark())
    counters = engine.metrics()
    print(json.dumps({
        "phase": "setup", "warmup_requests": n_warm, "greedy_check": greedy,
        "num_pages": engine.num_pages,
        "decode_attention": "pallas-paged" if counters["serving/attn_in_place"]
        else "xla-gathered-view",
        **{k: counters.get(k) for k in ("serving/kv_bytes_per_token",
                                        "serving/latent_cache",
                                        "serving/pool_donated")},
        **setup_compile}), flush=True)
    return serve.Served(engine, gateway, meter, mcfg.vocab_size, setup_compile,
                        greedy_ok, greedy)


class CountedTrace(TraceWindow):
    """The traced part, with the engine's counters read as the profiler
    starts and as it stops (`counters`: [before, after]): a metric that sets
    a count of the program beside device time of the trace takes the count
    of these very seconds, not the window's."""

    def __init__(self, engine, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._engine, self.counters = engine, []

    def start(self) -> None:
        if self.enabled:
            self.counters = [self._engine.metrics()]
        super().start()

    def stop(self) -> None:
        open_ = bool(self._notes)
        super().stop()
        if open_:
            self.counters.append(self._engine.metrics())


def experts_hit_a_step(config: dict, before: dict, after: dict):
    """Held experts a layer that a decode step's live rows reached, between
    two readings of `engine.metrics()`: `serving/held_experts_hit` (counted
    on the device from the router's choices) over `serving/decode_steps` and
    the expert layers. None where the program has no such counter or took no
    step."""
    w = ops_bytes_axk1.widths(config)
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        hit = after["serving/held_experts_hit"] - before["serving/held_experts_hit"]
    except KeyError:
        return None
    return hit / (steps * w["Le"]) if steps > 0 and w["Le"] else None


def run(cell, opts):
    seen = {}

    def started(cell, opts):
        seen["served"] = start(cell, opts)
        return seen["served"]

    def tracer(*args, **kwargs):
        seen["tracer"] = CountedTrace(seen["served"].engine, *args, **kwargs)
        return seen["tracer"]

    with substituted(serve, "start", started), \
            substituted(serve, "TraceWindow", tracer):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_ref"
    moe = dict((run_.get("greedy_check") or {}).get("moe") or {})
    if moe and cell.config.get("n_routed_experts_held"):
        spans = {"moe/held_experts_hit": (run_["counters"]["start"],
                                          run_["counters"]["end"])}
        if len(seen["tracer"].counters) == 2:
            spans["moe/held_experts_hit_traced"] = seen["tracer"].counters
        for name, (before, after) in spans.items():
            hit = experts_hit_a_step(cell.config, before, after)
            if hit is not None:
                moe[name] = hit
    run_["moe"] = moe
    if moe.get("moe/dropped_tokens"):
        result.why_not.append(
            f"moe/dropped_tokens of the scoring forward: {moe['moe/dropped_tokens']}")
    end = run_["counters"]["end"]
    if cell.config.get("kv_lora_rank") and end.get("serving/latent_cache") != 1:
        result.why_not.append("the page pool is not the latent one: "
                              f"serving/latent_cache = {end.get('serving/latent_cache')}")
    result.correct = not result.why_not
    if run_.get("trace") is not None:
        from harness import moe_trace, xplane

        path = xplane.newest_xplane(os.path.join(opts["out_dir"], "trace"))
        run_["moe_trace"] = moe_trace.scope_seconds(path) if path else None
    return result
