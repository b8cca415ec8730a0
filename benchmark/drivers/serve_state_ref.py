"""Driver for mixes of kind `serve_state_ref`: `drivers/serve.py`'s open-loop
serving run for a model whose cache is a STATE of fixed size a row beside
pages (conv layers beside attention layers, docs/STATE.md), its numerics
held to the float32 reference the configuration names.

`serve_mix_ref.py` cannot take such a cell unedited: its refusal and its
`run` demand window layers and window pages that were reused, its weights
rescale leaves this model's tree does not have, and its comparison has no
wave into rows that were used before. The window (`serve.measure`,
`serve.run`, `client_metrics`), the engine's start with `eos_unreachable`
weights (`serve_ref.start`, `serve_ref.init_weights`), the warm-up of every
suffix bucket and the chunk forward (`serve_mix_ref.warm_up`) and the
counters read inside the trace (`serve_mix_ref.InsideTrace`) are theirs, by
import; one class of traffic, so the child is `serve.py`'s own. This
module's own:

- the weights are `init_params`' (this model's kernels are drawn at
  std 1 / sqrt(fan-in) already) with the configuration's `assumed.init` laid
  over them (`spread`): q/k norm weights that are not ones and an expert
  bias that changes the choice, both from the seed; without them a program
  that dropped either would serve the same tokens;
- the greedy comparison (`check_greedy`) is teacher-forced logits of what
  the TIMED engine served against the reference, at the cell's own sizes,
  in four verdicts under `agreement.follows_greedy`'s unchanged limits:
  `long`, ONE prompt of `greedy_check.long_len` tokens (three whole prefill
  pieces and a last one of a few tokens: the state is carried three times),
  then `long_max_tokens` decode steps; `short`, `short_rows` cold prompts of
  `short_min`..`short_max` tokens asked AT THE SAME TIME, decoding beside
  the long prompt's pieces; `carry`, `carry_rows` prompts of one or two
  whole pieces and a last piece of one to three tokens, `carry_max_tokens`
  each: a state lost at the last carry is most of what the last piece and
  the first served tokens see, and fades within a few tokens, so a verdict
  of few tokens a prompt (the long row's 256 steps dilute it: with the state
  zeroed at every piece `long` still read 0.85 of its limit, my chip run,
  PR 38); `reuse`, after every earlier row is released, as many prompts as
  the engine has rows, each of `reuse_min`..`reuse_max` tokens (a few: what
  the row's last occupant left in the state, if it leaked, is most of what
  such a row sees). It also needs the run to have carried a state between
  pieces, reset one a request, and taken no prefix hit;
- the reference and the plain bf16 path take a verdict's rows left-padded to
  its longest, in parts of one shape and at most
  `greedy_check.tokens_at_once` slots: a program a verdict, whatever the
  rows' lengths and however many (the reference computes every expert for
  every slot in float32 beside 10.5 GB of weights: 48 rows of 2,083 at once
  ran out of memory, my chip run, PR 38; the long verdict is one row);
  `keep`, where given, takes what the verdicts were made of and the
  functions that recompute them for a model without the bias or without the
  q/k norms (tools/state_control.py: the comparison must be able to fail);
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's layer kinds, experts and
  bias (`refuse_a_program_without_the_model`): a parent commit that cannot
  build the configuration exits 4 within seconds;
- the run's artefacts gain `moe` (the scoring forward's router counters,
  `moe/bias_changed_choice` among them: the share of the long row's tokens
  whose chosen experts are not the top k of the scores alone, a layer),
  `traced_counters` and, traced, `moe_trace` and `attn_trace`. `correct`
  also needs `moe/dropped_tokens == 0`, the engine's `serving/state_layers`
  to be the file's conv layers and `serving/kv_bytes_per_token` to be what
  the file's attention layers hold.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys

import numpy as np

from drivers import serve, serve_mix_ref, serve_ref
from drivers.rl_ref import substituted
from harness import agreement, model, ops_bytes_lfm2, trafficgen

measure = serve.measure
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "num_experts": "num_experts", "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_dense_layers": "num_dense_layers", "conv_L_cache": "conv_L_cache",
    "use_expert_bias": "use_expert_bias", "intermediate_size": "intermediate_size",
}


def conv_layers(config: dict) -> int:
    return ops_bytes_lfm2.widths(config)["Lc"]


def kv_bytes_per_token(config: dict) -> int:
    """K and V of every attention layer, bf16 (or the file's dtype)."""
    width = {"bfloat16": 2, "float32": 4}[config["assumed"]["dtype"]]
    return (ops_bytes_lfm2.widths(config)["La"]
            * ops_bytes_lfm2.kv_bytes_per_token_layer(config, width))


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        if getattr(mcfg, "conv_layers", None) != conv_layers(cfg):
            lacking["conv_layers"] = (conv_layers(cfg),
                                      getattr(mcfg, "conv_layers", None))
        why = f"file against ModelConfig: {lacking}" if lacking else None
    except (ValueError, TypeError, NotImplementedError) as e:
        why = f"{type(e).__name__}: {e}"
    if why:
        print(f"benchmark: configuration {cell.config_name!r} is not a model "
              f"this program builds ({why}). Nothing was built.",
              file=sys.stderr)
        raise SystemExit(4)


def spread(params, init: dict | None, seed: int):
    """The configuration's `assumed.init` laid over `init_params`' weights:
    `qk_norm_log_std` (every q_norm / k_norm weight exp(N(0, that))) and
    `expert_bias_std` (every router bias N(0, that)), from the seed."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 17), 3)
    layers = params["layers"]
    if init.get("qk_norm_log_std"):
        for name, key in (("q_norm", keys[0]), ("k_norm", keys[1])):
            w = layers[name]
            layers[name] = jnp.exp(float(init["qk_norm_log_std"])
                                   * jax.random.normal(key, w.shape)).astype(w.dtype)
    if init.get("expert_bias_std"):
        b = layers["router"]["bias"]
        layers["router"]["bias"] = (float(init["expert_bias_std"])
                                    * jax.random.normal(keys[2], b.shape)
                                    ).astype(b.dtype)
    return params


def check_greedy(port: int, engine, params, mcfg, cell, seed: int,
                 keep: dict | None = None) -> tuple:
    """(ok, detail): module docstring."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    from nanorlhf_tpu.core.model import padded_forward_logits

    reference = importlib.import_module("harness." + cell.config["reference"])
    mix = cell.traffic
    chk = mix["greedy_check"]
    vocab, pad = mcfg.vocab_size, int(mix["pad_token_id"])
    rng = np.random.default_rng([seed, 78])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, int(n)).tolist()  # noqa: E731
    between = lambda lo, hi, n: [draw(x) for x in rng.integers(  # noqa: E731
        int(lo), int(hi) + 1, int(n))]
    long_ = [draw(chk["long_len"])]
    short = between(chk["short_min"], chk["short_max"], chk["short_rows"])
    chunk = int(mix["engine"]["prefill_chunk"])
    # one or two whole pieces and a last one of one to three tokens
    carry = [draw(chunk * (1 + i % 2) + 1 + i % 3)
             for i in range(int(chk["carry_rows"]))]
    reuse = between(chk["reuse_min"], chk["reuse_max"], mix["engine"]["rows"])
    n_long, n_short, n_carry, n_reuse = (int(chk[k]) for k in (
        "long_max_tokens", "short_max_tokens", "carry_max_tokens",
        "reuse_max_tokens"))
    ask = lambda p, n: serve.post(port, {"tokens": p, "greedy": True,    # noqa: E731
                                         "max_tokens": n})
    before = engine.metrics()
    with ThreadPoolExecutor(max(len(long_) + len(short), len(carry),
                                len(reuse))) as pool:
        # all at once: the short rows decode beside the long row's pieces
        jobs = [pool.submit(ask, p, n_long) for p in long_]
        jobs += [pool.submit(ask, p, n_short) for p in short]
        served = [j.result() for j in jobs]
        served_carry = list(pool.map(lambda p: ask(p, n_carry), carry))
        # every row so far is released; now as many as the engine has rows,
        # so each of them is some request's later occupant
        served_reuse = list(pool.map(lambda p: ask(p, n_reuse), reuse))
    after = engine.metrics()
    served_long, served_short = served[:len(long_)], served[len(long_):]
    wanted = ([n_long] * len(long_) + [n_short] * len(short)
              + [n_carry] * len(carry) + [n_reuse] * len(reuse))
    lengths = [len(s) for s in served + served_carry + served_reuse]
    if lengths != wanted:
        return False, {"error": "a greedy answer is short (eos_unreachable "
                       "mixes yield their budget)", "lengths": lengths}
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")

    def padded(batch, answers, n):
        """[(ids, real slots, rows that count)]: the rows left-padded to the
        batch's own width, in parts of at most `tokens_at_once` slots, every
        part of one shape (the last is filled up with its own first row), so
        a verdict is one program however many rows and however long."""
        width = max(len(p) for p in batch) + n
        seqs = np.full((len(batch), width), pad, np.int32)
        for i, (p, s) in enumerate(zip(batch, answers)):
            seqs[i, width - len(p) - n:] = p + s
        rows = min(len(batch), max(1, int(chk["tokens_at_once"]) // width))
        parts = []
        for at in range(0, len(batch), rows):
            part = seqs[at:at + rows]
            count = len(part)
            part = np.concatenate([part, np.repeat(part[:1], rows - count, 0)])
            parts.append((jnp.asarray(part), jnp.asarray(part != pad), count))
        return parts

    def reference_logits(batch, answers, n, **flags):
        """The float32 reference's logits at the answers' positions;
        `flags`: the negative controls."""
        program = jax.jit(lambda p, x, m: reference.logits(
            p, cell.config, x, pad, last=n + 1, mask=m, **flags))
        with jax.default_matmul_precision("highest"):
            ref = np.concatenate([
                np.asarray(program(params, seqs, real))[:count, :-1]
                for seqs, real, count in padded(batch, answers, n)])
        return ref.reshape(-1, ref.shape[-1])

    def plain_logits(weights, batch, answers, n):
        """(the plain bf16 path's logits there, the router stats a part)."""
        program = jax.jit(lambda p, x: padded_forward_logits(
            p, plain_mcfg, x, pad, response_context_length=x.shape[1] - n,
            router_stats=True))
        plain, stats = [], []
        for seqs, _, count in padded(batch, answers, n):
            logits, part = program(weights, seqs)
            plain.append(np.asarray(logits.astype(jnp.float32))[:count])
            stats.append(jax.tree.map(np.asarray, part))
        plain = np.concatenate(plain)
        return plain.reshape(-1, plain.shape[-1]), stats

    def verdict(name, batch, answers, n):
        ref = reference_logits(batch, answers, n)
        plain, rows = plain_logits(params, batch, answers, n)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n}
        return agreement.follows_greedy(ref, tokens, plain) + (rows,)

    ok, detail, stats = verdict("long", long_, served_long, n_long)
    for name, batch, answers, n in (("short", short, served_short, n_short),
                                    ("carry", carry, served_carry, n_carry),
                                    ("reuse", reuse, served_reuse, n_reuse)):
        ok_more, detail[name], _ = verdict(name, batch, answers, n)
        ok = ok and ok_more
    if keep is not None:
        keep.update(reference_logits=reference_logits,
                    plain_logits=plain_logits, params=params)
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))     # noqa: E731
    detail.update(
        chunked_admissions=engine.session.chunked_admissions,
        state_piece_carries=gain("serving/state_piece_carries"),
        state_resets=gain("serving/state_resets"),
        prefix_hit_tokens=gain("serving/prefix_hit_tokens"))
    pieces = -(-int(chk["long_len"]) // chunk)
    if detail["state_piece_carries"] < pieces - 1 + len(carry):
        ok = False
        detail["error"] = (f"the long prompt's {pieces} pieces and the "
                           f"{len(carry)} carry prompts carried the state "
                           f"{detail['state_piece_carries']} times")
    elif detail["state_resets"] != len(wanted):
        ok = False
        detail["error"] = (f"{len(wanted)} requests, "
                           f"{detail['state_resets']} states reset")
    elif detail["prefix_hit_tokens"]:
        ok = False
        detail["error"] = "a model with conv layers took a prefix hit"
    from nanorlhf_tpu.ops.moe import moe_counters

    detail["moe"] = moe_counters(stats)     # the long row's
    return ok, detail


def start(cell, opts, keep: dict | None = None) -> serve.Served:
    """`serve_ref.start` (the engine with the mix's `prefill_chunk`, the
    gateway, the hub's reset, the set-up line) with this module's refusal,
    weights and comparison in the places of its own."""
    refuse_a_program_without_the_model(cell)
    ref_weights = serve_ref.init_weights

    def weights(*args):
        return spread(ref_weights(*args), cell.config["assumed"].get("init"),
                      int(opts["seed"]))

    with substituted(serve_ref, "init_weights", weights), \
            substituted(serve_ref, "warm_up", serve_mix_ref.warm_up), \
            substituted(serve_ref, "check_greedy", check_greedy):
        return serve_ref.start(cell, opts, keep)


def run(cell, opts):
    seen = {}

    def started(cell, opts):
        seen["served"] = start(cell, opts)
        return seen["served"]

    def tracer(*args, **kwargs):
        seen["tracer"] = serve_mix_ref.InsideTrace(
            seen["served"].engine, *args, **kwargs)
        return seen["tracer"]

    with substituted(serve, "start", started), \
            substituted(serve, "TraceWindow", tracer):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_state_ref"
    run_["moe"] = dict((run_.get("greedy_check") or {}).get("moe") or {})
    run_["traced_counters"] = seen["tracer"].counters
    if run_["moe"].get("moe/dropped_tokens"):
        result.why_not.append("moe/dropped_tokens of the scoring forward: "
                              f"{run_['moe']['moe/dropped_tokens']}")
    end = run_["counters"]["end"]
    for key, want in (("serving/state_layers", conv_layers(cell.config)),
                      ("serving/kv_bytes_per_token",
                       kv_bytes_per_token(cell.config))):
        if end.get(key) != want:
            result.why_not.append(f"the engine's {key} is {end.get(key)}, "
                                  f"the file's {want}")
    result.correct = not result.why_not
    if run_.get("trace") is not None:
        from harness import attn_trace, moe_trace, xplane

        path = xplane.newest_xplane(os.path.join(opts["out_dir"], "trace"))
        run_["moe_trace"] = moe_trace.scope_seconds(path) if path else None
        run_["attn_trace"] = attn_trace.kernel_seconds(path) if path else None
        between = seen["tracer"].counters
        print(json.dumps({
            "phase": "traced_kinds", "attn_trace": run_["attn_trace"],
            "gmm": (run_["moe_trace"] or {}).get("kernel"),
            "counters": {k: between[1][k] - between[0][k] for k in (
                "serving/decode_steps", "serving/held_experts_hit",
                "serving/global_slots_read", "serving/state_resets",
                "serving/state_piece_carries", "serving/loop_beats")
                if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
