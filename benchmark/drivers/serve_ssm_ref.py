"""Driver for mixes of kind `serve_ssm_ref`: `drivers/serve.py`'s open-loop
serving run for a model whose EVERY layer keeps pages and a state at once (a
state-space mixer beside an attention, docs/SSM.md), its numerics held to the
float32 reference the configuration names.

`serve_state_ref.py` cannot take such a cell unedited: its refusal demands
experts, a router bias and conv layers, its weights rescale leaves this
model's tree does not have, and its comparison asks the plain path for router
statistics a dense model has none of. The window (`serve.measure`,
`serve.run`, `client_metrics`), the engine's start with `eos_unreachable`
weights (`serve_ref.start`, `serve_ref.init_weights`), the warm-up of every
suffix bucket and the chunk forward (`serve_mix_ref.warm_up`) and the
counters read inside the trace (`serve_mix_ref.InsideTrace`) are theirs, by
import; one class of traffic, so the child is `serve.py`'s own. This
module's own:

- the weights are `init_params`' (kernels at std 1 / sqrt(fan-in), `A_log`
  and `dt_bias` as Mamba-2 draws them) with the configuration's
  `assumed.init` laid over them (`spread`): the gains under which neither
  branch vanishes beside the other at the published multipliers, and `D`,
  the convolution's bias and the mixer's norm away from ones and zeros;
- the greedy comparison (`check_greedy`) is `serve_state_ref.check_greedy`'s
  for a dense model: teacher-forced logits of what the TIMED engine served
  against the reference, at the cell's own sizes, in four verdicts under
  `agreement.follows_greedy`'s unchanged limits (a served token may lie
  `GAP_SLACK` = 1.5 x in the mean and 4 x at the worst as far under the
  reference's top as the plain bf16 path's own argmax does, measured on the
  same positions in the same run: the limit is the rounding of THIS model at
  THESE weights, not a number chosen here). `long`: ONE prompt of
  `long_len` tokens (three whole prefill pieces and a last one of a few
  tokens: both state leaves are carried three times), then `long_max_tokens`
  decode steps, each a pass over the state; `short`: `short_rows` cold
  prompts asked AT THE SAME TIME, decoding beside the long prompt's pieces;
  `carry`: `carry_rows` prompts of one or two whole pieces and a last piece
  of one to three tokens; `reuse`: after every earlier row is released, as
  many short prompts as the engine has rows, into rows whose last occupant
  left a state. It also needs the run to have carried a state between
  pieces, reset one a request, and taken no prefix hit. What the limits are
  worth is read by tools/ssm_control.py: a branch zeroed, `mup` left out and
  a state not carried each fail the verdicts asked of them. The recurrent
  state kept in bfloat16 does NOT fail them (PERF.md, PR 49: its readings
  are the sound run's; sound `long` itself flips a twentieth of its tokens,
  so no limit on served tokens has power over the state here);
- so the state itself is compared (`state_reading`): once the `long` row
  and once a `carry` row have answered, the engine is idle and each row
  still holds what its request left, and the state of the row is read
  where it lies and held to the float32 reference's after the same tokens
  (`reference.final_states`: the prompt and every answered token but the
  last, which no step was fed), under `STATE_LIMIT`. That is the limit a
  bfloat16 state fails, and an update that rounds to bfloat16 or sums in
  it whatever the type of the buffer it stores;
- the reference and the plain bf16 path take a verdict's rows left-padded to
  its longest, in parts of one shape and at most
  `greedy_check.tokens_at_once` slots (`serve_state_ref.check_greedy`'s
  `padded`); `keep`, where given, takes what the verdicts were made of and
  the functions that recompute them for another model;
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's mixer and multipliers
  (`refuse_a_program_without_the_model`): a parent commit that cannot build
  the configuration exits 4 within seconds;
- `correct` also needs the engine's `serving/state_layers`,
  `serving/state_bytes_per_row` and `serving/kv_bytes_per_token` to be what
  the file's layers hold. Traced, the run's artefacts gain `attn_trace` (the
  in-place paged read's kernel) and `traced_counters`.
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
from harness import agreement, model, ops_bytes_falcon_h1 as ob, trafficgen

measure = serve.measure
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_n_groups": "ssm_groups", "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv", "mamba_chunk_size": "ssm_chunk",
    "embedding_multiplier": "embed_scale",
    "attention_in_multiplier": "attention_in_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "num_hidden_layers": "ssm_layers", "intermediate_size": "intermediate_size",
}
TUPLE_KEYS = ("ssm_multipliers", "mlp_multipliers")
# `state_reading`'s limit, between its two readings on the chip (my chip runs,
# PR 49, seed 77, `chiprun_out/r2`; PERF.md section 6): the engine as it is, a
# float32 state under bfloat16 activations, reads 2.2e-3 on the `long` row
# and 2.0e-3 on the `carry` row (1e-7 to 4e-7 where the activations are
# float32 too: the CPU rehearsal); the state kept in bfloat16, the nearest
# precision below, 7.9e-3 on `carry` (some thirty decode steps) and 1.1e-2
# on `long` (255).
STATE_LIMIT = 4.5e-3


def kv_bytes_per_token(config: dict) -> int:
    width = {"bfloat16": 2, "float32": 4}[config["assumed"]["dtype"]]
    return (config["num_hidden_layers"]
            * ob.kv_bytes_per_token_layer(config, width))


def state_bytes_per_row(config: dict) -> int:
    width = {"bfloat16": 2, "float32": 4}[config["assumed"]["dtype"]]
    return ob.state_bytes_per_row(config, width)


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        lacking.update({k: (cfg[k], getattr(mcfg, k, None))
                        for k in TUPLE_KEYS
                        if getattr(mcfg, k, None) != tuple(cfg[k])})
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
    `gains` (a kernel times its gain, `in_proj_parts` a gain a part of the
    mixer's input projection, each rescaled where it lies: donated, no second
    copy of a 2.67 GB head), `D_std` (`D` ~ N(1, that)), `conv_bias_std`
    (the bias ~ N(0, that)) and `ssm_norm_log_std` (the mixer's norm weight
    exp(N(0, that))), from the seed."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.donation import donate_argnums_on_accel

    rescale = jax.jit(lambda w, s: (w.astype(jnp.float32) * s).astype(w.dtype),
                      donate_argnums=donate_argnums_on_accel(0))
    layers = params["layers"]
    ssm = layers["ssm"]
    gains = dict(init.get("gains") or {})
    parts = gains.pop("in_proj_parts", None)
    if "lm_head" in gains:
        params["lm_head"] = rescale(params["lm_head"],
                                    jnp.float32(gains.pop("lm_head")))
    for name, gain in gains.items():
        layers[name]["kernel"] = rescale(layers[name]["kernel"],
                                         jnp.float32(gain))
    if parts:
        width = ssm["in_proj"]["kernel"].shape[-1]
        I = ssm["norm"].shape[-1]
        gn = (width - 2 * I) // 2
        columns = jnp.concatenate([
            jnp.full((n,), g, jnp.float32)
            for n, g in zip((I, I, gn, gn), parts)])
        ssm["in_proj"]["kernel"] = rescale(ssm["in_proj"]["kernel"], columns)
        ssm["dt_proj"]["kernel"] = rescale(ssm["dt_proj"]["kernel"],
                                           jnp.float32(parts[4]))
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 23), 3)
    draw = lambda key, like: jax.random.normal(key, like.shape, jnp.float32)  # noqa: E731
    if init.get("D_std"):
        ssm["D"] = (1.0 + float(init["D_std"]) * draw(keys[0], ssm["D"])
                    ).astype(ssm["D"].dtype)
    if init.get("conv_bias_std"):
        bias = ssm["conv"]["bias"]
        ssm["conv"]["bias"] = (float(init["conv_bias_std"])
                               * draw(keys[1], bias)).astype(bias.dtype)
    if init.get("ssm_norm_log_std"):
        ssm["norm"] = jnp.exp(float(init["ssm_norm_log_std"])
                              * draw(keys[2], ssm["norm"])
                              ).astype(ssm["norm"].dtype)
    return params


def slow_heads(params) -> np.ndarray:
    """The quarter of the first layer's heads that forget slowest: by the
    decay a token at `dt_t = 0`, `softplus(dt_bias) exp(A_log)`, which is the
    weights' own."""
    ssm = params["layers"]["ssm"]
    bias, A_log = (np.asarray(ssm[k][0], np.float32) for k in ("dt_bias", "A_log"))
    rate = np.logaddexp(bias, 0.0) * np.exp(A_log)
    return np.sort(np.argsort(rate)[:max(1, len(rate) // 4)])


def state_reading(engine, want, slow) -> dict:
    """The engine's recurrent state against the reference's `want` [L, H, P,
    N], the state after every token the row was fed: a head's distance in
    the Frobenius norm as a share of the reference's, [L, H], for the row of
    the engine that holds it. The engine does not say which row served a
    request and a finished row keeps its state until its next occupant
    (docs/SSM.md), so the row is the one nearest the reference: another
    request's state lies ~1 away (two unrelated states 1.4, a fresh row's
    zeros 1). Asked while the engine is idle: only then may another thread
    read the session's carry.

    What is held to `STATE_LIMIT` is the root mean square over the `slow`
    heads of the FIRST layer. A served model's activations are bfloat16, so
    every head's state lies some 1e-3 to 3e-2 from the float32 reference's
    whatever its own type, more with every layer of activations before it;
    the first layer's mixer reads the embedding's norm alone. There a head
    that forgets slowly sums the independent roundings of the tokens it
    keeps, which average out (2e-3), while a state ROUNDED to bfloat16 as it
    is stored adds a rounding a token that the head keeps as long (7e-3 to
    8e-3 after thirty decode steps, 8e-3 to 11e-3 after 255). The other
    heads and layers are reported."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def distance(S, want):                      # S [L, R, H, P, N]
        off = jnp.sum((S.astype(jnp.float32) - want[:, None]) ** 2, (-1, -2))
        return jnp.sqrt(off / jnp.sum(want ** 2, (-1, -2))[:, None])

    S = engine.session.state[3][-1][-1]
    far = np.asarray(distance(S, want))         # [L, R, H]
    by_row = far.mean(axis=(0, 2))
    row = int(by_row.argmin())
    heads = far[:, row]
    first_slow = float(np.sqrt(np.mean(heads[0, slow] ** 2)))
    return {"ok": bool(first_slow <= STATE_LIMIT), "row": row,
            "first_layer_slow": first_slow, "limit": STATE_LIMIT,
            "slow_heads": slow.tolist(),
            "layer_max": heads.max(axis=1).tolist(),
            "layer_median": np.median(heads, axis=1).tolist(),
            "next_row": float(np.partition(by_row, 1)[1]),
            "state_dtype": str(S.dtype), "heads": heads.tolist()}


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
    fed_most = max(len(long_[0]) + n_long, len(carry[-1]) + n_carry) - 1
    states = jax.jit(lambda p, x: reference.final_states(
        p, cell.config, x, pad)[:, 0])
    slow = slow_heads(params)

    def state_of(prompt, answer):
        """`state_reading` for a request just served: the reference's state
        after the prompt and every answered token but the last (which no
        step was fed), the row left-padded to the longer of the two so that
        both readings are one program."""
        fed = prompt + answer[:-1]
        ids = np.full((1, fed_most), pad, np.int32)
        ids[0, fed_most - len(fed):] = fed
        with jax.default_matmul_precision("highest"):
            want = states(params, jnp.asarray(ids))
        return state_reading(engine, want, slow)

    before = engine.metrics()
    with ThreadPoolExecutor(max(len(long_) + len(short), len(carry),
                                len(reuse))) as pool:
        # all at once: the short rows decode beside the long row's pieces
        jobs = [pool.submit(ask, p, n_long) for p in long_]
        jobs += [pool.submit(ask, p, n_short) for p in short]
        served = [j.result() for j in jobs]
        # every answer is in, so the engine is idle and each row still holds
        # the state its request left
        state = {"long": state_of(long_[0], served[0])}
        served_carry = list(pool.map(lambda p: ask(p, n_carry), carry))
        state["carry"] = state_of(carry[-1], served_carry[-1])
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

    def reference_logits(batch, answers, n, without=()):
        """The float32 reference's logits at the answers' positions;
        `without`: the negative controls."""
        program = jax.jit(lambda p, x, m: reference.logits(
            p, cell.config, x, pad, last=n + 1, mask=m, without=without))
        with jax.default_matmul_precision("highest"):
            ref = np.concatenate([
                np.asarray(program(params, seqs, real))[:count, :-1]
                for seqs, real, count in padded(batch, answers, n)])
        return ref.reshape(-1, ref.shape[-1])

    def plain_logits(weights, batch, answers, n, **other_model):
        """The plain bf16 path's logits there (`other_model`: fields of the
        `ModelConfig` that a control's model has otherwise)."""
        plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla",
                                         **other_model)
        program = jax.jit(lambda p, x: padded_forward_logits(
            p, plain_mcfg, x, pad, response_context_length=x.shape[1] - n))
        plain = np.concatenate([
            np.asarray(program(weights, seqs).astype(jnp.float32))[:count]
            for seqs, _, count in padded(batch, answers, n)])
        return plain.reshape(-1, plain.shape[-1])

    def verdict(name, batch, answers, n):
        ref = reference_logits(batch, answers, n)
        plain = plain_logits(params, batch, answers, n)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n}
        return agreement.follows_greedy(ref, tokens, plain)

    ok, detail = verdict("long", long_, served_long, n_long)
    for name, batch, answers, n in (("short", short, served_short, n_short),
                                    ("carry", carry, served_carry, n_carry),
                                    ("reuse", reuse, served_reuse, n_reuse)):
        ok_more, detail[name] = verdict(name, batch, answers, n)
        ok = ok and ok_more
    ok = ok and all(reading["ok"] for reading in state.values())
    detail["state"] = {name: {k: v for k, v in reading.items() if k != "heads"}
                       for name, reading in state.items()}
    if keep is not None:
        keep.update(reference_logits=reference_logits,
                    plain_logits=plain_logits, params=params, state=state)
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))     # noqa: E731
    detail.update(
        chunked_admissions=engine.session.chunked_admissions,
        state_carries=gain("serving/state_piece_carries"),
        state_resets=gain("serving/state_resets"),
        prefix_hit_tokens=gain("serving/prefix_hit_tokens"))
    pieces = -(-int(chk["long_len"]) // chunk)
    if detail["state_carries"] < pieces - 1 + len(carry):
        ok = False
        detail["error"] = (f"the long prompt's {pieces} pieces and the "
                           f"{len(carry)} carry prompts carried the state "
                           f"{detail['state_carries']} times")
    elif detail["state_resets"] != len(wanted):
        ok = False
        detail["error"] = (f"{len(wanted)} requests, "
                           f"{detail['state_resets']} states reset")
    elif detail["prefix_hit_tokens"]:
        ok = False
        detail["error"] = "a model that keeps a state took a prefix hit"
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
    run_["kind"] = "serve_ssm_ref"
    run_["traced_counters"] = seen["tracer"].counters
    end = run_["counters"]["end"]
    for key, want in (("serving/state_layers", cell.config["num_hidden_layers"]),
                      ("serving/state_bytes_per_row",
                       state_bytes_per_row(cell.config)),
                      ("serving/kv_bytes_per_token",
                       kv_bytes_per_token(cell.config))):
        if end.get(key) != want:
            result.why_not.append(f"the engine's {key} is {end.get(key)}, "
                                  f"the file's {want}")
    result.correct = not result.why_not
    if run_.get("trace") is not None:
        from harness import attn_trace, xplane

        path = xplane.newest_xplane(os.path.join(opts["out_dir"], "trace"))
        run_["attn_trace"] = attn_trace.kernel_seconds(path) if path else None
        between = seen["tracer"].counters
        print(json.dumps({
            "phase": "traced_kinds", "attn_trace": run_["attn_trace"],
            "counters": {k: between[1][k] - between[0][k] for k in (
                "serving/decode_steps", "serving/live_row_steps",
                "serving/global_slots_read", "serving/state_resets",
                "serving/state_piece_carries", "serving/state_tokens",
                "serving/loop_beats")
                if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
