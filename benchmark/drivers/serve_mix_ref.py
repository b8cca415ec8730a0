"""Driver for mixes of kind `serve_mix_ref`: `drivers/serve.py`'s open-loop
serving run for a model with WINDOW layers beside global ones, under traffic
of several CLASSES in one stream, its numerics held to the float32 reference
the configuration names.

`serve_ref.py` cannot take such a cell unedited: its greedy comparison
demands a radix hit (a model with window layers takes none: the tree holds
no window pages), its warm-up sends a shared-prefix pair, and its child draws
one class of prompt. The window (`serve.measure`, `serve.run`,
`client_metrics`) and the weights with `eos_unreachable` are `serve.py`'s
and `serve_ref.py`'s, by import; this module's own:

- the child is `harness/loadgen_child_classes.py`: the mix's `classes`, each
  a `trafficgen` schedule of its own, merged into one stream;
- the weights are `init_params`' with the configuration's `assumed.init`
  laid over them (`spread`: std 1 / sqrt(fan-in), attention scores and the
  embedding scaled so that bf16 stays near float32 and the window matters);
- the warm-up covers what chunked admissions use (every power-of-two suffix
  bucket up to the chunk, the KV-only chunk forward) and nothing shared;
- the greedy comparison (`check_greedy`) is teacher-forced logits of what the
  TIMED engine served against the reference, at the cell's own lengths, in
  two verdicts under `agreement.follows_greedy`'s unchanged limits: `long`,
  the prompts of `greedy_check.long_lengths` (the first at least 9,000
  tokens: it crosses prefill chunks, its window ring wraps, and it then
  decodes `long_max_tokens` tokens with the window's lower bound moving), and
  `short`, `short_rows` cold prompts served AT THE SAME TIME, a decode step
  of many live rows beside the long rows' prefill chunks, and with them the
  prompts of `tight_lengths` under the same small budget: lengths just past
  a power of two, whose admission writes a suffix bucket that reaches past
  the row's last block (600 tokens go as 1,024), so a window table that
  wrapped there would lay pad keys over the prompt's first pages (REVIEW,
  PR 34; the session drops them, `RingPages.claim`). It also needs the
  long rows to have reused window pages (`serving/window_pages_reused`) and
  the run to have taken no prefix hit;
- the reference and the plain bf16 path go a row at a time at the row's own
  length (no padding: two rows of 9,765 tokens do not fit beside a served
  model at once); `keep`, where given, takes what the verdicts were made of
  and the functions that recompute the reference and the plain path for a
  model without the window or with rotary on every layer
  (tools/window_control.py: the comparison must be able to fail);
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's window, layouts, experts
  and router placement (`refuse_a_program_without_the_model`);
- the run's artefacts gain `moe` (the scoring forward's router counters),
  `traced_counters` (`engine.metrics()` once the profiler runs and before it
  is stopped: `InsideTrace`)
  and, traced, `moe_trace` and `attn_trace` (device time of the grouped
  matmul by shape and of the two attention kernels). `correct` also needs
  `moe/dropped_tokens == 0` and the engine's `serving/window_layers` to be
  the file's.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys

import numpy as np

from drivers import serve, serve_ref
from drivers.rl_ref import substituted
from harness import agreement, model, trafficgen
from harness.window import TraceWindow

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(os.path.dirname(HERE), "harness", "loadgen_child_classes.py")
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "sliding_window_size": "sliding_window",
    "moe_num_primary_experts": "num_experts",
    "moe_num_active_primary_experts": "num_experts_per_tok",
    "moe_ffn_hidden_size": "intermediate_size",
}


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        windows = sum(1 for w in cfg["sliding_window_layout"] if w)
        for attr, want in (("window_layers", windows),
                           ("expert_activation", "relu"),
                           ("router_input", "pre_attention")):
            if getattr(mcfg, attr, None) != want:
                lacking[attr] = (want, getattr(mcfg, attr, None))
        why = f"file against ModelConfig: {lacking}" if lacking else None
    except (ValueError, TypeError, NotImplementedError) as e:
        why = f"{type(e).__name__}: {e}"
    if why:
        print(f"benchmark: configuration {cell.config_name!r} is not a model "
              f"this program builds ({why}). Nothing was built.",
              file=sys.stderr)
        raise SystemExit(4)


def spread(params, init: dict | None):
    """The configuration's `assumed.init` laid over `init_params`' weights,
    each leaf rescaled where it lies (donated: no second copy of 1.9 GB of
    expert kernels beside the model).

    `core.model.init_params` draws every STACKED kernel at std
    1 / sqrt(layers), not 1 / sqrt(fan-in) (its `stacked` hands `dense` the
    shape with the layer axis first; PERF.md section 7 has it as an open
    point, and a repair there moves every other cell's numerics). At 8
    layers that is std 0.35: q and k of RMS 18, attention one-hot on its
    top key, branch outputs of RMS ~100 over an embedding of 0.02. A network
    that chaotic amplifies a 1e-3 perturbation 13 x in its first layer and
    decorrelates bf16 from float32 by the eighth (logits' correlation 0.5,
    a served argmax 2.4 nats under the reference's top, my chip run, PR 34):
    the greedy comparison then cannot tell this model from one without the
    window. So, with `"stacked_kernels": "fan_in"`: every q/k/v/o and expert
    kernel x sqrt(layers / fan-in) (std 1 / sqrt(fan-in): unit-variance
    projections, attention scores N(0, 1)); then `q_proj` x its factor
    (scores' std 2: attention neither uniform nor one-hot, so a key outside
    the window matters) and `embed_tokens` x its factor (RMS 1: a token's
    identity is not drowned by the first branch outputs). The router keeps
    `init_params`' 1 / sqrt(D) (64 unit-variance logits)."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.donation import donate_argnums_on_accel

    rescale = jax.jit(lambda w, s: (w.astype(jnp.float32) * s).astype(w.dtype),
                      donate_argnums=donate_argnums_on_accel(0))
    layers = params["layers"]
    n = layers["input_layernorm"].shape[0]
    fan_in = init.get("stacked_kernels") == "fan_in"

    def kernel(entry, name):
        w = entry["kernel"]
        s = float(init.get(name, 1.0)) * ((n / w.shape[-2]) ** 0.5 if fan_in else 1.0)
        entry["kernel"] = rescale(w, jnp.float32(s))

    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        kernel(layers[name], name)
    for name in ("gate_proj", "up_proj", "down_proj"):
        kernel(layers["experts"][name], name)
    if "embed_tokens" in init:
        params["embed_tokens"] = rescale(params["embed_tokens"],
                                         jnp.float32(init["embed_tokens"]))
    return params


def warm_up(port: int, mix: dict, seed: int, vocab: int) -> int:
    """Every shape the window's traffic can use: the suffix buckets and the
    chunk forward (a prompt of two chunks and a bit)."""
    rng = np.random.default_rng([seed, 77])
    chunk = int(mix["engine"]["prefill_chunk"])
    lengths = serve_ref.suffix_buckets(chunk) + [2 * chunk + 5]
    for length in lengths:
        serve.post(port, {"tokens": rng.integers(
            trafficgen.FIRST_TOKEN_ID, vocab, length).tolist(),
            "greedy": length % 2 == 0, "temperature": 0.8, "top_p": 0.95,
            "max_tokens": 6})
    return len(lengths)


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
    long_ = [draw(n) for n in chk["long_lengths"]]
    short = [draw(chk["short_len"]) for _ in range(int(chk["short_rows"]))]
    short += [draw(n) for n in chk.get("tight_lengths", ())]
    n_long, n_short = int(chk["long_max_tokens"]), int(chk["short_max_tokens"])
    ask = lambda p, n: serve.post(port, {"tokens": p, "greedy": True,    # noqa: E731
                                         "max_tokens": n})
    before = engine.metrics()
    with ThreadPoolExecutor(len(long_) + len(short)) as pool:
        # all at once: the short rows decode beside the long rows' chunks
        jobs = [pool.submit(ask, p, n_long) for p in long_]
        jobs += [pool.submit(ask, p, n_short) for p in short]
        served = [j.result() for j in jobs]
    after = engine.metrics()
    served_long, served_short = served[:len(long_)], served[len(long_):]
    wanted = [n_long] * len(long_) + [n_short] * len(short)
    lengths = [len(s) for s in served]
    if lengths != wanted:
        return False, {"error": "a greedy answer is short (eos_unreachable "
                       "mixes yield their budget)", "lengths": lengths}
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")

    def reference_logits(batch, answers, n, **flags):
        """The float32 reference's logits at the answers' positions, a row
        at a time at its own length; `flags`: the negative controls."""
        fn = jax.jit(lambda p, x: reference.logits(
            p, cell.config, x, pad, last=n + 1,
            mask=jnp.ones(x.shape, bool), **flags)[0, :-1])
        with jax.default_matmul_precision("highest"):
            return np.concatenate([np.asarray(fn(
                params, jnp.asarray([p + s], jnp.int32)))
                for p, s in zip(batch, answers)])

    def plain_logits(weights, batch, answers, n, **other_model):
        """(the plain bf16 path's logits there, router stats a row);
        `other_model`: ModelConfig fields of a negative control's model."""
        cfg = dataclasses.replace(plain_mcfg, **other_model)

        def one_row(p, x):
            logits, stats = padded_forward_logits(
                p, cfg, x, pad, response_context_length=x.shape[1] - n,
                router_stats=True)
            return logits[0].astype(jnp.float32), stats

        fn = jax.jit(one_row)
        out = [fn(weights, jnp.asarray([p + s], jnp.int32))
               for p, s in zip(batch, answers)]
        return (np.concatenate([np.asarray(o[0]) for o in out]),
                [jax.tree.map(np.asarray, o[1]) for o in out])

    def verdict(name, batch, answers, n):
        ref = reference_logits(batch, answers, n)
        plain, rows = plain_logits(params, batch, answers, n)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n}
        return agreement.follows_greedy(ref, tokens, plain) + (rows,)

    ok, detail, stats = verdict("long", long_, served_long, n_long)
    ok_short, detail["short"], _ = verdict("short", short, served_short, n_short)
    ok = ok and ok_short
    if keep is not None:
        keep.update(reference_logits=reference_logits,
                    plain_logits=plain_logits, params=params)
    gain = lambda k: after.get(k, 0) - before.get(k, 0)     # noqa: E731
    detail.update(chunked_admissions=engine.session.chunked_admissions,
                  window_pages_reused=int(gain("serving/window_pages_reused")),
                  prefix_hit_tokens=int(gain("serving/prefix_hit_tokens")))
    if detail["window_pages_reused"] <= 0:
        ok = False
        detail["error"] = "the long rows reused no window page"
    elif detail["prefix_hit_tokens"]:
        ok = False
        detail["error"] = "a model with window layers took a prefix hit"
    from nanorlhf_tpu.ops.moe import moe_counters

    detail["moe"] = moe_counters(stats)     # the long rows'
    return ok, detail


def start(cell, opts, keep: dict | None = None) -> serve.Served:
    """`serve_ref.start` (the engine with the mix's `prefill_chunk`, the
    gateway, the hub's reset, the set-up line) with this module's refusal,
    weights, warm-up and comparison in the places of its own."""
    refuse_a_program_without_the_model(cell)
    ref_weights = serve_ref.init_weights

    def weights(*args):
        return spread(ref_weights(*args), cell.config["assumed"].get("init"))

    with substituted(serve_ref, "init_weights", weights), \
            substituted(serve_ref, "warm_up", warm_up), \
            substituted(serve_ref, "check_greedy", check_greedy):
        return serve_ref.start(cell, opts, keep)


class InsideTrace(TraceWindow):
    """The traced part, with the engine's counters read INSIDE it (`counters`:
    [once the profiler runs, before it is stopped]). The profiler takes
    seconds to start and to write its file while the engine goes on serving:
    counters read around it (`serve_ref.CountedTrace`) held 740 decode steps
    where the trace held 172 (my chip run, PR 34), and a metric that sets a
    count beside the trace's own seconds then takes the rows of another
    stretch of time."""

    def __init__(self, engine, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._engine, self.counters = engine, []

    def start(self) -> None:
        super().start()
        if self.enabled:
            self.counters = [self._engine.metrics()]

    def stop(self) -> None:
        if self.enabled and self._notes:
            self.counters.append(self._engine.metrics())
        super().stop()


def measure(served, cell, opts, tracer, rate: float | None = None) -> dict:
    """`serve.measure` with the child that draws the mix's classes."""
    with substituted(serve, "CHILD", CHILD):
        return serve.measure(served, cell, opts, tracer, rate)


def run(cell, opts):
    seen = {}

    def started(cell, opts):
        seen["served"] = start(cell, opts)
        return seen["served"]

    def tracer(*args, **kwargs):
        seen["tracer"] = InsideTrace(seen["served"].engine, *args, **kwargs)
        return seen["tracer"]

    with substituted(serve, "start", started), \
            substituted(serve, "TraceWindow", tracer), \
            substituted(serve, "CHILD", CHILD):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_mix_ref"
    run_["moe"] = dict((run_.get("greedy_check") or {}).get("moe") or {})
    run_["traced_counters"] = seen["tracer"].counters
    if run_["moe"].get("moe/dropped_tokens"):
        result.why_not.append("moe/dropped_tokens of the scoring forward: "
                              f"{run_['moe']['moe/dropped_tokens']}")
    windows = sum(1 for w in cell.config["sliding_window_layout"] if w)
    end = run_["counters"]["end"]
    if end.get("serving/window_layers") != windows:
        result.why_not.append(
            f"the engine has {end.get('serving/window_layers')} window "
            f"layers, the file {windows}")
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
                "serving/global_slots_read", "serving/window_slots_read",
                "serving/loop_beats") if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
