"""Driver for mixes of kind `serve_reason_ref`: `drivers/serve.py`'s open-loop
serving run for a model whose rows pass the attention window WHILE THEY
DECODE (short tasks, thousands of generated tokens), a chip's share of
bias-selected experts under gated attention (Trinity, docs/AFMOE.md), its
numerics held to the float32 reference the configuration names.

`serve_mix_ref.py` cannot take such a cell unedited: its refusal and its `run`
read SmallThinker's layouts (`sliding_window_layout`), its weights rescale a
one-stack tree, and its long verdict crosses the window at prefill. The
window (`serve.measure`, `serve.run`, `client_metrics`), the engine's start
with `eos_unreachable` weights (`serve_ref.start`, `serve_ref.init_weights`),
the warm-up of every suffix bucket and the chunk forward
(`serve_mix_ref.warm_up`) and the counters read inside the trace
(`serve_mix_ref.InsideTrace`) are theirs, by import; one class of traffic, so
the child is `serve.py`'s own. This module's own:

- the weights are `init_params`' (this model's kernels are drawn at std
  1 / sqrt(fan-in) already) with the configuration's `assumed.init` laid over
  them (`spread`), so that the comparison can tell the model from one without
  each mechanism: the gate's projection scaled, every norm's weight
  exp(N(0, s)), an expert bias that changes the choice, an embedding of RMS 1
  after its scale;
- the greedy comparison (`check_greedy`) is teacher-forced logits of what the
  TIMED engine served against the reference with the same share and
  vocabulary slice, at the cell's own sizes, in two verdicts under
  `agreement.follows_greedy`'s unchanged limits: `long`, the prompts of
  `greedy_check.long_lengths` with `long_max_tokens` new tokens each (the
  first is admitted in three pieces, passes the window's edge some 1,100
  steps into its decode, and its window ring of 42 pages wraps while decode
  chunks are in flight); `short`, `short_rows` cold prompts of `short_len`
  tokens served AT THE SAME TIME, beside the long rows' prefill pieces, and
  with them the prompts of `tight_lengths` (just past a power of two:
  `serve_mix_ref`'s reason). It also needs window pages reused by a DECODING
  row (`serving/window_pages_reused_in_decode`), no prefix hit, and a
  non-zero share of tokens whose chosen experts the bias changed;
- the asking, the reference and the plain bf16 path (a row at a time at the
  row's own length, one program a distinct length, five in all) and `keep`
  are `serve_mix_ref.check_greedy`'s, by call, its router counters reduced
  for the chip's share; `keep` takes what the verdicts were made of and the
  functions that recompute them for a model without one mechanism
  (tools/gate_control.py: the comparison must be able to fail);
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's gate, norms, scale,
  layouts, experts, share and bias (`refuse_a_program_without_the_model`): a
  parent commit that cannot build the configuration exits 4 within seconds;
- the run's artefacts gain `moe` (the scoring forward's router counters, and
  `moe/held_experts_hit`, the held experts a layer that a decode step's live
  rows reached in the window, counted on the device), `traced_counters` and,
  traced, `moe_trace` and `attn_trace`. `correct` also needs
  `moe/dropped_tokens == 0` and the engine's `serving/window_layers` to be
  the file's.
"""

from __future__ import annotations

import json
import os
import sys

from drivers import serve, serve_mix_ref, serve_ref
from drivers.rl_ref import substituted
from harness import model
from harness import ops_bytes_trinity as ob

measure = serve.measure
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "num_experts": "num_experts", "num_experts_per_tok": "num_experts_per_tok",
    "num_experts_held": "experts_held", "num_experts_offset": "experts_offset",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_dense_layers": "num_dense_layers",
    "num_shared_experts": "n_shared_experts",
    "intermediate_size": "intermediate_size",
    "sliding_window": "sliding_window", "route_scale": "routed_scaling_factor",
}


def window_layers(config: dict) -> int:
    return ob.widths(config)["Lw"]


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        sliding = tuple(int(t == "sliding_attention") for t in cfg["layer_types"])
        for attr, want in (("attention_gate", True), ("branch_norms", True),
                           ("use_expert_bias", True),
                           ("qk_norm_per_head", True),
                           ("embed_scale", float(cfg["hidden_size"]) ** 0.5),
                           ("sliding_window_layout", sliding),
                           ("rope_layout", sliding),
                           ("window_layers", window_layers(cfg))):
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


NORMS = ("input_layernorm", "post_attention_layernorm", "attn_branch_norm",
         "mlp_branch_norm")


def spread(params, init: dict | None, seed: int):
    """The configuration's `assumed.init` laid over `init_params`' weights, in
    both stacks: `g_proj` (the gate's kernel times that), `norm_log_std`
    (every weight of the four norms exp(N(0, that))), `qk_norm_log_std` (the
    per-head norms alike), `q_norm` (the queries' per-head norm weights times
    that, after the draw: the attention scores' spread), `expert_bias_std`
    (every router bias N(0, that)), `embed_tokens` (the embedding times
    that), from the seed."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 42), 16))

    def log_normal(w, std):
        return jnp.exp(float(std) * jax.random.normal(
            next(keys), w.shape)).astype(w.dtype)

    def scaled(w, factor):
        return (w.astype(jnp.float32) * float(factor)).astype(w.dtype)

    for stack in ("dense_layers", "layers"):
        tree = params.get(stack)
        if tree is None:
            continue
        for names, key in ((NORMS, "norm_log_std"),
                           (("q_norm", "k_norm"), "qk_norm_log_std")):
            if init.get(key):
                for name in names:
                    tree[name] = log_normal(tree[name], init[key])
        if init.get("g_proj"):
            tree["g_proj"]["kernel"] = scaled(tree["g_proj"]["kernel"],
                                              init["g_proj"])
        if init.get("q_norm"):
            tree["q_norm"] = scaled(tree["q_norm"], init["q_norm"])
    if init.get("expert_bias_std"):
        b = params["layers"]["router"]["bias"]
        params["layers"]["router"]["bias"] = (
            float(init["expert_bias_std"])
            * jax.random.normal(next(keys), b.shape)).astype(b.dtype)
    if init.get("embed_tokens"):
        params["embed_tokens"] = scaled(params["embed_tokens"],
                                        init["embed_tokens"])
    return params


def check_greedy(port: int, engine, params, mcfg, cell, seed: int,
                 keep: dict | None = None) -> tuple:
    """(ok, detail): `serve_mix_ref.check_greedy` (the two verdicts, asked at
    once, a row at a time against this configuration's reference; the same
    `greedy_check` keys and the same `keep`) with the router's counters taken
    for the chip's SHARE, and this cell's own demands on top: a window page
    reused by a decoding row, and a bias that changed some token's choice."""
    from nanorlhf_tpu.ops import moe

    held = ((mcfg.experts_held, mcfg.experts_offset)
            if getattr(mcfg, "experts_held", 0) else None)
    counters = moe.moe_counters
    before = engine.metrics()
    # (`serve_mix_ref` looks `moe_counters` up when it reduces the long rows'
    # router stats, and knows no share)
    with substituted(moe, "moe_counters",
                     lambda stats: counters(stats, held=held)):
        ok, detail = serve_mix_ref.check_greedy(port, engine, params, mcfg,
                                                cell, seed, keep)
    after = engine.metrics()
    if "moe" not in detail:     # a short answer: nothing was compared
        return ok, detail
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))     # noqa: E731
    detail.update(
        window_pages_reused_in_decode=gain(
            "serving/window_pages_reused_in_decode"),
        rows_past_window=gain("serving/rows_past_window"))
    if "error" in detail:
        return False, detail
    if detail["window_pages_reused_in_decode"] <= 0:
        ok = False
        detail["error"] = "no DECODING row reused a window page"
    elif not detail["moe"].get("moe/bias_changed_frac"):
        ok = False
        detail["error"] = "the bias changed no token's chosen experts"
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


def experts_hit_a_step(config: dict, before: dict, after: dict):
    """Held experts a layer that a decode step's live rows reached, between
    two readings of `engine.metrics()` (`serve_ref.experts_hit_a_step`, by
    this configuration's count of expert layers)."""
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        hit = after["serving/held_experts_hit"] - before["serving/held_experts_hit"]
    except KeyError:
        return None
    layers = ob.widths(config)["Le"]
    return hit / (steps * layers) if steps > 0 and layers else None


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
    run_["kind"] = "serve_reason_ref"
    moe = dict((run_.get("greedy_check") or {}).get("moe") or {})
    spans = {"moe/held_experts_hit": (run_["counters"]["start"],
                                      run_["counters"]["end"])}
    if len(seen["tracer"].counters) == 2:
        spans["moe/held_experts_hit_traced"] = seen["tracer"].counters
    for name, (before, after) in spans.items():
        hit = experts_hit_a_step(cell.config, before, after)
        if hit is not None:
            moe[name] = hit
    run_["moe"] = moe
    run_["traced_counters"] = seen["tracer"].counters
    if moe.get("moe/dropped_tokens"):
        result.why_not.append("moe/dropped_tokens of the scoring forward: "
                              f"{moe['moe/dropped_tokens']}")
    end = run_["counters"]["end"]
    if end.get("serving/window_layers") != window_layers(cell.config):
        result.why_not.append(
            f"the engine has {end.get('serving/window_layers')} window "
            f"layers, the file {window_layers(cell.config)}")
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
            "counters": {k: float(between[1][k] - between[0][k]) for k in (
                "serving/decode_steps", "serving/held_experts_hit",
                "serving/global_slots_read", "serving/window_slots_read",
                "serving/rows_past_window",
                "serving/window_pages_reused_in_decode", "serving/loop_beats")
                if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
