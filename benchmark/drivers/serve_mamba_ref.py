"""Driver for mixes of kind `serve_mamba_ref`: `drivers/serve.py`'s open-loop
serving run for a model whose state-space mixers stand ALONE in their layers
(a state and no pages) beside attention layers (pages and no state), a chip's
share of softmax-routed experts and a shared expert after every one (Granite
4.0-H, docs/GRANITE_H.md), under traffic of several CLASSES in one stream,
its numerics held to the float32 reference the configuration names.

`serve_ssm_ref.py` cannot take such a cell unedited: its refusal reads
Falcon-H1's multipliers, its weights rescale leaves this model's tree does
not have, its child draws one class of prompt, its `run` holds the state's
layers to `num_hidden_layers`, and it keeps no account of experts. The
window (`serve.measure`, `serve.run`, `client_metrics`), the engine's start
with `eos_unreachable` weights (`serve_ref.start`, `serve_ref.init_weights`),
the warm-up of every suffix bucket and the chunk forward
(`serve_mix_ref.warm_up`), the child that draws the mix's classes
(`serve_mix_ref.measure`) and the counters read inside the trace
(`serve_mix_ref.InsideTrace`) are theirs, by import; and so is the whole
comparison: `serve_ssm_ref.check_greedy`, by call, with this configuration's
reference (its four verdicts `long`, `short`, `carry`, `reuse` under
`agreement.follows_greedy`'s unchanged limits, the engine's recurrent state
read where it lies against the reference's after the same tokens, the
state's carries and resets counted, no prefix hit; the same `greedy_check`
keys and the same `keep`). This module's own:

- the weights are `init_params`' (kernels at std 1 / sqrt(fan-in), `A_log`
  and `dt_bias` as Mamba-2 draws them) with the configuration's
  `assumed.init` laid over them (`spread`): the embedding and the final
  norm's weight scaled so that the TIED head does not make the model repeat
  its input, `q_proj` so that attention under the published scale picks
  keys, and `D`, the convolution's bias and the mixer's norm away from ones
  and zeros (the file's `assumed.weights` has the arithmetic);
- `STATE_LIMIT`, this model's, in `serve_ssm_ref.state_reading`'s place of
  its own;
- the plain bf16 path's experts go through the grouped-matmul kernel on a
  TPU (`plain_experts`: XLA's `ragged_dot` does not compile there at this
  model's 360 groups);
- the router's counters of a chip's share (`moe`): one scoring forward of
  the plain path over the `short` verdict's first row with
  `router_stats`, reduced by `ops/moe.moe_counters` for the held experts;
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's mixer, layer kinds,
  experts, share and multipliers (`refuse_a_program_without_the_model`): a
  parent commit that cannot build the configuration exits 4 within seconds;
- `correct` also needs `moe/dropped_tokens == 0` and the engine's
  `serving/state_layers`, `serving/page_layers`,
  `serving/state_bytes_per_row` and `serving/kv_bytes_per_token` to be what
  the file's layers hold. The run's artefacts gain `moe` (with
  `moe/held_experts_hit`, the held experts a layer that a decode step's live
  rows reached in the window, counted on the device), `traced_counters`
  and, traced, `moe_trace` and `attn_trace`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

from drivers import serve, serve_mix_ref, serve_ref, serve_ssm_ref
from drivers.rl_ref import substituted
from harness import model
from harness import ops_bytes_granite_h as ob

measure = serve_mix_ref.measure
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_n_groups": "ssm_groups", "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv", "mamba_chunk_size": "ssm_chunk",
    "num_local_experts": "num_experts", "num_experts_held": "experts_held",
    "num_experts_offset": "experts_offset",
    "num_experts_per_tok": "num_experts_per_tok",
    "intermediate_size": "intermediate_size",
    "shared_intermediate_size": "shared_expert_width",
    "embedding_multiplier": "embed_scale",
    "attention_multiplier": "attention_multiplier",
    "residual_multiplier": "residual_scale",
}
# `serve_ssm_ref.state_reading`'s limit for THIS model, between its two
# readings on the chip (my chip runs, PR 59; PERF.md section 6): the engine
# as it is, a float32 state under bfloat16 activations, reads 1.4e-3 to
# 2.0e-3 on the `long` row and on the `carry` row over fourteen seeds, once
# 2.9e-3 (`chiprun_out/c59_1`, `c59_3`, `c59_4`); the state kept in
# bfloat16, the nearest precision below, 7.9e-3 on `carry` (some thirty
# decode steps) and 1.6e-2 on `long` (255) (`benchmark/tools/mamba_control.py`,
# seed 2147483777, `c59_5b`). Falcon-H1's limit, and for its reason: the
# first layer's slowest heads sum independent roundings of bfloat16
# activations, a state rounded as it is stored adds one a token.
STATE_LIMIT = 4.5e-3


def _itemsize(config: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[config["assumed"]["dtype"]]


def state_bytes_per_row(config: dict) -> int:
    return ob.state_bytes_per_row(config, _itemsize(config))


def kv_bytes_per_token(config: dict) -> int:
    return ob.kv_bytes_per_token(config, _itemsize(config))


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        w = ob.widths(cfg)
        for attr, want in (("mamba_layers", w["Lm"]), ("ssm_layers", w["Lm"]),
                           ("page_layers", w["La"]),
                           ("lm_head_multiplier",
                            1.0 / float(cfg["logits_scaling"]))):
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


def spread(params, init: dict | None, seed: int):
    """The configuration's `assumed.init` laid over `init_params`' weights:
    `embed_tokens` (the embedding, which is the head, times that),
    `final_norm` (the final norm's weight times that), `q_proj` (the
    queries' kernel times that, rescaled where it lies), `D_std` (`D` ~ N(1,
    that)), `conv_bias_std` (the bias ~ N(0, that)) and `ssm_norm_log_std`
    (the mixer's norm weight exp(N(0, that))), from the seed."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    scaled = lambda w, s: (w.astype(jnp.float32) * float(s)).astype(w.dtype)  # noqa: E731
    layers = params["layers"]
    ssm = layers["ssm"]
    if init.get("embed_tokens"):
        params["embed_tokens"] = scaled(params["embed_tokens"],
                                        init["embed_tokens"])
    if init.get("final_norm"):
        params["norm"] = scaled(params["norm"], init["final_norm"])
    if init.get("q_proj"):
        layers["q_proj"]["kernel"] = scaled(layers["q_proj"]["kernel"],
                                            init["q_proj"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 59), 3)
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


def plain_experts():
    """What the plain bf16 path's experts go through while this is open: on
    a TPU the grouped-matmul kernel the engine runs anyway, elsewhere what
    they always did. XLA:TPU fails to compile `ragged_dot` over this model's
    360 groups (ten layers' 36 held experts, addressed in the stacks where
    they lie) inside the long row's program (`INTERNAL: Bitcast cannot have
    different shape sizes`, at 4,355 tokens and not at 1,000; compiled for a
    described v5e, PR 59): the plain path is then XLA's attention and scan
    around the kernel's experts."""
    import jax

    from nanorlhf_tpu.core import model as M

    sound = M.use_expert_kernel
    return substituted(M, "use_expert_kernel", lambda config: (
        jax.default_backend() == "tpu" or sound(config)))


def router_counters(params, mcfg, cell, row) -> dict:
    """`ops/moe.moe_counters` of the plain path's scoring forward over one
    prompt, for the chip's share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanorlhf_tpu.core.model import padded_forward_logits
    from nanorlhf_tpu.ops.moe import moe_counters

    pad = int(cell.traffic["pad_token_id"])
    plain = dataclasses.replace(mcfg, attention_impl="xla")
    _, stats = jax.jit(lambda p, x: padded_forward_logits(
        p, plain, x, pad, response_context_length=1, router_stats=True))(
            params, jnp.asarray([row], jnp.int32))
    held = ((mcfg.experts_held, mcfg.experts_offset)
            if mcfg.experts_held else None)
    return moe_counters([jax.tree.map(np.asarray, stats)], held=held)


def check_greedy(port: int, engine, params, mcfg, cell, seed: int,
                 keep: dict | None = None) -> tuple:
    """(ok, detail): `serve_ssm_ref.check_greedy` under this model's
    `STATE_LIMIT`, and the router's counters beside it."""
    own = {} if keep is None else keep
    with substituted(serve_ssm_ref, "STATE_LIMIT", STATE_LIMIT), \
            plain_experts():
        ok, detail = serve_ssm_ref.check_greedy(port, engine, params, mcfg,
                                                cell, seed, own)
        if "short" in own:
            detail["moe"] = router_counters(params, mcfg, cell,
                                            own["short"]["batch"][0])
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
    this configuration's count of layers: every one has experts)."""
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        hit = after["serving/held_experts_hit"] - before["serving/held_experts_hit"]
    except KeyError:
        return None
    layers = ob.widths(config)["L"]
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
            substituted(serve, "TraceWindow", tracer), \
            substituted(serve, "CHILD", serve_mix_ref.CHILD):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_mamba_ref"
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
    w = ob.widths(cell.config)
    end = run_["counters"]["end"]
    for key, want in (("serving/state_layers", w["Lm"]),
                      ("serving/page_layers", w["La"]),
                      ("serving/state_bytes_per_row",
                       state_bytes_per_row(cell.config)),
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
            "counters": {k: float(between[1][k] - between[0][k]) for k in (
                "serving/decode_steps", "serving/live_row_steps",
                "serving/held_experts_hit", "serving/global_slots_read",
                "serving/state_resets", "serving/state_piece_carries",
                "serving/state_tokens", "serving/loop_beats")
                if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
