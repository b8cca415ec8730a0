"""Driver for mixes of kind `serve_loop_ref`: `drivers/serve.py`'s open-loop
serving run for a LOOPED model (Ouro, docs/OURO.md): one shared stack of
layers that every token passes `total_ut_steps` times, the final norm after
each pass, a cache slot a pass a layer; served whole on one chip, its
numerics held to the float32 reference the configuration names.

The window (`serve.measure`, `serve.run`, `client_metrics`), the `eos_unreachable`
weights (`serve_ref.init_weights`) and the counters read inside the trace
(`serve_mix_ref.InsideTrace`) are theirs, by import. This module's own:

- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's loop
  (`refuse_a_program_without_the_model`: `loop_passes`, `cache_layers`, the
  branch norms, no attention bias): a parent commit that drops
  `total_ut_steps` would serve a 48-layer model run once under the
  configuration's name, and exits 4 within seconds instead;
- the weights are `init_params`' with the configuration's `assumed.init`
  laid over them (`spread`): every stacked kernel rescaled to 1 / sqrt(fan-in)
  (a stacked kernel is drawn at 1 / sqrt(layers), which at 2,048 wide
  saturates every softmax, and bf16 then follows float32 nowhere), every
  norm's weight exp(N(0, s)) so that a model without one of its five norms
  is another model, the branch norms' times 0.3 (at weight 1 every pass
  starts from a state of RMS 1 and its first layers each move it by its own
  size: two bf16 paths then part from one another almost as far as from
  float32, and a verdict's ratio of gaps swings with the seed), the
  embedding at RMS 1;
- `check_cached`, BEFORE the engine is built (the memory is free then): the
  program's cached path on a contiguous cache at the cell's sizes (a prefill
  of `engine.prompt_len` tokens, then `cached_steps` decode steps through
  all `cache_layers`, teacher-forced on its own argmax) against the float32
  reference, under `agreement.bf16_agreement`'s unchanged limits: LOGITS,
  not tokens;
- `check_greedy`, through the TIMED engine at the timed sizes: what it served
  greedily against the reference's logits on the served context
  (`agreement.follows_greedy`, unchanged limits), in two verdicts: `steady`
  (the mix's `steady_lengths`, one prompt a row of the engine, asked AT ONCE,
  so decode chunks run beside admissions; `steady_waves` such waves, because
  a row's gaps hang together for a hundred tokens on end and a verdict of 8
  rows read up to 0.91 of its limit where 24 rows of the same kind are
  steady) and `full` (`full_rows` prompts of
  `engine.prompt_len` tokens served to their `engine.max_new_tokens`-th new
  token: every page of every cache layer of a row is written and read).
  Every row of a verdict is left-padded to the width the MIX fixes
  (`engine.prompt_len` + the verdict's new tokens), never to what a seed
  drew, so the reference's and the plain path's programs are the same for
  every seed (PR 53's refused check compiled 315-348 s a warm run);
- `correct` also needs `serving/loop_passes_per_token` = the file's
  `total_ut_steps`, `serving/cache_layers` = passes x layers and
  `serving/kv_bytes_per_token` = harness/ops_bytes_ouro's;
- the warm-up is a request a suffix bucket of the mix's prompt lengths (a
  prompt is one piece: the engine runs no chunked prefill), greedy and
  sampled in turn;
- `start(..., other_model=, weights=)` builds the engine for ANOTHER model or
  over other weights (benchmark/tools/loop_control.py: the comparison must
  be able to fail); `keep` takes what the verdicts were made of.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import sys
import time

import numpy as np

from drivers import serve, serve_mix_ref, serve_ref
from drivers.rl_ref import substituted
from harness import agreement, model, trafficgen
from harness import ops_bytes_ouro as ob
from harness.window import Meter, annotate, memory_by_device

measure = serve.measure
client_metrics = serve.client_metrics

NORMS = ("input_layernorm", "post_attention_layernorm", "attn_branch_norm",
         "mlp_branch_norm")


def wanted_of(config: dict) -> dict:
    """ModelConfig attribute -> what the file's keys make it."""
    passes = int(config["total_ut_steps"])
    return {"loop_passes": passes,
            "cache_layers": passes * int(config["num_hidden_layers"]),
            "branch_norms": True, "attention_bias": False}


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    try:
        mcfg = model.model_config(cell.config)
        lacking = {k: (v, getattr(mcfg, k, None))
                   for k, v in wanted_of(cell.config).items()
                   if getattr(mcfg, k, None) != v}
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
    `fan_in` (every stacked kernel `[L, in, out]` times sqrt(L / in): std
    1 / sqrt(fan-in)), `norm_log_std` (every weight of the four norms a layer
    and of the final norm exp(N(0, that))), `branch_norm` (the two BRANCH
    norms' weights times that: a branch then joins the stream at that RMS and
    not at 1, so a pass's first layers, which start from a state of RMS 1,
    move it by a third and not by its own size), `embed_tokens` (the
    embedding times that), from the seed."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 55), 8))
    # each leaf where it lies: op by op, a stacked kernel's float32 copies
    # (2.2 GB each) were the process's high-water mark, 15.7 GB before the
    # engine was even built (my chip runs, PR 55)
    in_place = jax.jit(lambda w, f: (w.astype(jnp.float32) * f).astype(w.dtype),
                       donate_argnums=0)
    scaled = lambda w, f: in_place(w, jnp.float32(f))    # noqa: E731
    log_normal = lambda w, std: jnp.exp(float(std) * jax.random.normal(  # noqa: E731
        next(keys), w.shape)).astype(w.dtype)
    layers = params["layers"]
    if init.get("fan_in"):
        for name, leaf in layers.items():
            if isinstance(leaf, dict) and "kernel" in leaf:
                L, fan = leaf["kernel"].shape[:2]
                leaf["kernel"] = scaled(leaf["kernel"], (L / fan) ** 0.5)
    if init.get("norm_log_std"):
        for name in NORMS:
            layers[name] = log_normal(layers[name], init["norm_log_std"])
        params["norm"] = log_normal(params["norm"], init["norm_log_std"])
    if init.get("branch_norm"):
        for name in NORMS[2:]:
            layers[name] = scaled(layers[name], init["branch_norm"])
    if init.get("embed_tokens"):
        params["embed_tokens"] = scaled(params["embed_tokens"],
                                        init["embed_tokens"])
    return params


def weights_of(cell, mcfg, seed: int):
    """The cell's weights from the seed: `serve_ref.init_weights` (the
    `eos_unreachable` head) with the configuration's `assumed.init` laid over
    them."""
    return spread(serve_ref.init_weights(mcfg, seed, model.dtype_of(cell.config),
                                         cell.traffic),
                  cell.config["assumed"].get("init"), seed)


def warm_up(port: int, mix: dict, seed: int, vocab: int) -> int:
    """Every shape the window's traffic can use: a request a suffix bucket of
    the mix's prompt lengths (a prompt is one piece), greedy and sampled in
    turn (`serve.warm_up` samples only where tenants share a prefix), and a
    pair of equal length whose shared prefix ends inside a page: the mix has
    no tenants, but two of its random prompts can still begin alike (they do
    at a test's vocabulary), and the copy-on-write page copy must not be new
    to the process then."""
    rng = np.random.default_rng([seed, 77])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, n).tolist()  # noqa: E731
    p = mix["prompt_len"]
    lengths = serve.buckets(int(p["min"]), int(p["max"]))
    for i, length in enumerate(lengths):
        serve.post(port, {"tokens": draw(length), "greedy": i % 2 == 0,
                          "temperature": 0.8, "top_p": 0.95, "max_tokens": 6})
    length = int(p["max"]) * 3 // 4
    prefix = draw(length // 2 - 1)
    for _ in range(2):
        serve.post(port, {"tokens": prefix + draw(length - len(prefix)),
                          "greedy": True, "max_tokens": 6})
    return len(lengths) + 2


def cached_program(mcfg, ctx: int, steps: int, page: int = 0):
    """`jit(params, ids [1, ctx]) -> (logits [steps + 1, V] float32, tokens
    [steps + 1])`: a prefill, then `steps` decode steps fed their own argmax,
    through a contiguous cache or (`page` > 0) through the row's pages of
    that size under a table (the row's pages in reverse order), the session's
    layout and read; the cache in the weights' dtype."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.core import model as M

    width = -(-(ctx + steps) // page) * page if page else ctx + steps

    @jax.jit
    def cached(p, ids):
        dtype, kw = p["embed_tokens"].dtype, {}
        if page:
            # (the pages in reverse: a table that is not the identity)
            kw = dict(page_table=jnp.arange(width // page, dtype=jnp.int32)[None, ::-1],
                      page_size=page)
            caches = M.init_paged_kv_cache(mcfg, width // page, page, dtype)
            logits, caches = M.prefill(p, mcfg, ids, jnp.ones_like(ids, bool),
                                       caches, logical_len=width, **kw)
        else:
            caches = M.init_kv_cache(mcfg, 1, width, dtype)
            logits, caches = M.prefill(p, mcfg, ids, jnp.ones_like(ids, bool),
                                       caches)
        key_mask = jnp.zeros((1, width), bool).at[:, :ctx].set(True)

        def step(carry, i):
            logits, caches, key_mask = carry
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key_mask = key_mask.at[:, ctx + i].set(True)
            at = jnp.full((1,), ctx, jnp.int32) + i
            new, caches = M.decode_step(
                p, mcfg, token, at, at if page else ctx + i, key_mask, caches,
                **kw)
            return (new, caches, key_mask), (logits[0], token[0])

        (last, _, _), (rows, tokens) = jax.lax.scan(
            step, (logits, caches, key_mask), jnp.arange(steps))
        return (jnp.concatenate([rows, last]).astype(jnp.float32),
                jnp.concatenate([tokens, jnp.argmax(last, -1).astype(jnp.int32)]))

    return cached


def check_cached(params, mcfg, cell, seed: int, steps: int | None = None,
                 paged: bool = False) -> tuple:
    """(ok, detail): the program's cached path (`cached_program`) against the
    float32 reference, on logits, under `agreement.bf16_agreement`; the plain
    path (the uncached forward under `attention_impl="xla"`) is the
    yardstick, measured in the same run. The set-up reads a contiguous cache
    for the mix's `cached_steps`; tools/loop_control.py's witness reads
    `paged`, to the row's last slot."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.core import model as M

    reference = importlib.import_module("harness." + cell.config["reference"])
    mix = cell.traffic
    chk, pad = mix["greedy_check"], int(mix["pad_token_id"])
    ctx = int(mix["engine"]["prompt_len"])
    steps = int(chk["cached_steps"] if steps is None else steps)
    rng = np.random.default_rng([seed, 79])
    prompt = jnp.asarray(rng.integers(trafficgen.FIRST_TOKEN_ID,
                                      mcfg.vocab_size, (1, ctx)), jnp.int32)
    tested, tokens = cached_program(
        mcfg, ctx, steps, int(mix["engine"]["page_size"]) if paged else 0)(
            params, prompt)
    seq = jnp.concatenate([prompt, tokens[None, :]], axis=1)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: reference.logits(
            p, cell.config, x, pad, last=steps + 2,
            mask=jnp.ones_like(x, bool)))(params, seq)[0, :-1]
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    plain = jax.jit(lambda p, x: M.padded_forward_logits(
        p, plain_mcfg, x, -1, response_context_length=ctx))(params, seq)[0]
    tested, plain, ref = (np.asarray(a, np.float32)
                          for a in (tested, plain, ref))
    ok, detail = agreement.bf16_agreement(tested, plain, ref,
                                          np.ones(ref.shape, bool))
    detail.update(prompt=ctx, decode_steps=steps,
                  cache_layers=int(mcfg.cache_layers))
    if paged:   # (the witness: its own argmax under the reference as well)
        detail["greedy"] = agreement.follows_greedy(ref, np.asarray(tokens),
                                                    plain)[1]
    return ok, detail


def check_greedy(port: int, engine, params, mcfg, cell, seed: int,
                 keep: dict | None = None) -> tuple:
    """(ok, detail): the two verdicts of the module docstring."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    from nanorlhf_tpu.core.model import padded_forward_logits

    reference = importlib.import_module("harness." + cell.config["reference"])
    mix = cell.traffic
    chk, eng = mix["greedy_check"], mix["engine"]
    pad, longest = int(mix["pad_token_id"]), int(eng["prompt_len"])
    rng = np.random.default_rng([seed, 78])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, mcfg.vocab_size,  # noqa: E731
                                  int(n)).tolist()
    waves = int(chk.get("steady_waves", 1))
    steady = [draw(n) for _ in range(waves) for n in chk["steady_lengths"]]
    full = [draw(longest) for _ in range(int(chk["full_rows"]))]
    n_steady, n_full = int(chk["steady_max_tokens"]), int(eng["max_new_tokens"])
    assert max(len(p) for p in steady) <= longest
    ask = lambda p, n: serve.post(port, {"tokens": p, "greedy": True,   # noqa: E731
                                         "max_tokens": n})
    took: dict = {}

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        try:
            yield
        finally:
            took[name] = took.get(name, 0.0) + time.perf_counter() - t

    before = engine.metrics()
    rows = len(chk["steady_lengths"])
    with timed("served"), ThreadPoolExecutor(max(rows, len(full))) as pool:
        served_steady = [answer for at in range(0, len(steady), rows)
                         for answer in pool.map(lambda p: ask(p, n_steady),
                                                steady[at:at + rows])]
        served_full = list(pool.map(lambda p: ask(p, n_full), full))
    after = engine.metrics()
    lengths = [len(s) for s in served_steady + served_full]
    wanted = [n_steady] * len(steady) + [n_full] * len(full)
    if lengths != wanted:
        return False, {"error": "a greedy answer is short (eos_unreachable "
                       "mixes yield their budget)", "lengths": lengths}

    def padded(batch, answers, n):
        """The rows left-padded to the VERDICT's width, the mix's and no
        seed's, in parts of `rows_at_once` rows (one shape a verdict: the
        comparison's temporaries lie beside the engine's weights and pool)."""
        width = longest + n
        seqs = np.full((len(batch), width), pad, np.int32)
        real = np.zeros(seqs.shape, bool)
        for i, (p, s) in enumerate(zip(batch, answers)):
            seqs[i, width - len(p) - n:] = p + s
            real[i, width - len(p) - n:] = True
        rows = min(len(batch), int(chk.get("rows_at_once", len(batch))))
        assert len(batch) % rows == 0, (len(batch), rows)
        return [(jnp.asarray(seqs[at:at + rows]), jnp.asarray(real[at:at + rows]))
                for at in range(0, len(batch), rows)]

    def reference_logits(batch, answers, n, **flags):
        """The float32 reference's logits at the answers' positions
        (`flags`: its negative controls)."""
        program = jax.jit(lambda p, x, m: reference.logits(
            p, cell.config, x, pad, last=n + 1, mask=m, **flags)[:, :-1])
        with timed("reference"), jax.default_matmul_precision("highest"):
            ref = np.concatenate([np.asarray(program(params, seqs, real))
                                  for seqs, real in padded(batch, answers, n)])
        return ref.reshape(-1, ref.shape[-1])

    def plain_logits(weights, batch, answers, n, **other_model):
        """The plain bf16 path's logits there (`other_model`: fields of the
        `ModelConfig` that a control's model has otherwise)."""
        plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla",
                                         **other_model)
        program = jax.jit(lambda p, x: padded_forward_logits(
            p, plain_mcfg, x, pad, response_context_length=longest).astype(
                jnp.float32))
        with timed("plain"):
            plain = np.concatenate([np.asarray(program(weights, seqs))
                                    for seqs, _ in padded(batch, answers, n)])
        return plain.reshape(-1, plain.shape[-1])

    def verdict(name, batch, answers, n):
        ref = reference_logits(batch, answers, n)
        plain = plain_logits(params, batch, answers, n)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n}
        return agreement.follows_greedy(ref, tokens, plain)

    ok, detail = verdict("steady", steady, served_steady, n_steady)
    ok_full, detail["full"] = verdict("full", full, served_full, n_full)
    ok = ok and ok_full
    if keep is not None:
        keep.update(reference_logits=reference_logits,
                    plain_logits=plain_logits, params=params)
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))     # noqa: E731
    detail.update(
        seconds={k: round(v, 1) for k, v in took.items()},
        widths={"steady": longest + n_steady, "full": longest + n_full},
        prefix_hit_tokens=gain("serving/prefix_hit_tokens"),
        decode_steps=gain("serving/decode_steps"),
        pool_live_frac=gain("serving/pool_live_slots") / max(
            gain("serving/pool_reserved_slots"), 1))
    return ok, detail


def start(cell, opts, keep: dict | None = None, other_model: dict | None = None,
          weights=None, cached: bool = True) -> serve.Served:
    """Set-up up to the child's ramp: the refusal, weights, the cached
    path's comparison, the engine, the gateway, the warm-up, the greedy
    comparison, the hub's reset. `other_model`: `ModelConfig` fields of
    ANOTHER model the engine is built for over the same weights;
    `weights(params)`: what to serve instead of them (both:
    tools/loop_control.py)."""
    from nanorlhf_tpu.serving.engine import ServingEngine
    from nanorlhf_tpu.serving.gateway import ServingGateway
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    refuse_a_program_without_the_model(cell)
    mix, seed = cell.traffic, int(opts["seed"])
    meter = Meter()
    mark0 = meter.mark()
    peak_gb = {}        # the process's high-water mark after each stage

    def noted(stage):
        peak_gb[stage] = round(max(memory_by_device(cell.chips)) / 1e9, 3)

    mcfg = model.model_config(cell.config)
    params = weights_of(cell, mcfg, seed)
    if weights is not None:
        params = weights(params)
    noted("weights")
    cached_ok, cached_detail = True, None
    if cached:
        with annotate("bench.warmup"):
            cached_ok, cached_detail = check_cached(params, mcfg, cell, seed)
    noted("cached")
    served_mcfg = dataclasses.replace(mcfg, **(other_model or {}))
    hub = LatencyHub(enabled=True)
    e = mix["engine"]
    engine = ServingEngine(
        params, served_mcfg, eos_token_id=int(mix["eos_token_id"]),
        pad_token_id=int(mix["pad_token_id"]), page_size=int(e["page_size"]),
        prompt_len=int(e["prompt_len"]), max_new_tokens=int(e["max_new_tokens"]),
        rows=int(e["rows"]), headroom=float(e["headroom"]),
        sync_every=int(e["sync_every"]), max_queue=int(e["max_queue"]),
        latency=hub, seed=seed)
    gateway = ServingGateway(engine, port=-1)
    noted("engine")
    try:
        with annotate("bench.warmup"):
            n_warm = warm_up(gateway.port, mix, seed, mcfg.vocab_size)
            noted("warm_up")
            greedy_ok, greedy = check_greedy(gateway.port, engine, params,
                                             mcfg, cell, seed, keep)
    except BaseException:
        gateway.close()
        engine.close()
        raise
    noted("greedy")
    greedy.update(cached=cached_detail, peak_hbm_gb=peak_gb)
    # the comparison's host arrays (1.7 GB of float32 logits) are garbage now;
    # what is left is the engine's, for the process's life: a full collection
    # inside the window is a pause every live request pays (a run of eight
    # lost 0.7 s to one pause and read tpot_p95_ms 46.9 against 39.8)
    gc.collect()
    gc.freeze()
    serve.reset_hub(hub)
    setup_compile = Meter.delta(mark0, meter.mark())
    counters = engine.metrics()
    print(json.dumps({
        "phase": "setup", "warmup_requests": n_warm, "greedy_check": greedy,
        "num_pages": engine.num_pages,
        "decode_attention": "pallas-paged" if counters["serving/attn_in_place"]
        else "xla-gathered-view",
        **{k: counters.get(k) for k in (
            "serving/kv_bytes_per_token", "serving/loop_passes_per_token",
            "serving/cache_layers", "serving/pool_donated")},
        **setup_compile}), flush=True)
    return serve.Served(engine, gateway, meter, mcfg.vocab_size, setup_compile,
                        bool(greedy_ok and cached_ok), greedy)


def run(cell, opts):
    seen = {}

    def started(cell, opts):
        seen["served"] = start(cell, opts)
        return seen["served"]

    def tracer(*args, **kwargs):
        seen["tracer"] = serve_mix_ref.InsideTrace(seen["served"].engine,
                                                   *args, **kwargs)
        return seen["tracer"]

    with substituted(serve, "start", started), \
            substituted(serve, "TraceWindow", tracer):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_loop_ref"
    run_["traced_counters"] = seen["tracer"].counters
    end = run_["counters"]["end"]
    for key, want in (
            ("serving/loop_passes_per_token", int(cell.config["total_ut_steps"])),
            ("serving/cache_layers", ob.cache_layers(cell.config)),
            ("serving/kv_bytes_per_token", ob.kv_bytes_per_token(
                cell.config, 4 if cell.config["assumed"]["dtype"] == "float32"
                else 2))):
        if end.get(key) != want:
            result.why_not.append(f"{key} = {end.get(key)}, the file's "
                                  f"configuration makes it {want}")
    result.correct = not result.why_not
    return result
