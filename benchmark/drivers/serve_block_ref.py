"""Driver for mixes of kind `serve_block_ref`: `drivers/serve.py`'s open-loop
serving run for a model that GENERATES BY DIFFUSION OVER BLOCKS (SDAR,
docs/BLOCKDIFF.md): every forward after the prompt runs a block of four
tokens a row, rows stand at different denoise steps of different blocks, and
a request chooses its denoising steps. Its numerics are held to the float32
reference the configuration names (harness/reference_sdar.py).

No driver that is there takes such a cell unedited: their comparisons
teacher-force NEXT-token logits of a causal model, their children post no
`denoising_steps`, and their refusals read other models' keys. The window
(`serve.measure`, `serve.run`, `client_metrics`), the engine's start with
`eos_unreachable` weights (`serve_ref.start`, `serve_ref.init_weights`), the
rescaling of `init_params`' stacked kernels (`serve_mix_ref.spread`) and the
counters read inside the trace (`serve_mix_ref.InsideTrace`) are theirs, by
import. This module's own:

- the child is `harness/loadgen_child_block.py`: `loadgen_child`'s, with a
  request's `denoising_steps` / `remasking` in its body and its budget from
  the mix's `max_tokens_choices`; a run in which any body went without the
  two keys is not `correct` (the child counts them in its summary);
- the weights: `serve_mix_ref.spread` (every stacked kernel at std
  1 / sqrt(fan-in)) and, over it, this configuration's `assumed.init`: norm
  weights exp(N(0, s)), the per-head ones too and the queries' scaled, an
  embedding of RMS 1, a head whose logits spread enough that a token's
  confidence is neither 1 nor 1 / V (`spread`);
- the warm-up: every power-of-two bucket a prompt's whole blocks can end in,
  the chunk forward, and the block chunk under both kinds of sampling;
- the comparison (`check`, what decides `correct` for numerics): a served
  token is conditioned on WHICH tokens of its block were already unmasked,
  so the reference REPLAYS each denoise forward as it stood (the response
  carries, a token, the step of its block that unmasked it, and what was cut
  past the budget). All asked at once through the gateway, so that rows at
  different steps share forwards: `long_lengths` greedy prompts with
  `long_max_tokens` new tokens (two prefill pieces; prompt tails 0 and 1),
  `short_rows` greedy prompts of `short_len` + 0..3 tokens (every tail) with
  `short_max_tokens`, split between 4 and 2 denoising steps, and
  `dynamic_rows` under `low_confidence_dynamic`. Two verdicts (`long`,
  `short`), each under `agreement.GAP_SLACK`, unchanged, twice: (a) the gap
  of every served token under the reference's top at ITS position in ITS
  forward against the plain bf16 path's own argmax's gap there; (b) how far
  the reference's confidence of a chosen position lies under its best
  then-masked position, against the same measured on the plain path's own
  choice. The plain path is the program's UNCACHED forward
  (`padded_forward_hidden`, `attention_impl="xla"`) over each forward's whole
  sequence as it stood, on at most `plain_forwards` forwards a row (evenly
  spaced blocks; the cell's mix takes every one of a long row's 512: a
  thin sample of the plain path swings the limits it sets; a short row's
  forwards run 8 a call, a long row's one a call). It also needs `serving/commit_forwards`
  = `serving/blocks_done`, `serving/tokens_unmasked` = tokens streamed + cut,
  no prefix hit and `moe/dropped_tokens` = 0;
- `keep`, where given, takes what the verdicts were made of and the function
  that recomputes them against a control (tools/block_control.py: the
  comparison must be able to fail);
- it fails at once, non-zero and before any weights are built, when the
  program does not build the file's model with its experts, per-head norms,
  block mask and block generation (`refuse_a_program_without_the_model`): a
  parent commit that cannot build the configuration exits 4 within seconds;
- the run's artefacts gain `moe`, `traced_counters`, `block` (the block
  counters' gain over the window) and, traced, `moe_trace`, `block_trace`
  and a `scope_trace` reduced with the `sample.` family kept (the harness's
  own reduction reads `sample.unmask` as `sample`).
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
from harness import agreement, model, trafficgen
from harness import ops_bytes_sdar as ob

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(os.path.dirname(HERE), "harness", "loadgen_child_block.py")
client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "num_experts": "num_experts", "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "norm_topk_prob": "norm_topk_prob", "head_dim": "head_dim",
    "num_key_value_heads": "num_key_value_heads",
}
PLAIN_CALL_TOKENS = 4096    # the plain path batches forwards up to this
BLOCK_COUNTERS = ("serving/block_forwards", "serving/commit_forwards",
                  "serving/tokens_unmasked", "serving/blocks_done",
                  "serving/prompt_tail_tokens", "serving/tokens_streamed",
                  "serving/tokens_cut", "serving/decode_steps",
                  "serving/held_experts_hit", "serving/attn_live_pages")


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model and
    generates by blocks."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        assumed = cfg["assumed"]
        for attr, want in (("qk_norm_per_head", True),
                           ("block_generation", True),
                           ("block_length", int(assumed["block_length"])),
                           ("mask_token_id", int(assumed["mask_token_id"]))):
            if getattr(mcfg, attr, None) != want:
                lacking[attr] = (want, getattr(mcfg, attr, None))
        from nanorlhf_tpu.core import model as program

        if not hasattr(program, "block_forward"):
            lacking["core.model.block_forward"] = ("a function", None)
        why = f"file against ModelConfig: {lacking}" if lacking else None
    except (ValueError, TypeError, NotImplementedError) as e:
        why = f"{type(e).__name__}: {e}"
    if why:
        print(f"benchmark: configuration {cell.config_name!r} is not a model "
              f"this program builds ({why}). Nothing was built.",
              file=sys.stderr)
        raise SystemExit(4)


def spread(params, init: dict | None, seed: int):
    """`serve_mix_ref.spread` (stacked kernels at std 1 / sqrt(fan-in), the
    embedding's factor) and, over it, from the seed: `norm_log_std` (the two
    norms' weights exp(N(0, that))), `qk_norm_log_std` (the per-head norms'
    alike), `q_norm` (the queries' per-head norm times that, after its draw:
    the attention scores' spread), `lm_head` (the head times that: the
    logits' spread, and with it a token's confidence)."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    params = serve_mix_ref.spread(params, init)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 46), 8))
    layers = params["layers"]

    def log_normal(w, std):
        return jnp.exp(float(std) * jax.random.normal(
            next(keys), w.shape)).astype(w.dtype)

    def scaled(w, factor):
        return (w.astype(jnp.float32) * float(factor)).astype(w.dtype)

    for names, key in ((("input_layernorm", "post_attention_layernorm"),
                        "norm_log_std"),
                       (("q_norm", "k_norm"), "qk_norm_log_std")):
        if init.get(key):
            for name in names:
                layers[name] = log_normal(layers[name], init[key])
    if init.get("q_norm"):
        layers["q_norm"] = scaled(layers["q_norm"], init["q_norm"])
    if init.get("lm_head"):
        params["lm_head"] = jax.jit(
            scaled, static_argnums=1, donate_argnums=0)(
                params["lm_head"], float(init["lm_head"]))
    return params


def warm_up(port: int, mix: dict, seed: int, vocab: int) -> int:
    """Every shape the window's traffic can use: each power-of-two bucket
    that what is left of a prompt's whole blocks can end in, from one block
    to the chunk (a prompt of `bucket + 1` tokens where the mix has such a
    prompt, else one piece and the bucket: so the chunk forward runs too),
    and the block chunk, greedy and sampled, at both counts of steps."""
    rng = np.random.default_rng([seed, 77])
    chunk = int(mix["engine"]["prefill_chunk"])
    lo, block = int(mix["prompt_len"]["min"]), int(mix["block_length"])
    buckets = [b for b in serve_ref.suffix_buckets(chunk) if b >= block]
    lengths = sorted({b + 1 if b + 1 >= lo else chunk + b + 1
                      for b in buckets} | {chunk + block + 1})
    steps = [int(s) for s in mix["denoising_steps_choices"]]
    for i, length in enumerate(lengths):
        serve.post(port, {
            "tokens": rng.integers(trafficgen.FIRST_TOKEN_ID, vocab,
                                   length).tolist(),
            "greedy": i % 2 == 0, "temperature": 0.8, "top_p": 0.95,
            "max_tokens": 9, "denoising_steps": steps[i % len(steps)],
            "remasking": mix["remasking"]})
    return len(lengths)


def post(port: int, spec: dict, timeout: float = 900.0) -> dict:
    """`serve.post`, the whole response (a block model's carries more than
    its tokens)."""
    import urllib.request

    return json.loads(urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(spec).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout).read())


def chosen_by_rule(conf, masked, n: int, strategy: str, threshold: float):
    """docs/BLOCKDIFF.md's rule on the confidences at hand (numpy): the
    positions a forward unmasks, as a mask."""
    idx = np.flatnonzero(masked)
    order = idx if strategy == "sequential" else idx[np.lexsort(
        (idx, -conf[idx]))]
    out = np.zeros(len(conf), bool)
    out[order[:n]] = True
    if strategy == "low_confidence_dynamic":
        out |= masked & (conf > threshold)
    return out


def shortfall(conf, masked, chosen):
    """How far the confidence of the least sure chosen position lies under
    the best masked position left unchosen; 0 where none is left or the
    chosen are the best. `conf`, `masked`, `chosen` [N, B]."""
    rest = masked & ~chosen
    best_left = np.where(rest, conf, -np.inf).max(axis=1)
    least_chosen = np.where(chosen, conf, np.inf).min(axis=1)
    ok = rest.any(axis=1) & chosen.any(axis=1)
    return np.where(ok, np.maximum(best_left - least_chosen, 0.0), 0.0)


def within_slack(tested, plain) -> bool:
    """`agreement.follows_greedy`'s rule (GAP_SLACK, its two margins)."""
    mean, worst = agreement.GAP_SLACK
    return bool(tested.mean() <= mean * plain.mean() + 1e-3
                and tested.max() <= worst * plain.max() + 5e-2)


def judge(rows: list) -> tuple:
    """(ok, detail) of one verdict from its rows' replays (`replayed`)."""
    cat = lambda k: np.concatenate([r[k] for r in rows])    # noqa: E731
    gap, plain_gap = cat("gap"), cat("plain_gap")
    short, plain_short = cat("short"), cat("plain_short")
    ok = within_slack(gap, plain_gap) and within_slack(short, plain_short)
    return ok, {"tokens": int(len(gap)), "flips": int((gap > 0).sum()),
                "plain_tokens": int(len(plain_gap)),
                "plain_flips": int((plain_gap > 0).sum()),
                "gap": agreement.stat(gap),
                "plain_gap": agreement.stat(plain_gap),
                "forwards": int(len(short)),
                "choice_shortfall": agreement.stat(short),
                "plain_choice_shortfall": agreement.stat(plain_short)}


def check(port: int, engine, params, mcfg, cell, seed: int,
          keep: dict | None = None) -> tuple:
    """(ok, detail): module docstring."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    from nanorlhf_tpu.core.model import padded_forward_hidden, unembedding_weight
    from nanorlhf_tpu.sampler import blockdiff

    reference = importlib.import_module("harness." + cell.config["reference"])
    mix, cfg = cell.traffic, cell.config
    chk = mix["greedy_check"]
    B, mask_id = mcfg.block_length, mcfg.mask_token_id
    vocab, pad = mcfg.vocab_size, int(mix["pad_token_id"])
    rng = np.random.default_rng([seed, 78])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, int(n)).tolist()  # noqa: E731
    static, dynamic = "low_confidence_static", "low_confidence_dynamic"
    asks = [(draw(n), int(chk["long_max_tokens"]), B, static, "long")
            for n in chk["long_lengths"]]
    for i in range(int(chk["short_rows"])):     # every tail, both step counts
        asks.append((draw(int(chk["short_len"]) + i % B),
                     int(chk["short_max_tokens"]),
                     B if (i // B) % 2 == 0 else B // 2, static, "short"))
    for i in range(int(chk["dynamic_rows"])):
        asks.append((draw(int(chk["short_len"]) + 1 + i),
                     int(chk["short_max_tokens"]), B, dynamic, "short"))
    before = engine.metrics()
    with ThreadPoolExecutor(len(asks)) as pool:
        # all at once: rows at different steps of different blocks share
        # forwards, the short rows beside the long rows' prefill pieces
        served = list(pool.map(lambda a: post(port, {
            "tokens": a[0], "greedy": True, "max_tokens": a[1],
            "denoising_steps": a[2], "remasking": a[3]}), asks))
    after = engine.metrics()
    lengths = [len(s["tokens"]) for s in served]
    if lengths != [a[1] for a in asks]:
        return False, {"error": "a greedy answer is short (eos_unreachable "
                       "mixes yield their budget)", "lengths": lengths}
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    file_cfg = {**cfg, "block_length": B, "mask_token_id": mask_id}
    threshold = blockdiff.CONFIDENCE_THRESHOLD
    programs: dict = {}     # the jitted reference and plain path, by flags

    def plain_path(weights, fwd, sub, **other_model):
        """The plain bf16 path at the B positions of the forwards `sub` of
        one row: (argmax [n, B], confidence [n, B]) and the router's stats.
        Each forward's whole sequence as it stood, left-padded to one width
        with one more token behind it (the next block's: invisible under the
        mask), so that the response slice is the block's own positions."""
        pcfg = dataclasses.replace(plain_mcfg, **other_model)
        n = len(sub)
        # (one width a size of row and whole multiples of 8 forwards, the
        # last repeated: rows of like sizes share one compilation)
        width = int(fwd["start"].max()) + B + 1
        width += -width % reference.T_PAD
        sub = np.concatenate([sub, np.repeat(sub[-1:], -len(sub) % 8)])
        # forwards a call: a short row's run 8 at a time (each forward
        # alone reads every expert's weights for ~170 tokens), a long
        # row's one at a time (the XLA form holds a layer's scores)
        per = 8 if 8 * width <= PLAIN_CALL_TOKENS else 1
        seqs = np.full((len(sub), width), pad, np.int64)
        for i, f in enumerate(sub):
            s = int(fwd["start"][f])
            seqs[i, width - 1 - s - B:width - 1 - B] = fwd["final"][:s]
            seqs[i, width - 1 - B:width - 1] = fwd["ids"][f]
            seqs[i, width - 1] = mask_id

        def one(p, rows):
            hidden, stats = padded_forward_hidden(
                p, pcfg, rows, pad, response_context_length=width - B,
                router_stats=True)
            lg = (hidden @ unembedding_weight(pcfg, p)).astype(jnp.float32)
            return (jnp.argmax(lg, axis=-1).astype(jnp.int32),
                    jnp.exp(lg.max(axis=-1)
                            - jax.nn.logsumexp(lg, axis=-1))), stats

        fn = programs.setdefault(
            ("plain", width, per, tuple(sorted(other_model.items()))),
            jax.jit(lambda p, x: jax.lax.map(lambda rows: one(p, rows), x)))
        (arg, conf), stats = fn(weights, jnp.asarray(
            seqs.reshape(-1, per, width), jnp.int32))
        # the router's stats a CALL (where a call holds 8 forwards, the
        # last call's may be the last forward's repeats)
        calls = [jax.tree.map(lambda a, i=i: np.asarray(a)[i], stats)
                 for i in range(-(-n // per))]
        flat = lambda a: np.asarray(a).reshape(-1, B)[:n]   # noqa: E731
        return flat(arg), flat(conf), calls

    def replayed(weights, ask, resp, controls=None, plain=None,
                 **other_model):
        """One row's part of a verdict: the served tokens' gaps and choice
        shortfalls under the reference's replay, and the plain path's own
        on the forwards it ran (`plain`: a sound reading's, kept)."""
        prompt, _, steps, strategy, _ = ask
        fwd = reference.forwards_of(
            prompt, resp["tokens"] + resp["cut_tokens"],
            resp["unmask_steps"] + resp["cut_unmask_steps"], B, mask_id)
        N = len(fwd["start"])
        blocks = np.unique(fwd["start"])
        most = max(int(chk["plain_forwards"]) // steps, 1)
        kept = blocks[np.linspace(0, len(blocks) - 1, min(most, len(blocks)))
                      .round().astype(int)]
        sub = np.flatnonzero(np.isin(fwd["start"], kept))
        if plain is None:
            plain = plain_path(weights, fwd, sub, **other_model)
        p_arg, p_conf, stats = plain
        probe = fwd["token"].copy()
        probe[sub] = p_arg
        with jax.default_matmul_precision("highest"):
            r = reference.replay(weights, file_cfg, None, None, None,
                                 probes=(probe,), fwd=fwd, programs=programs,
                                 **(controls or {}))
        conf = np.exp(r["top"] - r["lse"])
        n_s = np.asarray([blockdiff.transfer_count(int(s), steps, B)
                          for s in fwd["step"]])
        p_chosen = np.stack([chosen_by_rule(
            p_conf[i], fwd["masked"][n], int(n_s[n]), strategy, threshold)
            for i, n in enumerate(sub)])
        return {
            "gap": (r["top"] - r["probes"][..., 0])[fwd["chosen"]],
            "plain_gap": (r["top"] - r["probes"][..., 1])[sub][
                fwd["chosen"][sub]],
            "short": shortfall(conf, fwd["masked"], fwd["chosen"]),
            "plain_short": shortfall(conf[sub], fwd["masked"][sub], p_chosen),
            "forwards": N, "plain": plain, "stats": stats, "fwd": fwd,
            "sub": sub}

    def verdicts(weights, controls=None, reuse=None, **other_model):
        """{verdict: (ok, detail)} and every row's replay."""
        rows = [replayed(weights, a, s, controls,
                         None if reuse is None else reuse[i]["plain"],
                         **other_model)
                for i, (a, s) in enumerate(zip(asks, served))]
        return ({name: judge([r for r, a in zip(rows, asks) if a[4] == name])
                 for name in ("long", "short")}, rows)

    judged, rows = verdicts(params)
    ok, detail = judged["long"]
    ok_short, detail["short"] = judged["short"]
    ok = ok and ok_short
    if keep is not None:
        keep.update(verdicts=verdicts, rows=rows, params=params, asks=asks,
                    served=served, plain_path=plain_path, judge=judge,
                    within_slack=within_slack)
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))    # noqa: E731
    detail.update(
        {k.split("/")[1]: gain(k) for k in BLOCK_COUNTERS[:7]},
        chunked_admissions=engine.session.chunked_admissions,
        prefix_hit_tokens=gain("serving/prefix_hit_tokens"))
    from nanorlhf_tpu.ops.moe import moe_counters

    detail["moe"] = moe_counters(
        [s for r, a in zip(rows, asks) if a[4] == "long" for s in r["stats"]])
    if detail["commit_forwards"] != detail["blocks_done"]:
        ok, detail["error"] = False, "a block ended without its commit"
    elif detail["tokens_unmasked"] != (detail["tokens_streamed"]
                                       + detail["tokens_cut"]):
        ok, detail["error"] = False, "tokens unmasked that were neither " \
            "streamed nor cut"
    elif detail["prefix_hit_tokens"]:
        ok, detail["error"] = False, "a model that generates by blocks " \
            "took a prefix hit"
    return ok, detail


def start(cell, opts, keep: dict | None = None) -> serve.Served:
    """`serve_ref.start` (the engine with the mix's `prefill_chunk`, the
    gateway, the hub's reset, the set-up line) with this module's refusal,
    weights, warm-up and comparison in the places of its own."""
    refuse_a_program_without_the_model(cell)
    ref_weights = serve_ref.init_weights

    def weights(*args):
        build = lambda: spread(     # noqa: E731
            ref_weights(*args), cell.config["assumed"].get("init"),
            int(opts["seed"]))
        if keep is not None:    # (a control tool rounds them where they lie)
            keep["rebuild"] = build
        return build()

    with substituted(serve_ref, "init_weights", weights), \
            substituted(serve_ref, "warm_up", warm_up), \
            substituted(serve_ref, "check_greedy", check), \
            substituted(serve_ref, "refuse_a_program_without_the_model",
                        refuse_a_program_without_the_model):
        return serve_ref.start(cell, opts, keep)


def measure(served, cell, opts, tracer, rate: float | None = None) -> dict:
    """`serve.measure` with the child that posts a block model's requests."""
    with substituted(serve, "CHILD", CHILD):
        return serve.measure(served, cell, opts, tracer, rate)


def experts_hit_a_forward(config: dict, before: dict, after: dict):
    """Experts a layer that a block forward's live rows reached, between two
    readings of `engine.metrics()`."""
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
            substituted(serve, "TraceWindow", tracer), \
            substituted(serve, "CHILD", CHILD):
        result = serve.run(cell, opts)
    run_ = result.run
    run_["kind"] = "serve_block_ref"
    moe = dict((run_.get("greedy_check") or {}).get("moe") or {})
    start_, end = run_["counters"]["start"], run_["counters"]["end"]
    spans = {"moe/held_experts_hit": (start_, end)}
    if len(seen["tracer"].counters) == 2:
        spans["moe/held_experts_hit_traced"] = seen["tracer"].counters
    for name, (before, after) in spans.items():
        hit = experts_hit_a_forward(cell.config, before, after)
        if hit is not None:
            moe[name] = hit
    run_["moe"] = moe
    run_["traced_counters"] = seen["tracer"].counters
    run_["block"] = {k: end[k] - start_[k] for k in BLOCK_COUNTERS
                     if k in end and k in start_}
    if moe.get("moe/dropped_tokens"):
        result.why_not.append("moe/dropped_tokens of the scoring forward: "
                              f"{moe['moe/dropped_tokens']}")
    child = run_.get("child") or {}
    sent = sum("sent" in r for r in run_["records"])
    if child.get("plain_bodies", 1) or child.get("block_bodies", 0) < sent:
        result.why_not.append(
            f"of {sent} requests sent in the window, the child laid "
            f"denoising_steps / remasking over {child.get('block_bodies')} "
            f"bodies and missed {child.get('plain_bodies')}")
    want = int(cell.config["assumed"]["block_length"])
    if end.get("serving/block_length") != want:
        result.why_not.append(
            f"the engine's block length is {end.get('serving/block_length')}, "
            f"the file's {want}")
    result.correct = not result.why_not
    if run_.get("trace") is not None:
        from harness import block_trace, moe_trace, scope_trace, xplane

        path = xplane.newest_xplane(os.path.join(opts["out_dir"], "trace"))
        run_["moe_trace"] = moe_trace.scope_seconds(path) if path else None
        run_["block_trace"] = block_trace.kernel_seconds(path) if path else None
        if path:
            # the one reduction of the trace by scopes, with `sample.unmask`
            # kept apart (the readers take `run["scope_trace"]` as it is)
            with substituted(scope_trace, "SCOPE_FAMILIES",
                             scope_trace.SCOPE_FAMILIES + ("sample.",)):
                run_["scope_trace"] = scope_trace.scope_seconds(path)
            print(json.dumps({"phase": "scopes", **run_["scope_trace"]}),
                  flush=True)
        between = seen["tracer"].counters
        print(json.dumps({
            "phase": "traced_kinds", "block_trace": run_["block_trace"],
            "gmm": (run_["moe_trace"] or {}).get("kernel"),
            "counters": {k: float(between[1][k] - between[0][k])
                         for k in BLOCK_COUNTERS + ("serving/loop_beats",)
                         if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
