"""Driver for mixes of kind `serve_sala_ref`: `drivers/serve.py`'s open-loop
serving run for MiniCPM-SALA (docs/SALA.md): lightning layers that keep a
float32 matrix state and no pages beside sparse-attention layers whose
queries choose the blocks of their pages they read, its numerics held to the
float32 reference the configuration names.

`serve_ssm_ref.py` cannot take such a cell unedited: its refusal demands a
Mamba mixer's keys, its weights rescale leaves this tree does not have, its
traffic is one class, and nothing in it asks about a selection. The window
(`serve.measure`, `serve.run`, `client_metrics`), the engine's start with
`eos_unreachable` weights (`serve_ref.start`, `serve_ref.init_weights`), the
classes' child, the warm-up and the counters read inside the trace
(`serve_mix_ref.CHILD`, `warm_up`, `InsideTrace`) and the state's reading
(`serve_ssm_ref.state_reading`) are theirs, by import. This module's own:

- the weights are `init_params`' with the configuration's `assumed.init`
  laid over them (`spread`): every norm weight exp(N(0, `norm_log_std`)),
  the sparse layers' q and k norms times `sparse_qk_gain` (so that the
  compressed scores are not near-uniform and a wrong selection shows), the
  head's gain;
- the greedy comparison (`check_greedy`) is `serve_ssm_ref.check_greedy`'s:
  teacher-forced logits of what the TIMED engine served against the
  reference, at the cell's own sizes, under `agreement.follows_greedy`'s
  unchanged limits, in six verdicts. `long`: ONE prompt of `long_len`
  tokens (twenty whole prefill pieces and a last one of 3, past
  `dense_len`, so every piece selects), then `long_max_tokens` decode steps,
  each a selection over ~320 blocks and a pass over the state; `short`:
  `short_rows` cold prompts asked AT THE SAME TIME, read dense, decoding
  beside the long prompt's pieces; `steady`: `steady_rows` prompts read
  dense and `steady_max_tokens` decode steps each (the state's reading,
  below, is one of them's); `cross`: `cross_rows` prompts just under
  `dense_len`, whose rows pass it while they decode, each at a step of its
  own; `carry` and `reuse` as Falcon-H1's. Every verdict is sized for a
  few hundred bf16 flips: a verdict's mean gap is the sum of its flips'
  gaps, the served path's and the plain path's flips fall on different
  tokens, and the ratio of two such sums of N flips each spreads by about
  2 / sqrt(N) (with 23 to 41 flips a verdict, the first sizes, two sound
  runs of six read a ratio over `GAP_SLACK`'s 1.5: PERF.md section 6, PR
  53). The reference and the plain path are told which
  of a row's tokens were decode steps (`decoded`; `response_context_length`):
  a call's keys decide whether its queries select;
- the lightning STATE is read where it lies after `long`, `steady` and
  `carry` (`engine_state`: to the host, while the engine is idle) and held
  to the reference's (`state_reading`) once that verdict's reference pass
  has left it: the reference runs ONCE a verdict, over the tokens each
  request was FED (the prompt and every answered token but the last), and
  gives the logits at the answers' positions and the state after the last
  row's last token from the same pass (`logits_and_states`; a pass for the
  logits and another for the state made the set-up outlast the check's
  limit for a run, PERF.md section 6, PR 53). The model's first lightning layer is its
  SECOND layer, so its keys and values have passed a bfloat16 layer and the
  state lies 1.5 % from the float32 reference's whatever its own type (a
  sum of rounded terms is as far off as its terms are); a state KEPT in
  bfloat16 lies 2.2 % off after 255 decode steps (PERF.md section 6, PR
  53: `chiprun_out/a2`), too near for a fixed limit over seeds. So the
  limit is the program's own, as `follows_greedy`'s is: on `steady` (a
  prompt read dense, then `steady_max_tokens` decode steps, each a pass
  over the state and a rounding of it if it is kept in bfloat16) the
  engine's state may lie `STATE_SLACK` times as far from the reference's as
  the state of the program's PLAIN cached path does (`plain_state`: the
  contiguous cache with its float32 state, XLA's reads, the same pieces
  and steps, on the same tokens): the two see the same rounded keys and
  values, so they read alike (0.96-1.00) unless the engine's state is
  rounded as it is stored (1.33-1.45). `long` and `carry` are held to `STATE_GROSS`, which
  a state not carried, not reset or decayed otherwise fails (a distance of
  about 1); the `long` row's reading holds its prefill's selection flips
  too (9 %);
- the SELECTION is compared (`selection_reading`): for every decode step
  of the `long` row, the program's `select_blocks` over the compressed
  keys the TIMED engine cached for that row (`cached_compressed_keys`: read
  where they lie, through the block table the row held when the engine
  released it, so the prefill pieces' and the decode steps' `compress_write`
  and the cache's layout are in it) with the first layer's query at the
  served type (that layer reads the embedding alone, so every
  implementation's q is the same), against the reference's chosen blocks.
  A top-k flips on rounding where two scores tie, so a block may differ
  only where the reference's own score of it lies within `SELECTION_MARGIN`
  of its k-th score; the largest such distance is reported. NOT observed:
  the work list the engine's step made of its selection and what its kernel
  then fetched (the decode chunk hands neither out); those are held by
  `long` and `cross` (tools/sparse_control.py: the newest 64 blocks, the
  group sum left out and `dense_len` ignored each fail them, and the
  pooling moved by a block fails this verdict);
- every program of the comparison has ONE shape whatever the seed: a
  verdict's rows are padded to the longest prompt its draws allow, not to
  the longest drawn, and the row whose state is read is of `steady_max`
  tokens. The check draws a new seed for every run; with the widths the
  seed's own, the reference, the plain path and the plain cached path of
  `short`, `steady` and `cross` were eight programs new to every run, 130
  to 180 s of compiles in a set-up that the persistent cache had otherwise
  warm (`compile_seconds` of the set-up line; PERF.md section 6, PR 53);
- it fails at once, non-zero and before any weights are built, when the
  program's `ModelConfig` does not carry the file's mixers
  (`refuse_a_program_without_the_model`): a parent commit that cannot build
  the configuration exits 4 within seconds;
- `correct` also needs the engine's `serving/state_layers`,
  `serving/state_bytes_per_row` and `serving/kv_bytes_per_token` to be what
  the file's layers hold (the sparse layers' K, V and compressed keys).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time
import types

import numpy as np

from drivers import serve, serve_mix_ref, serve_ref
from drivers.rl_ref import substituted
from drivers.serve_ssm_ref import state_reading
from harness import agreement, model, ops_bytes_sala as ob, trafficgen

client_metrics = serve.client_metrics

# configuration file key -> ModelConfig attribute
MODEL_KEYS = {
    "lightning_nh": "lightning_heads", "lightning_head_dim": "lightning_head_dim",
    "scale_emb": "embed_scale", "scale_depth": "scale_depth",
    "published_layers": "published_layers",
    "first_published_layer": "first_published_layer",
    "intermediate_size": "intermediate_size",
    "num_key_value_heads": "num_key_value_heads",
}
SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
               "init_blocks", "window_size", "dense_len")
# `state_reading`'s limits (module docstring; PERF.md section 6, PR 53). On
# `steady`: the engine's distance from the reference's state over the plain
# cached path's, between its two readings on the chip (a float32 state 1.0,
# a bfloat16 one 1.33 to 1.45 at 255 decode steps: thirteen sound runs read
# 0.957-0.999, the control 1.326, the probe 1.45: `chiprun_out/b1`-`e1`,
# `a2`); where both distances are float32 roundoff (a rehearsal) the floor
# stands in.
STATE_SLACK = 1.15
STATE_FLOOR = 1e-4
# on `long` and `carry`: what a state not carried between pieces, not reset
# on re-use or decayed otherwise cannot pass (about 1), with room over the
# `long` row's 0.09 (its prefill's selection flips)
STATE_GROSS = 0.3
# a chosen block may differ from the reference's only where the reference's
# score of it lies this near its k-th score (relative): bfloat16 q and
# compressed keys carry 2^-8 of relative rounding into logits of size ~3,
# which moves a block's mass by a few per cent. Between its two readings
# (PERF.md section 6, PR 53): sound runs' largest distance 0.028-0.057
# (nine seeds, the first layer at the published widths on the CPU; the
# chip's runs beside them there), and the pooling moved by one block, which
# puts 39 k of its 46 k differing blocks outside (distances up to 15-40)
SELECTION_MARGIN = 0.15


def kv_bytes_per_token(config: dict) -> int:
    width = {"bfloat16": 2, "float32": 4}[config["assumed"]["dtype"]]
    return ob.kv_bytes_per_token(config, width)


def state_bytes_per_row(config: dict) -> int:
    return ob.state_bytes_per_row(config)


def refuse_a_program_without_the_model(cell) -> None:
    """Raises SystemExit(4) unless the program builds the file's model."""
    cfg = cell.config
    try:
        mcfg = model.model_config(cfg)
        lacking = {k: (cfg[k], getattr(mcfg, attr, None))
                   for k, attr in MODEL_KEYS.items()
                   if getattr(mcfg, attr, None) != cfg[k]}
        lacking.update({k: (v, getattr(mcfg, "sparse_" + k, None))
                        for k, v in cfg["sparse_config"].items()
                        if k in SPARSE_KEYS
                        and getattr(mcfg, "sparse_" + k, None) != v})
        mixers = [ob.MIXERS[m] for m in cfg["mixer_types"]]
        if list(getattr(mcfg, "layer_kinds", ())) != mixers:
            lacking["mixer_types"] = (mixers, getattr(mcfg, "layer_kinds", None))
        why = f"file against ModelConfig: {lacking}" if lacking else None
    except (ValueError, TypeError, NotImplementedError, KeyError) as e:
        why = f"{type(e).__name__}: {e}"
    if why:
        print(f"benchmark: configuration {cell.config_name!r} is not a model "
              f"this program builds ({why}). Nothing was built.",
              file=sys.stderr)
        raise SystemExit(4)


def spread(params, init: dict | None, seed: int):
    """The configuration's `assumed.init` laid over `init_params`' weights:
    `gains` (a kernel times its gain, rescaled where it lies), `norm_log_std`
    (every norm weight exp(N(0, that)), from the seed) and `sparse_qk_gain`
    (the sparse layers' q and k norms times that)."""
    if not init:
        return params
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.utils.donation import donate_argnums_on_accel

    rescale = jax.jit(lambda w, s: (w.astype(jnp.float32) * s).astype(w.dtype),
                      donate_argnums=donate_argnums_on_accel(0))
    gains = dict(init.get("gains") or {})
    if "lm_head" in gains:
        params["lm_head"] = rescale(params["lm_head"],
                                    jnp.float32(gains.pop("lm_head")))
    for name, gain in gains.items():
        params["layers"][name]["kernel"] = rescale(
            params["layers"][name]["kernel"], jnp.float32(gain))
    std = float(init.get("norm_log_std") or 0.0)
    qk = float(init.get("sparse_qk_gain") or 1.0)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 29), 64))

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" not in name:
            return a
        w = jnp.exp(std * jax.random.normal(next(keys), a.shape, jnp.float32))
        if name.endswith(("['q_norm']", "['k_norm']")) and "lightning" not in name:
            w = w * qk
        return w.astype(a.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


def slow_heads(mcfg) -> np.ndarray:
    """The quarter of the first lightning layer's heads that forget
    slowest, by the decays, which are the configuration's own."""
    rate = -np.asarray(mcfg.lightning_log_decays()[0])
    return np.sort(np.argsort(rate)[:max(1, len(rate) // 4)])


def cached_compressed_keys(engine, mcfg, table, start: int):
    """The compressed keys the ENGINE cached for a row, read where they lie:
    the first sparse layer's, `[1, KV, Nc, hd]` in position order, through
    the block table `table` [nb] the row held when it was released and its
    first real slot `start`. A released page keeps what it held until its
    next owner writes it; asked while the engine is idle, as
    `state_reading` is."""
    import jax.numpy as jnp

    from nanorlhf_tpu.core import sala

    view = types.SimpleNamespace(
        span=(jnp.asarray([start], jnp.int32), None),
        table=jnp.asarray(table, jnp.int32)[None],
        page_size=engine.session.page_size)
    return sala.compressed_keys(mcfg, engine.session.state[3][0][2], 0, view)


def selection_reading(params, mcfg, cell, ids, pad: int, last: int, kc) -> dict:
    """Module docstring: the program's selection over the engine's cached
    compressed keys `kc` (`cached_compressed_keys`) against the
    reference's, for the last `last` queries of ONE row `ids` [1, T]."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.core import sala

    reference = importlib.import_module("harness." + cell.config["reference"])
    first = jax.jit(lambda p, x: reference.first_layer_selection(
        p, cell.config, x, pad, last))
    with jax.default_matmul_precision("highest"):
        q, _, t, chosen, score = first(params, ids)
    dtype = params["embed_tokens"].dtype
    program = jax.jit(lambda q, kc, t: sala.select_blocks(
        mcfg, q.astype(dtype)[None], kc, t[None]))
    # (the keys of the row's own slots; the table's other pages hold none)
    kc = kc[:, :, :-(-ids.shape[1] // mcfg.sparse_kernel_stride) + 1]
    idx, ok = (np.asarray(a)[0] for a in program(q, kc, t))
    chosen, score = np.asarray(chosen), np.asarray(score)
    KV, Tq, NB = chosen.shape
    got = np.zeros((KV, Tq, NB + 1), bool)
    g, i = np.meshgrid(np.arange(KV), np.arange(Tq), indexing="ij")
    got[g[..., None], i[..., None], np.where(ok, idx, NB)] = True
    got = got[..., :NB]
    # the reference's k-th score a query: its lowest chosen FREE block's
    own = (np.asarray(t) // mcfg.sparse_block_size)[None, :, None]
    b = np.arange(NB)[None, None, :]
    free = (b >= mcfg.sparse_init_blocks) & (
        b <= own - mcfg.sparse_window_size // mcfg.sparse_block_size)
    kth = np.where(chosen & free, score, np.inf).min(-1, keepdims=True)
    differs = got != chosen
    with np.errstate(invalid="ignore"):
        distance = np.where(differs & np.isfinite(kth),
                            np.abs(score - kth) / np.maximum(kth, 1e-30), 0.0)
    wrong = int((distance > SELECTION_MARGIN).sum())
    # how peaked the reference's free scores are: the best free block's mass
    # over the mean free block's (a near-uniform selection cannot be wrong)
    free_scores = np.where(free, score, np.nan)
    peak = float(np.nanmean(np.nanmax(free_scores, -1)
                            / np.nanmean(free_scores, -1)))
    return {"ok": wrong == 0 and int(got.sum(-1).min()) == mcfg.sparse_topk,
            "queries": int(KV * Tq), "blocks_differ": int(differs.sum()),
            "outside_margin": wrong, "margin": SELECTION_MARGIN,
            "largest_distance": float(distance.max()),
            "best_free_over_mean": peak,
            "chosen_a_query": [int(got.sum(-1).min()), int(got.sum(-1).max())]}


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
    steady = between(chk["steady_min"], chk["steady_max"], chk["steady_rows"])
    # (the row whose state is read: of ONE length whatever the seed, so the
    # plain cached path's piece and steps are the same programs every run)
    steady[-1] = draw(chk["steady_max"])
    # under `dense_len` by less than the answer is long: each row passes it
    # at a step of its own
    cross = between(chk["cross_min"], chk["cross_max"], chk["cross_rows"])
    chunk = int(mix["engine"]["prefill_chunk"])
    carry = [draw(chunk * (1 + i % 2) + 1 + i % 3)
             for i in range(int(chk["carry_rows"]))]
    reuse = between(chk["reuse_min"], chk["reuse_max"], mix["engine"]["rows"])
    n_long, n_short, n_steady, n_cross, n_carry, n_reuse = (
        int(chk[k]) for k in (
            "long_max_tokens", "short_max_tokens", "steady_max_tokens",
            "cross_max_tokens", "carry_max_tokens", "reuse_max_tokens"))
    ask = lambda p, n: serve.post(port, {"tokens": p, "greedy": True,    # noqa: E731
                                         "max_tokens": n})
    slow = slow_heads(mcfg)
    session = engine.session

    def engine_state():
        """The engine's lightning state as it lies now, on the host: it is
        held to the reference's once the verdict of the request just served
        has computed that (`state_reading`'s engine, asked while idle)."""
        return np.asarray(session.state[3][-1][-1])

    def plain_state(prompt, answer):
        """The state the program's PLAIN cached path holds after the same
        tokens: the contiguous cache (a float32 state), XLA's reads, the
        prompt in the engine's pieces, then a decode step a token."""
        from nanorlhf_tpu.core.model import (decode_step, decode_verify,
                                             init_kv_cache)

        plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
        fed = np.asarray([prompt + answer[:-1]], np.int32)
        P, T = len(prompt), fed.shape[1]
        T_max = -(-T // 128) * 128
        caches = init_kv_cache(plain_mcfg, 1, T_max,
                               params["embed_tokens"].dtype)
        # (the yardstick's state is float32 whatever the program's is)
        caches = caches[:2] + ((caches[2][0].astype(jnp.float32),),)
        piece = jax.jit(lambda p, x, pos, at, km, c: decode_verify(
            p, plain_mcfg, x, pos, at, km, c, want_logits=False,
            call_keys=jnp.asarray([P], jnp.int32))[1])
        step = jax.jit(lambda p, tok, pos, t, km, c: decode_step(
            p, plain_mcfg, tok, pos, t, km, c, want_logits=False)[1])
        km = jnp.zeros((1, T_max), bool)
        for lo in range(0, P, chunk):
            hi = min(lo + chunk, P)
            caches = piece(params, jnp.asarray(fed[:, lo:hi]),
                           jnp.arange(lo, hi)[None],
                           jnp.asarray([lo], jnp.int32), km, caches)
            km = km.at[:, lo:hi].set(True)
        for t in range(P, T):
            km = km.at[:, t].set(True)
            caches = step(params, jnp.asarray(fed[:, t]), jnp.asarray([t]),
                          jnp.int32(t), km, caches)
        return caches[2][0][:, 0]

    def held_to(name, S, want, prompt, answer):
        """Module docstring: the state `S` the engine held after `name`'s
        last request against the reference's `want` after the tokens that
        request was FED, under `STATE_GROSS`; `steady`'s against the plain
        path's own distance from the reference's."""
        stand_in = types.SimpleNamespace(session=types.SimpleNamespace(
            state=(None, None, None, ((jnp.asarray(S),),))))
        reading = state_reading(stand_in, want, slow)
        if name != "steady":
            return {**reading, "limit": STATE_GROSS,
                    "ok": bool(reading["first_layer_slow"] <= STATE_GROSS)}
        S = plain_state(prompt, answer).astype(jnp.float32)
        far = np.asarray(jnp.sqrt(jnp.sum((S - want) ** 2, (-1, -2))
                                  / jnp.sum(want ** 2, (-1, -2))))
        plain = float(np.sqrt(np.mean(far[0, slow] ** 2)))
        limit = max(STATE_SLACK * plain, STATE_FLOOR)
        return {**reading, "plain_first_layer_slow": plain,
                "over_plain": reading["first_layer_slow"] / max(plain, 1e-12),
                "slack": STATE_SLACK, "limit": limit,
                "ok": bool(reading["first_layer_slow"] <= limit)}

    tables = {}         # first real slot -> the row's block table, as the
    release = session.release       # row is released

    def noted(r, *args, **kwargs):
        tables[int(session._row_start_np[r])] = session.table_np[r].copy()
        return release(r, *args, **kwargs)

    took = collections.defaultdict(float)    # seconds by what they went to

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        try:
            yield
        finally:
            took[name] += time.perf_counter() - t

    before = engine.metrics()
    with timed("served"), ThreadPoolExecutor(
            max(len(long_) + len(short), len(steady), len(cross), len(carry),
                len(reuse))) as pool:
        # all at once: the short rows decode beside the long row's pieces
        with substituted(session, "release", noted):
            jobs = [pool.submit(ask, p, n_long) for p in long_]
            jobs += [pool.submit(ask, p, n_short) for p in short]
            served = [j.result() for j in jobs]
        with timed("state"):
            # the long row's compressed keys lie where it left them until
            # another row is admitted, and its state until a row's next
            # occupant
            first = session.Tp - len(long_[0])
            long_kc = cached_compressed_keys(engine, mcfg, tables[first],
                                             first)
            held = {"long": engine_state()}
        served_steady = list(pool.map(lambda p: ask(p, n_steady), steady))
        with timed("state"):
            held["steady"] = engine_state()
        served_cross = list(pool.map(lambda p: ask(p, n_cross), cross))
        served_carry = list(pool.map(lambda p: ask(p, n_carry), carry))
        with timed("state"):
            held["carry"] = engine_state()
        served_reuse = list(pool.map(lambda p: ask(p, n_reuse), reuse))
    after = engine.metrics()
    took["served"] -= took["state"]
    served_long, served_short = served[:len(long_)], served[len(long_):]
    wanted = ([n_long] * len(long_) + [n_short] * len(short)
              + [n_steady] * len(steady) + [n_cross] * len(cross)
              + [n_carry] * len(carry) + [n_reuse] * len(reuse))
    lengths = [len(s) for s in served + served_steady + served_cross
               + served_carry + served_reuse]
    if lengths != wanted:
        return False, {"error": "a greedy answer is short (eos_unreachable "
                       "mixes yield their budget)", "lengths": lengths}

    def padded(batch, answers, n, at_once, longest):
        """`serve_ssm_ref.check_greedy`'s, but for the width: the rows
        left-padded to the VERDICT's width (`longest`, the longest prompt
        the mix lets it draw, and `n`), in parts of one shape and at most
        `at_once` slots. A width taken from the seed's own draws is another
        program a seed, compiled in every run's set-up (module docstring)."""
        width = longest + n
        seqs = np.full((len(batch), width), pad, np.int32)
        real = np.zeros((len(batch), width), bool)
        for i, (p, s) in enumerate(zip(batch, answers)):
            seqs[i, width - len(p) - n:] = p + s
            real[i, width - len(p) - n:] = True
        rows = min(len(batch), max(1, int(at_once) // width))
        parts = []
        for at in range(0, len(batch), rows):
            part, mask = seqs[at:at + rows], real[at:at + rows]
            count = len(part)
            fill = lambda a: np.concatenate(                     # noqa: E731
                [a, np.repeat(a[:1], rows - count, 0)])
            parts.append((jnp.asarray(fill(part)), jnp.asarray(fill(mask)),
                          count))
        return parts

    def referred(batch, answers, n, longest, without=()):
        """The float32 reference over the tokens each request was FED (its
        prompt and every answered token but the last, which no step was
        fed): `(its logits at the answers' positions, the lightning state
        it leaves after the batch's LAST row)`, the two from one pass;
        `without`: the negative controls. Its rows go side by side, so a
        part is as many slots as the long row (`reference_tokens_at_once`)."""
        program = jax.jit(lambda p, x, m: reference.logits_and_states(
            p, cell.config, x, pad, last=n, mask=m, without=without,
            decoded=n - 1))
        fed = [a[:-1] for a in answers]
        at_once = chk.get("reference_tokens_at_once", chk["tokens_at_once"])
        ref = []
        with timed("reference"), jax.default_matmul_precision("highest"):
            for seqs, real, count in padded(batch, fed, n - 1, at_once,
                                            longest):
                part, states = program(params, seqs, real)
                ref.append(np.asarray(part)[:count])
                want = states[count - 1]
        ref = np.concatenate(ref)
        return ref.reshape(-1, ref.shape[-1]), want

    def reference_logits(batch, answers, n, longest, without=()):
        return referred(batch, answers, n, longest, without)[0]

    def plain_logits(weights, batch, answers, n, longest, **other_model):
        """The plain bf16 path's logits there (`other_model`: fields of the
        `ModelConfig` that a control's model has otherwise)."""
        plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla",
                                         **other_model)
        program = jax.jit(lambda p, x: padded_forward_logits(
            p, plain_mcfg, x, pad, response_context_length=x.shape[1] - n))
        with timed("plain"):
            plain = np.concatenate([
                np.asarray(program(weights, seqs).astype(jnp.float32))[:count]
                for seqs, _, count in padded(batch, answers, n,
                                             chk["tokens_at_once"], longest)])
        return plain.reshape(-1, plain.shape[-1])

    state = {}

    def verdict(name, batch, answers, n, longest):
        ref, want = referred(batch, answers, n, longest)
        if name in held:
            with timed("state"):
                state[name] = held_to(name, held.pop(name), want, batch[-1],
                                      answers[-1])
        plain = plain_logits(params, batch, answers, n, longest)
        tokens = np.asarray(answers).reshape(-1)
        if keep is not None:
            keep[name] = {"ref": ref, "plain": plain, "tokens": tokens,
                          "batch": batch, "answers": answers, "n": n,
                          "longest": longest}
        return agreement.follows_greedy(ref, tokens, plain)

    ok, detail = verdict("long", long_, served_long, n_long, len(long_[0]))
    for name, batch, answers, n, longest in (
            ("short", short, served_short, n_short, chk["short_max"]),
            ("steady", steady, served_steady, n_steady, chk["steady_max"]),
            ("cross", cross, served_cross, n_cross, chk["cross_max"]),
            ("carry", carry, served_carry, n_carry, 2 * chunk + 3),
            ("reuse", reuse, served_reuse, n_reuse, chk["reuse_max"])):
        assert max(len(p) for p in batch) <= longest, (name, longest)
        ok_more, detail[name] = verdict(name, batch, answers, n, int(longest))
        ok = ok and ok_more
    ok = ok and all(reading["ok"] for reading in state.values())
    detail["state"] = {name: {k: v for k, v in reading.items() if k != "heads"}
                       for name, reading in state.items()}
    # the tokens the row was FED: every decode step's query, and no other
    fed = jnp.asarray([long_[0] + served_long[0][:-1]], jnp.int32)
    with timed("selection"):
        detail["selection"] = selection_reading(params, mcfg, cell, fed, pad,
                                                n_long - 1, long_kc)
    ok = ok and detail["selection"]["ok"]
    # (the engine's requests; the reference's passes, which leave the
    # logits and the states; the plain path's logits; the engine's states
    # read, compared, and `steady`'s plain cached path; the selection)
    detail["seconds"] = {k: round(v, 1) for k, v in took.items()}
    # a verdict's slots a row, the mix's and no seed's (module docstring)
    detail["widths"] = {
        "long": len(long_[0]) + n_long, "short": chk["short_max"] + n_short,
        "steady": chk["steady_max"] + n_steady,
        "cross": chk["cross_max"] + n_cross, "carry": 2 * chunk + 3 + n_carry,
        "reuse": chk["reuse_max"] + n_reuse}
    if keep is not None:
        keep.update(reference_logits=reference_logits,
                    plain_logits=plain_logits, params=params, state=state,
                    selection={"ids": fed, "last": n_long - 1, "kc": long_kc})
    gain = lambda k: int(after.get(k, 0) - before.get(k, 0))     # noqa: E731
    detail.update(
        chunked_admissions=engine.session.chunked_admissions,
        state_carries=gain("serving/state_piece_carries"),
        state_resets=gain("serving/state_resets"),
        sparse_rows=gain("serving/sparse_rows"),
        prefix_hit_tokens=gain("serving/prefix_hit_tokens"))
    pieces = -(-int(chk["long_len"]) // chunk)
    dense_len = int(cell.config["sparse_config"]["dense_len"])
    past = (n_long - 1) + sum(      # steps of rows past it
        max(0, len(p) + n_cross - dense_len) for p in cross)
    if detail["state_carries"] < pieces - 1 + len(carry):
        ok = False
        detail["error"] = (f"the long prompt's {pieces} pieces and the "
                           f"{len(carry)} carry prompts carried the state "
                           f"{detail['state_carries']} times")
    elif detail["state_resets"] != len(wanted):
        ok = False
        detail["error"] = (f"{len(wanted)} requests, "
                           f"{detail['state_resets']} states reset")
    elif detail["sparse_rows"] < past:
        ok = False
        detail["error"] = (f"{detail['sparse_rows']} decode steps of rows "
                           f"past dense_len, the long and the cross rows "
                           f"alone took {past}")
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

    took = {}       # what `setup_s` is made of, beside the comparison's parts

    def warm_up(*args):
        took["before_warm_up"] = time.time() - opts["t_process_start"]
        n = serve_mix_ref.warm_up(*args)
        took["warm_up"] = time.time() - opts["t_process_start"] - took[
            "before_warm_up"]
        return n

    def compared(*args):
        ok, detail = check_greedy(*args)
        if "seconds" in detail:
            detail["seconds"] = {**{k: round(v, 1) for k, v in took.items()},
                                 **detail["seconds"]}
        return ok, detail

    with substituted(serve_ref, "init_weights", weights), \
            substituted(serve_ref, "warm_up", warm_up), \
            substituted(serve_ref, "check_greedy", compared), \
            substituted(serve_ref, "refuse_a_program_without_the_model",
                        refuse_a_program_without_the_model):
        return serve_ref.start(cell, opts, keep)


def measure(served, cell, opts, tracer, rate: float | None = None) -> dict:
    """`serve.measure` with the child that draws the mix's classes."""
    return serve_mix_ref.measure(served, cell, opts, tracer, rate)


TRACED = ("serving/decode_steps", "serving/live_row_steps",
          "serving/global_slots_read", "serving/sparse_rows",
          "serving/sparse_slots_read", "serving/sparse_slots_held",
          "serving/state_resets", "serving/state_piece_carries",
          "serving/state_tokens", "serving/loop_beats")


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
    run_["kind"] = "serve_sala_ref"
    run_["traced_counters"] = seen["tracer"].counters
    end = run_["counters"]["end"]
    lightning = sum(ob.MIXERS[m] == "lightning"
                    for m in cell.config["mixer_types"])
    for key, want in (("serving/state_layers", lightning),
                      ("serving/state_bytes_per_row",
                       state_bytes_per_row(cell.config)),
                      ("serving/kv_bytes_per_token",
                       kv_bytes_per_token(cell.config))):
        if end.get(key) != want:
            result.why_not.append(f"the engine's {key} is {end.get(key)}, "
                                  f"the file's {want}")
    result.correct = not result.why_not
    if run_.get("trace") is not None:
        between = seen["tracer"].counters
        print(json.dumps({
            "phase": "traced_kinds",
            "counters": {k: between[1][k] - between[0][k] for k in TRACED
                         if len(between) == 2 and k in between[0]}}),
            flush=True)
    return result
