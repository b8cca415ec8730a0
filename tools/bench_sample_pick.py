"""The sampler's pick of its 64 candidates on the chip: `approx_max_k`'s own
aggregation (a sort of all C candidates of its partial reduce) beside the
selection `sampler._nucleus_candidates` takes from `sampler._PICK_ROWS` rows
on (`ops/top_select.top_k_select` over the unaggregated candidates), at the
rows x candidates the benchmark's cells sample (PERF.md PR 60 has the table
`_PICK_ROWS` was set from).

    chiprun -- python3 tools/bench_sample_pick.py [--rows 8,16,..] \
        [--vocab 50304,151936] [--timings alone,rollout,served] \
        [--layers 4] [--forms-file FILE [--forms REGEX]]

A case is rows x vocabulary (50,304 gives 6,400 candidates, 151,936 gives
9,600). The selection is first checked ON THE DEVICE against `lax.top_k`
over the unaggregated candidates (values and positions bit for bit; logits
of bfloat16 values, so with ties, a run of equal values across the k-th
place in one row and `-inf` past the 40th in another), then timed three
ways, each with the rule forced off (`sort`) and on (`select`):

- `alone_us`: the candidates' pick over `[rows, V]` logits that are already
  there, `REPS` calls inside one jitted loop;
- `rollout_step_us`: the one-jit rollout (`sampler.generate_tokens`) of a
  Qwen2.5-1.5B cut to `--layers` layers with that vocabulary, `rows` rows,
  a prompt of 128 and `STEPS` decode steps, no EOS: the call's wall time
  over its steps (the prefill is in it on both sides);
- `served_step_us`: a served step's head and sampler
  (`session._over_needed` around `session._serving_sample`) over hidden
  states that are already there, the needed rows `rows` of 64 (of `rows`
  where that is no branch of 64), so that the `lax.switch` takes the branch
  of that size.

The compiler lays a sort out for the code around it (PR 58), so the rule
follows the two in-program timings, not `alone_us`. `--forms-file` names a
Python file with `FORMS = {name: pick(values, k) -> (vals, pos)}`; each form
it holds is checked and timed as the selection in turn (how PR 60 tried the
forms that were deleted). One JSON line a case and a form on stdout, all of
them in `chiprun_out/sample_pick/`.
"""
import argparse
import dataclasses
import importlib.util
import json
import os
import re
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params  # noqa: E402
from nanorlhf_tpu.core.model import _logits  # noqa: E402
from nanorlhf_tpu.ops.top_select import top_k_select  # noqa: E402
from nanorlhf_tpu.sampler import sampler  # noqa: E402
from nanorlhf_tpu.sampler.paged import session  # noqa: E402

REPS, DISTINCT, STEPS, K = 200, 4, 256, 64
OFF = 1 << 30


def logits_of(rows, V, seed=0):
    """`[DISTINCT, rows, V]` float32 logits of bfloat16 values (ties)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (DISTINCT, rows, V),
                          jnp.float32) * 3
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def forced(form, rule):
    """Run `fn` with the sampler's rule forced and `form` as its pick."""
    def deco(fn):
        def run(*a, **kw):
            was = sampler._PICK_ROWS, sampler.top_k_select
            sampler._PICK_ROWS, sampler.top_k_select = rule, form
            try:
                return fn(*a, **kw)
            finally:
                sampler._PICK_ROWS, sampler.top_k_select = was
        return run
    return deco


def wall_us(run, *operands, per):
    """Microseconds a unit of a jitted call's second run, and its result."""
    jax.block_until_ready(run(*operands))
    t0 = time.perf_counter()
    got = jax.block_until_ready(run(*operands))
    return (time.perf_counter() - t0) / per * 1e6, got


def check(form, rows, V):
    """The form over the device's unaggregated candidates against
    `lax.top_k` over them, and the candidates' width."""
    cand, _ = jax.jit(lambda x: jax.lax.approx_max_k(
        x, K, recall_target=0.99, aggregate_to_topk=False))(
            logits_of(rows, V)[0])
    cand = cand.at[0, : cand.shape[-1] // 2].set(1.5).at[1, 40:].set(-jnp.inf)
    got, ref = jax.jit(form, static_argnums=1)(cand, K), jax.lax.top_k(cand, K)
    same = all(np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))
               for a, b in zip(got, ref))
    return bool(same), int(cand.shape[-1])


def alone(form, rule, rows, V):
    xs = logits_of(rows, V)

    @forced(form, rule)
    def call(i, xs):
        vals, idx, keep = sampler._nucleus_candidates(xs[i], 0.95, K, True)
        return idx + keep + (vals > 0)

    def run(xs):
        return jax.lax.fori_loop(
            0, REPS, lambda i, acc: acc + call(i % DISTINCT, xs),
            jnp.zeros((rows, K), jnp.int32))
    return wall_us(jax.jit(run), xs, per=REPS)[0]


def rollout(form, rule, rows, V, layers):
    config = dataclasses.replace(ModelConfig.qwen2_1_5b(), vocab_size=V,
                                 num_hidden_layers=layers)
    params = init_params(config, jax.random.PRNGKey(0), jnp.bfloat16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, 128), 0, V)
    @jax.jit
    @forced(form, rule)
    def gen(params, ids, key):
        return sampler.generate_tokens.__wrapped__(
            params, config, ids, jnp.ones_like(ids, bool), key,
            max_tokens=STEPS + 1, eos_token_id=-1, pad_token_id=0,
            temperature=0.9, top_p=0.95, top_k=K, approx_top_k=True)
    us, toks = wall_us(gen, params, ids, jax.random.PRNGKey(2), per=STEPS)
    return us, np.asarray(toks)


def served(form, rule, rows, V):
    N = 64 if rows in session.needed_sizes(64) else rows
    config = dataclasses.replace(ModelConfig.qwen2_1_5b(), vocab_size=V)
    D = config.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    params = {"embed_tokens": jax.random.normal(ks[0], (V, D), jnp.bfloat16)
              * 0.05, "norm": jnp.ones((D,), jnp.bfloat16)}
    hidden = jax.random.normal(ks[1], (DISTINCT, N, D), jnp.bfloat16)
    need = jnp.arange(N) < rows
    temp, topp = jnp.full((N,), 0.9), jnp.full((N,), 0.95)
    greedy = jnp.zeros((N,), bool)

    @forced(form, rule)
    def step(i, params, hidden):
        key = jax.random.fold_in(ks[2], i)
        (tok,), taken = session._over_needed(
            need, hidden[i % DISTINCT], partial(_logits, config, params),
            lambda logits, idx: (session._serving_sample(
                key, logits, temp[idx], topp[idx], greedy[idx], top_k=K,
                approx_top_k=True, draw=(idx, N)),))
        return tok + taken

    def run(params, hidden):
        return jax.lax.fori_loop(
            0, REPS, lambda i, acc: acc + step(i, params, hidden),
            jnp.zeros((N,), jnp.int32))
    us, toks = wall_us(jax.jit(run), params, hidden, per=REPS)
    return us, np.asarray(toks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="8,16,32,48,64,128")
    ap.add_argument("--vocab", default="50304,151936")
    ap.add_argument("--timings", default="alone,rollout,served")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--forms-file")
    ap.add_argument("--forms", default=".")
    ap.add_argument("--out", default="table")
    args = ap.parse_args()
    forms = {"select": top_k_select}
    if args.forms_file:
        spec = importlib.util.spec_from_file_location("forms", args.forms_file)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        forms = {n: f for n, f in mod.FORMS.items()
                 if re.search(args.forms, n)}
    timings = args.timings.split(",")
    print(jax.devices(), flush=True)
    out = []
    for V in map(int, args.vocab.split(",")):
        for rows in map(int, args.rows.split(",")):
            ref = {}
            for name, form in {"sort": None, **forms}.items():
                rule = OFF if form is None else 0
                row = {"rows": rows, "vocab": V, "k": K, "form": name}
                if form is not None:
                    row["equals_top_k"], row["candidates"] = check(
                        form, rows, V)
                if "alone" in timings:
                    row["alone_us"] = round(alone(form, rule, rows, V), 1)
                if "rollout" in timings:
                    us, toks = rollout(form, rule, rows, V, args.layers)
                    ref.setdefault("rollout", toks)
                    row["rollout_step_us"] = round(us, 1)
                    row["rollout_layers"] = args.layers
                    row["rollout_tokens_equal_sorts"] = float(np.mean(
                        toks == ref["rollout"]))
                if "served" in timings:
                    us, toks = served(form, rule, rows, V)
                    ref.setdefault("served", toks)
                    row["served_step_us"] = round(us, 1)
                    row["served_tokens_equal_sorts"] = float(np.mean(
                        toks == ref["served"]))
                out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out/sample_pick", exist_ok=True)
    with open(f"chiprun_out/sample_pick/{args.out}.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
