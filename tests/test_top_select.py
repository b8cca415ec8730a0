"""The sampler's pick of its k candidates by selection (PR 60):
`ops/top_select.top_k_select` is `lax.top_k` bit for bit, and
`sampler._nucleus_candidates` takes `approx_max_k`'s unaggregated candidates
through it from `sampler._PICK_ROWS` rows on. `approx_max_k` is exact on the
CPU, so both sides of the rule compare bit for bit here.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.ops.top_select import take_at, top_k_select
from nanorlhf_tpu.sampler import SamplingParams, sampler as S
from nanorlhf_tpu.sampler.paged import session

K = 64
OFF = 1 << 30


@contextlib.contextmanager
def rule(rows):
    """`sampler._PICK_ROWS` forced: 0 takes the selection at any number of
    rows, `OFF` at none."""
    was = S._PICK_ROWS
    S._PICK_ROWS = rows
    try:
        yield
    finally:
        S._PICK_ROWS = was


def both_sides(fn, *args):
    """`fn(*args)` traced anew with the rule forced off and on."""
    out = []
    for rows in (OFF, 0):
        with rule(rows):
            out.append(jax.jit(fn)(*args))
    return out


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    return np.array_equal(a, b)


def values_of(kind, rows, C, k):
    """Candidate values of bfloat16 resolution (ties everywhere)."""
    x = np.random.default_rng(rows * C).standard_normal((rows, C)) * 4
    x = np.array(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                 .astype(jnp.float32))
    if kind == "run":           # equal values across the k-th place
        x[:, C // 3: C // 3 + 2 * k] = np.float32(x.max() + 1)
        x[0] = np.float32(1.5)
    elif kind == "inf":         # fewer than k finite entries in some rows
        x[::2, k // 2:] = -np.inf
        x[1, :] = -np.inf
    elif kind == "zero":        # 0.0 ranks before -0.0, as in lax.top_k
        x = -np.abs(x)
        x[:, 3:40:2], x[:, 4:40:2] = 0.0, -0.0
    return jnp.asarray(x)


@pytest.mark.parametrize("kind", ["bf16", "run", "inf", "zero"])
@pytest.mark.parametrize("rows,C,k", [
    (64, 9600, K), (64, 6400, K), (128, 9600, K), (8, 9600, K),
    (5, 1000, K), (3, 130, 7), (4, K, K),
])
def test_the_pick_is_lax_top_k_bit_for_bit(rows, C, k, kind):
    values = values_of(kind, rows, C, k)
    vals, pos = jax.jit(top_k_select, static_argnums=1)(values, k)
    ref_vals, ref_pos = jax.lax.top_k(values, k)
    assert same_bits(vals, ref_vals)
    assert same_bits(pos, ref_pos)


def test_the_pick_takes_leading_axes():
    values = values_of("bf16", 6, 1000, K).reshape(2, 3, 1000)
    vals, pos = top_k_select(values, K)
    ref_vals, ref_pos = jax.lax.top_k(values, K)
    assert same_bits(vals, ref_vals) and same_bits(pos, ref_pos)


def _logits(rows, V, seed=0, ties=False):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, V), jnp.float32) * 3
    return x.astype(jnp.bfloat16).astype(jnp.float32) if ties else x


@pytest.mark.parametrize("V,ties", [(50304, True), (50304, False),
                                    (4096, False)])
@pytest.mark.parametrize("per_row", [False, True])
def test_nucleus_candidates_are_the_same_on_both_sides_of_the_rule(
        V, ties, per_row):
    logits = _logits(16, V, ties=ties)
    top_p = jnp.linspace(0.5, 1.0, 16)[:, None] if per_row else 0.95
    off, on = both_sides(
        lambda x: S._nucleus_candidates(x, top_p, K, True), logits)
    for a, b in zip(off, on):
        assert same_bits(a, b)
    assert int(jnp.sum(on[2])) > 16     # a nucleus of more than one token


def test_the_rule_reads_rows_and_whether_there_are_candidates():
    n = S._PICK_ROWS
    assert S.sample_picks((n, 4096), K, True)
    assert S.sample_picks((2, n // 2, 4096), K, True)      # rows in all
    assert not S.sample_picks((n - 1, 4096), K, True)
    assert not S.sample_picks((n, 4096), K, False)         # lax.top_k
    assert not S.sample_picks((n, K), K, True)             # top_k >= V
    assert not S.sample_picks((n, 4096), 0, True)


def test_sample_token_draws_the_same_on_both_sides():
    logits, key = _logits(32, 4096, seed=1), jax.random.PRNGKey(5)
    off, on = both_sides(
        lambda x: S._sample_token(key, x, 0.9, 0.95, False, K, True), logits)
    assert same_bits(off, on)
    assert len(set(np.asarray(on).tolist())) > 1


def test_serving_sample_draws_the_same_on_both_sides():
    N, s = 32, 16
    logits, key = _logits(s, 4096, seed=2), jax.random.PRNGKey(6)
    idx = jnp.arange(s) * 2
    temp = jnp.linspace(0.5, 1.2, s)
    top_p = jnp.linspace(0.6, 1.0, s)
    greedy = jnp.arange(s) % 5 == 0
    off, on = both_sides(
        lambda x: session._serving_sample(
            key, x, temp, top_p, greedy, top_k=K, approx_top_k=True,
            draw=(idx, N)), logits)
    assert same_bits(off, on)


def test_the_verifiers_dense_distribution_is_the_same_on_both_sides():
    logits = _logits(24, 4096, seed=3).reshape(6, 4, 4096)
    off, on = both_sides(
        lambda x: S.filtered_logits_full(x, 0.9, 0.95, K, True), logits)
    assert same_bits(off, on)
    assert np.isfinite(np.asarray(on)).sum() > 24


def test_a_rollouts_stream_is_the_same_on_both_sides():
    config = ModelConfig.qwen2_tiny(vocab_size=512)
    params = init_params(config, jax.random.PRNGKey(0), jnp.float32)
    rows = max(S._PICK_ROWS, 16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, 6), 4, 512)

    def gen(params, ids):
        return S.generate_tokens.__wrapped__(
            params, config, ids, jnp.ones_like(ids, bool),
            jax.random.PRNGKey(2), max_tokens=12, eos_token_id=3,
            pad_token_id=0, temperature=0.9, top_p=0.95, top_k=K,
            approx_top_k=True)

    assert S.sample_picks((rows, 512), K, True)
    off, on = both_sides(gen, params, ids)
    assert same_bits(off, on)
    # and as the rule stands, it is the selection's side
    assert same_bits(jax.jit(gen)(params, ids), on)


def _sort_widths(text):
    """The sorted axis' length of every `stablehlo.sort` in a lowering."""
    widths = []
    for m in re.finditer(
            r'"stablehlo\.sort"\(.*?dimension = (\d+) : i64.*?'
            r'\(tensor<([0-9x]+)x[a-z0-9]+>', text, re.S):
        dims = [int(d) for d in m.group(2).split("x")]
        widths.append(dims[int(m.group(1))])
    return widths


def test_the_lowered_program_sorts_no_more_than_k_above_the_rule():
    n = S._PICK_ROWS

    def text(rows):
        return jax.jit(
            lambda x: S._nucleus_candidates(x, 0.95, K, True)
        ).lower(jax.ShapeDtypeStruct((rows, 50304), jnp.float32)).as_text()

    above, below = text(n), text(n - 1)
    assert "aggregate_to_topk = false" in above
    assert "aggregate_to_topk = true" not in above
    assert _sort_widths(above) == [K]
    assert "stablehlo.while" in above               # the threshold search
    # below the rule: XLA's own aggregation and nothing of the selection
    assert "aggregate_to_topk = true" in below
    assert "aggregate_to_topk = false" not in below
    assert _sort_widths(below) == [] and "stablehlo.while" not in below
    with rule(OFF):     # the parent's program at any number of rows
        assert text(n - 1) == below and "stablehlo.while" not in text(n)


@pytest.mark.parametrize("sampling,rows,want", [
    (SamplingParams(), 64, 1),
    (SamplingParams(), 8, 1),
    (SamplingParams(), 4, 0),
    (SamplingParams(greedy=True), 64, 0),
    (SamplingParams(top_p=1.0), 64, 0),
    (SamplingParams(top_k=0), 64, 0),
    (SamplingParams(approx_top_k=False), 64, 0),
    (SamplingParams(page_size=16, decode_rows=4), 64, 0),   # resident rows
    (SamplingParams(page_size=16, decode_rows=32), 64, 1),
    (SamplingParams(spec_k=3), 2, 1),       # 2 rows x 4 candidate positions
])
def test_the_trainers_counter_says_which_side_a_rollout_took(
        sampling, rows, want):
    config = ModelConfig.qwen2_tiny(vocab_size=512)
    assert S._PICK_ROWS == 8
    assert S.sample_pick(config, sampling, rows) == want


def test_a_serving_session_names_the_sizes_that_take_the_selection():
    from nanorlhf_tpu.serving import ServingEngine

    config = ModelConfig.qwen2_tiny(vocab_size=512)
    params = init_params(config, jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(params, config, eos_token_id=3, pad_token_id=0,
                        page_size=8, prompt_len=8, max_new_tokens=8, rows=64)
    try:
        sizes = eng.metrics()["serving/sample_pick_sizes"]
        assert session.needed_sizes(64) == (8, 16, 32, 64)
        assert sizes == tuple(s for s in (8, 16, 32, 64)
                              if s >= S._PICK_ROWS)
    finally:
        eng.close()
    small = dataclasses.replace(config, vocab_size=K)       # top_k >= V
    sess_sizes = session.DecodeSession(
        init_params(small, jax.random.PRNGKey(0), jnp.float32), small,
        rows=64, prompt_len=8, max_tokens=8, page_size=8, eos_token_id=3,
        pad_token_id=0, key=jax.random.PRNGKey(0), per_row=True,
    ).sample_pick_sizes
    assert sess_sizes == ()


@pytest.mark.parametrize("C", [9600, 1000, 64])
def test_take_at_is_take_along_axis(C):
    rng = np.random.default_rng(C)
    ints = jnp.asarray(rng.integers(0, 1 << 24, (3, 5, C)), jnp.int32)
    ints = ints.at[0, 0, 0].set((1 << 24) - 1).at[0, 0, C - 1].set(0)
    pos = jnp.asarray(rng.integers(0, C, (3, 5, 17)), jnp.int32)
    pos = pos.at[0, 0, :2].set(jnp.asarray([0, C - 1]))
    assert same_bits(jax.jit(take_at)(ints, pos),
                     jnp.take_along_axis(ints, pos, axis=-1))
