"""A served forward samples the rows that NEED a token (ISSUE 47).

`session._over_needed` gathers the needed items (a decode step's live rows, a
block forward's masked positions of the rows on a denoise forward) to the
front, scores them with the head and runs the sampler over an eighth, a
quarter, a half or all of them, whichever holds the needed ones. Pinned here,
on the CPU:

  * a needed item's token (and confidence) is what scoring EVERY item gave it,
    bit for bit, for the same key: the candidates are the row's own, the
    Gumbel noise is drawn at the full shape and gathered;
  * the size taken is the smallest that holds the needed count, none under 8;
  * the two chunk bodies leave the carry they left when every item was
    scored, with dead rows, rows on their commit forward and half-unmasked
    blocks in one forward;
  * the counters: `serving/sample_rows` <= `serving/sample_slots`, equal when
    every row is live, eight a step with one live row of 64.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler import blockdiff
from nanorlhf_tpu.sampler.paged import session
from nanorlhf_tpu.sampler.paged.session import DecodeSession
from nanorlhf_tpu.sampler.sampler import _categorical_rows
from nanorlhf_tpu.serving.radix import RadixCache

EOS, PAD, V = 3, 0, 160
N = 64                               # items: sizes 8, 16, 32, 64
KW = dict(top_k=24, approx_top_k=False)


@pytest.mark.parametrize("items,sizes", [
    (64, (8, 16, 32, 64)), (32, (8, 16, 32)), (256, (32, 64, 128, 256)),
    (16, (8, 16)), (8, (8,)), (3, (3,)), (20, (10, 20))])
def test_the_sizes_are_an_eighth_to_all_and_none_under_eight(items, sizes):
    assert session.needed_sizes(items) == sizes


def test_rows_draw_what_the_batch_would_have_drawn():
    kept = jax.random.normal(jax.random.PRNGKey(0), (N, 24)) * 2.0
    key = jax.random.PRNGKey(1)
    whole = np.asarray(jax.random.categorical(key, kept, axis=-1))
    idx = jnp.asarray([5, 63, 0, 17, 17, 40])
    some = np.asarray(_categorical_rows(key, kept[idx], (idx, N)))
    np.testing.assert_array_equal(some, whole[np.asarray(idx)])
    np.testing.assert_array_equal(
        np.asarray(_categorical_rows(key, kept)), whole)


def every_item(need, hidden, head, sample):
    """`_over_needed` as a forward was before it: every item sampled."""
    n = need.shape[0]
    return sample(head(hidden), jnp.arange(n)), jnp.int32(n)


def needed(n, seed):
    need = np.zeros(N, bool)
    need[np.random.default_rng(seed).permutation(N)[:n]] = True
    return need


def row_params(seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
            jnp.asarray(rng.choice([0.7, 0.9, 1.0], N), jnp.float32),
            jnp.asarray(rng.random(N) < 0.4))


# n = 0, 1, exactly a size, a size + 1 and N
COUNTS = [(0, 8), (1, 8), (8, 8), (9, 16), (16, 16), (17, 32), (33, 64),
          (64, 64)]


@pytest.mark.parametrize("n,size", COUNTS)
def test_a_needed_rows_token_is_the_whole_steps(n, size):
    """`_serving_sample` over the gathered rows against itself over every
    row as the parent ran it (`jax.random.categorical` of `[N, K]`)."""
    logits = jax.random.normal(jax.random.PRNGKey(n), (N, V)) * 3.0
    temp, topp, greedy = row_params(n)
    key = jax.random.PRNGKey(100 + n)
    want = np.asarray(session._serving_sample(
        key, logits, temp, topp, greedy, **KW))
    need = needed(n, n)
    (got,), taken = jax.jit(lambda need: session._over_needed(
        need, logits, lambda hidden: hidden,
        lambda some, idx: (session._serving_sample(
            key, some, temp[idx], topp[idx], greedy[idx], draw=(idx, N),
            **KW),)))(jnp.asarray(need))
    assert int(taken) == size
    np.testing.assert_array_equal(np.asarray(got)[need], want[need])
    if n >= 8:      # greedy rows and sampled ones among the needed
        assert 0 < np.asarray(greedy)[need].sum() < n


@pytest.mark.parametrize("n,size", COUNTS)
def test_a_needed_positions_token_and_confidence_are_the_whole_forwards(
        n, size):
    """`sample_positions` over the gathered positions (16 rows of 4) against
    itself over every position."""
    block = 4
    logits = jax.random.normal(jax.random.PRNGKey(50 + n), (N, V)) * 3.0
    per = lambda a: jnp.repeat(a[:N // block], block)   # noqa: E731
    temp, topp, greedy = (per(a) for a in row_params(50 + n))
    key = jax.random.PRNGKey(200 + n)
    want_tok, want_conf = (np.asarray(a) for a in blockdiff.sample_positions(
        key, logits, temp, topp, greedy, **KW))
    need = needed(n, 50 + n)
    (tok, conf), taken = jax.jit(lambda need: session._over_needed(
        need, logits, lambda hidden: hidden,
        lambda some, idx: blockdiff.sample_positions(
            key, some, temp[idx], topp[idx], greedy[idx], draw=(idx, N),
            **KW)))(jnp.asarray(need))
    assert int(taken) == size
    np.testing.assert_array_equal(np.asarray(tok)[need], want_tok[need])
    np.testing.assert_array_equal(np.asarray(conf)[need], want_conf[need])
    assert ((want_conf[need] > 0) & (want_conf[need] <= 1)).all()


# ------------------------------------------------------- the chunk bodies

def pad_left(sess, prompt):
    toks = np.full(sess.Tp, PAD, np.int32)
    toks[sess.Tp - len(prompt):] = prompt
    return toks, toks != PAD


def prompt_of(n, seed):
    return np.random.default_rng([seed, n]).integers(4, V - 8, n).tolist()


def both_ways(monkeypatch, body, *args):
    """`body` compiled as it is and with every item sampled (once each: an
    eager call would lower the layer scans anew every time; a function of
    its own each, or the second trace would be the first's, cached)."""
    ours = jax.jit(lambda *a: body(*a)).lower(*args).compile()
    with monkeypatch.context() as m:
        m.setattr(session, "_over_needed", every_item)
        whole = jax.jit(lambda *a: body(*a)).lower(*args).compile()
    return lambda *a: (ours(*a), whole(*a))


def same_carry(got, want):
    def plain(a):       # a PRNG key compares by its data
        return np.asarray(jax.random.key_data(a) if jnp.issubdtype(
            a.dtype, jax.dtypes.prng_key) else a)

    for i, (a, b) in enumerate(zip(got, want)):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(plain(x), plain(y), str(i))


def test_a_decode_step_over_the_live_rows_leaves_the_carry_it_left(
        monkeypatch):
    """16 rows, 5 admitted (sampled and greedy), one of them done: the step
    takes the size 8, and every slot of the carry is what scoring all 16 rows
    left (a dead row's token is the pad either way)."""
    config = ModelConfig.qwen2_tiny(vocab_size=V)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    sess = DecodeSession(
        params, config, rows=16, prompt_len=12, max_tokens=6, page_size=4,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, prefix_cache=RadixCache(True), sync_every=1, top_k=24,
        approx_top_k=False)
    for i, r in enumerate([1, 4, 7, 11, 14]):
        sess.admit(r, *pad_left(sess, prompt_of(5 + i, i)), i,
                   budget=2 if r == 7 else 6, temperature=0.8 + 0.1 * i,
                   top_p=0.9, greedy=i % 2 == 1)
    statics = {k: v for k, v in sess._statics.items() if k != "sync_every"}
    step = None
    for beat in range(3):
        args = (jnp.asarray(sess._temp_np), jnp.asarray(sess._topp_np),
                jnp.asarray(sess._greedy_np), jnp.asarray(sess._budget_np))
        state, table = sess.state, jnp.array(sess.table_np)
        step = step or both_ways(
            monkeypatch, lambda state, table, args:
            session._session_decode_body(
                params, config, state, table, args, **statics),
            state, table, args)
        (got, seen), (want, whole) = step(state, table, args)
        assert int(whole[1]) == 16      # (the other program samples them all)
        live = int((~np.asarray(state[5])).sum())
        assert live == (5 if beat < 1 else 4) and int(seen[1]) == 8
        same_carry(got, want)
        sess.step()
    assert sess.sample_rows == 3 * 8 and sess.sample_slots == 3 * 16


def test_a_block_forward_over_the_masked_positions_leaves_the_carry_it_left(
        monkeypatch):
    """8 rows of blocks of 4 (32 positions: sizes 8, 16, 32): dead rows, rows
    on their commit forward and rows with half a block unmasked in the same
    forwards. `out`, `blk`, `masked` and every other slot of the carry are
    what sampling at all 32 positions left, forward by forward."""
    from test_sdar import CFG, lay_weights
    from test_sdar import prompt_of as block_prompt

    params = lay_weights()
    sess = DecodeSession(
        params, CFG, rows=8, prompt_len=16, max_tokens=8, page_size=8,
        eos_token_id=1, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, prefix_cache=RadixCache(headroom=0.0), prefill_chunk=8,
        sync_every=1, top_k=24, approx_top_k=False)
    plan = [(0, 9, 4, "low_confidence_static", True),
            (2, 6, 2, "low_confidence_dynamic", False),
            (3, 8, 1, "sequential", False),
            (5, 11, 4, "low_confidence_static", False),
            (6, 7, 2, "low_confidence_static", True)]
    for i, (r, plen, steps, strategy, greedy) in enumerate(plan):
        toks, mask = pad_left(sess, block_prompt(plen, 20 + i))
        assert sess.admit(r, toks, mask, i, budget=8, greedy=greedy,
                          temperature=0.9, top_p=0.9, denoising_steps=steps,
                          remasking=strategy) is None
    statics = {k: v for k, v in sess._block_statics.items()
               if k != "sync_every"}
    taken, kinds, forward = [], set(), None
    for _ in range(40):
        args = tuple(jnp.asarray(a) for a in (
            sess._temp_np, sess._topp_np, sess._greedy_np, sess._budget_np,
            sess._steps_np, sess._remask_np))
        state, table = sess.state, jnp.array(sess.table_np)
        done, masked = np.asarray(state[5]), np.asarray(state[11])
        if done.all():
            break
        forward = forward or both_ways(
            monkeypatch, lambda state, table, args: session._block_body(
                params, CFG, state, table, args, **statics),
            state, table, args)
        (got, seen), (want, whole) = forward(state, table, args)
        assert int(whole[1]) == 32      # (the other program samples them all)
        same_carry(got, want)
        n = int((~done[:, None] & masked).sum())
        assert int(seen[1]) == min(s for s in (8, 16, 32) if s >= n)
        taken.append(int(seen[1]))
        live = ~done
        kinds |= {"dead"} if done.any() else set()
        kinds |= {"commit"} if (live & ~masked.any(1)).any() else set()
        kinds |= {"half"} if (live & masked.any(1) & ~masked.all(1)).any() \
            else set()
        sess.step()
    assert kinds == {"dead", "commit", "half"} and len(set(taken)) > 1
    assert sess.sample_rows == sum(taken)
    assert sess.sample_slots == len(taken) * 32


# ---------------------------------------------------------- the counters

def test_every_row_live_takes_the_whole_batch():
    """16 rows admitted before the first beat, equal budgets, no EOS: every
    step scores 16 rows, and the two counters stay equal."""
    config = ModelConfig.qwen2_tiny(vocab_size=V)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    sess = DecodeSession(
        params, config, rows=16, prompt_len=12, max_tokens=6, page_size=4,
        eos_token_id=V + 5, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, prefix_cache=RadixCache(True), sync_every=2)
    for r in range(16):
        sess.admit(r, *pad_left(sess, prompt_of(4 + r % 5, r)), r, budget=6,
                   temperature=1.0, top_p=1.0, greedy=r % 2 == 0)
    while True:
        done, _ = sess.step()
        if done.all():
            break
    assert sess.iterations() == 5
    assert sess.sample_rows == sess.sample_slots == 5 * 16


def test_the_engine_reports_the_rows_its_sampler_ran_over():
    """One request in an engine of 64 rows: every step has one live row and
    takes the size 8. Then five at once in the same engine: never more than
    the rows there are."""
    from nanorlhf_tpu.serving.engine import ServingEngine

    config = ModelConfig.qwen2_tiny(vocab_size=V)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    with ServingEngine(params, config, eos_token_id=V + 5, pad_token_id=PAD,
                       page_size=4, prompt_len=12, max_new_tokens=8, rows=64,
                       sync_every=2) as eng:
        req, shed = eng.submit(prompt_of(9, 1), greedy=True, max_tokens=7)
        assert shed is None and len(list(eng.stream(req))) == 7
        one = eng.metrics()
        reqs = [eng.submit(prompt_of(5 + i, i), temperature=0.9, top_p=0.9,
                           max_tokens=8)[0] for i in range(5)]
        assert all(len(list(eng.stream(r))) == 8 for r in reqs)
        more = eng.metrics()
    steps = one["serving/decode_steps"]
    assert steps == 6
    assert one["serving/sample_rows"] == 8 * steps
    assert one["serving/sample_slots"] == 64 * steps
    assert 0 < more["serving/sample_rows"] - one["serving/sample_rows"] <= (
        more["serving/sample_slots"] - one["serving/sample_slots"])
    assert (more["serving/sample_slots"]
            == 64 * more["serving/decode_steps"])
