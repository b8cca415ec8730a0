"""Sampler contract + KV-cache correctness on the tiny model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import (
    ModelConfig,
    init_params,
    model_forward,
    init_kv_cache,
    prefill,
    decode_step,
)
from nanorlhf_tpu.sampler import SamplingParams, generate

EOS, PAD = 3, 0


@pytest.fixture(scope="module")
def tiny():
    config = ModelConfig.qwen2_tiny(vocab_size=128)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    return config, params


def _left_pad(rows, T, pad=PAD):
    ids = np.full((len(rows), T), pad, np.int32)
    mask = np.zeros((len(rows), T), np.int32)
    for i, r in enumerate(rows):
        ids[i, T - len(r):] = r
        mask[i, T - len(r):] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


def test_prefill_decode_matches_full_forward(tiny):
    """Greedy decode via KV cache == iterative argmax via full forward."""
    config, params = tiny
    rows = [[5, 6, 7, 8], [9, 10]]
    Tp = 5
    ids, mask = _left_pad(rows, Tp)
    max_tokens = 6
    out = generate(
        params, config, ids, mask, jax.random.PRNGKey(0),
        SamplingParams(greedy=True, max_tokens=max_tokens, n=1),
        eos_token_id=EOS, pad_token_id=PAD,
    )
    # oracle: grow the sequence one token at a time through model_forward
    for b, row in enumerate(rows):
        seq = list(row)
        got_row = []
        done = False
        for _ in range(max_tokens):
            if done:
                got_row.append(PAD)
                continue
            cur = jnp.asarray([seq])
            m = jnp.ones_like(cur)
            pos = jnp.cumsum(m, axis=1) - 1
            logits = model_forward(params, config, cur, m, pos)
            nxt = int(jnp.argmax(logits[0, -1]))
            got_row.append(nxt)
            seq.append(nxt)
            if nxt == EOS:
                done = True
        np.testing.assert_array_equal(np.asarray(out[b]), got_row)


def test_generate_contract_n_samples(tiny):
    config, params = tiny
    ids, mask = _left_pad([[5, 6, 7], [8, 9]], 4)
    N, T = 3, 5
    out = generate(
        params, config, ids, mask, jax.random.PRNGKey(1),
        SamplingParams(temperature=1.0, top_p=0.95, n=N, max_tokens=T),
        eos_token_id=EOS, pad_token_id=PAD,
    )
    assert out.shape == (2 * N, T)
    arr = np.asarray(out)
    # after the first EOS, everything is PAD
    for row in arr:
        seen_eos = False
        for t in row:
            if seen_eos:
                assert t == PAD
            if t == EOS:
                seen_eos = True


def test_generate_is_seed_dependent(tiny):
    config, params = tiny
    ids, mask = _left_pad([[5, 6, 7, 11, 12, 13]], 6)
    sp = SamplingParams(temperature=1.0, top_p=1.0, n=4, max_tokens=8)
    a = generate(params, config, ids, mask, jax.random.PRNGKey(0), sp,
                 eos_token_id=EOS, pad_token_id=PAD)
    b = generate(params, config, ids, mask, jax.random.PRNGKey(1), sp,
                 eos_token_id=EOS, pad_token_id=PAD)
    c = generate(params, config, ids, mask, jax.random.PRNGKey(0), sp,
                 eos_token_id=EOS, pad_token_id=PAD)
    assert np.asarray(a).tolist() == np.asarray(c).tolist()  # same key → same sample
    assert np.asarray(a).tolist() != np.asarray(b).tolist()  # changing seed parity


def test_prefill_logits_match_full_forward(tiny):
    config, params = tiny
    rows = [[5, 6, 7, 8], [9, 10, 11]]
    Tp = 6
    ids, mask = _left_pad(rows, Tp)
    caches = init_kv_cache(config, 2, Tp + 4, jnp.float32)
    last_logits, caches = prefill(params, config, ids, mask, caches)
    pos = jnp.cumsum(mask, axis=1) - mask
    full = model_forward(params, config, jnp.where(mask.astype(bool), ids, 0), mask, pos)
    np.testing.assert_allclose(
        np.asarray(last_logits), np.asarray(full[:, -1, :]), rtol=1e-4, atol=1e-4
    )


def test_topk_nucleus_matches_exact_filter():
    """The fused top-k nucleus path with the EXACT candidate set
    (approx_top_k=False) samples only tokens inside the exact full-vocab
    nucleus (the keep rule is applied over true probabilities via a
    full-vocab logsumexp, so whenever the nucleus fits in top-k the two
    filters agree). The approx path intentionally offers a weaker guarantee
    (see SamplingParams.approx_top_k) and is covered separately below."""
    from nanorlhf_tpu.sampler.sampler import _sample_token, top_p_filter

    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 512)) * 3.0  # peaked
    allowed = np.asarray(top_p_filter(logits, 0.95)) > -np.inf
    keys = jax.random.split(jax.random.PRNGKey(1), 256)
    toks = np.asarray(jax.vmap(
        lambda k: _sample_token(k, logits, 1.0, 0.95, False, 64,
                                approx_top_k=False)
    )(keys))                                            # [256, 4]
    for t_row in toks:
        for b, t in enumerate(t_row):
            assert allowed[b, t], f"sampled token {t} outside exact nucleus"


def test_approx_topk_candidates_high_probability():
    """The approx path samples only top-k candidates whose true probability
    mass is nucleus-grade: every sampled token must be inside the exact
    top-p KEEP SET UNION the exact top-k set (the approx candidate set is a
    subset of plausible-high-prob tokens; on CPU ApproxTopK is exact, so
    this degenerates to the exact-path property — the TPU-side deviation is
    bounded by recall_target=0.99 and validated on silicon by the bench's
    distribution of sampled ids, not unit-testable off-TPU)."""
    from nanorlhf_tpu.sampler.sampler import _sample_token

    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 512)) * 3.0
    exact_topk = np.asarray(
        jax.lax.top_k(logits, 64)[1]
    )                                                   # [4, 64]
    keys = jax.random.split(jax.random.PRNGKey(1), 128)
    toks = np.asarray(jax.vmap(
        lambda k: _sample_token(k, logits, 1.0, 0.95, False, 64,
                                approx_top_k=True)
    )(keys))
    for t_row in toks:
        for b, t in enumerate(t_row):
            assert t in exact_topk[b], f"sampled {t} outside top-64 set"


def test_topk_sampling_distribution_small_vocab():
    """With top_k == vocab the fused path IS exact nucleus sampling: the
    empirical distribution over many draws matches the renormalized nucleus
    probabilities."""
    from nanorlhf_tpu.sampler.sampler import _sample_token, top_p_filter

    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -8.0, -8.0, -8.0, -8.0]])
    masked = np.asarray(top_p_filter(logits, 0.9))[0]
    probs = np.exp(masked - masked.max())
    probs[~np.isfinite(masked)] = 0.0
    probs /= probs.sum()
    keys = jax.random.split(jax.random.PRNGKey(3), 4000)
    toks = np.asarray(jax.vmap(
        lambda k: _sample_token(k, logits, 1.0, 0.9, False, 8)
    )(keys))[:, 0]
    counts = np.bincount(toks, minlength=8) / len(toks)
    np.testing.assert_allclose(counts, probs, atol=0.03)


def test_capture_logprobs_match_scoring_pass(tiny):
    """Sampler-captured logprobs equal the scoring pass's
    `logprobs_from_logits` at the response positions (f32 tiny model — the
    two paths share the same math, so agreement is tight)."""
    from nanorlhf_tpu.core import padded_forward_logits
    from nanorlhf_tpu.ops.masking import logprobs_from_logits

    config, params = tiny
    ids, mask = _left_pad([[5, 6, 7], [8, 9]], 4)
    T = 6
    temp = 0.9
    out, lp = generate(
        params, config, ids, mask, jax.random.PRNGKey(5),
        SamplingParams(temperature=temp, top_p=0.95, n=2, max_tokens=T,
                       capture_logprobs=True),
        eos_token_id=EOS, pad_token_id=PAD,
    )
    out, lp = np.asarray(out), np.asarray(lp)
    assert out.shape == (4, T) and lp.shape == (4, T)

    # de-pad the prompt rows like the trainer does and rescore
    ids_rep = np.asarray(jnp.repeat(ids, 2, axis=0))
    qr = np.concatenate([ids_rep, out], axis=1)
    logits = padded_forward_logits(params, config, jnp.asarray(qr), PAD,
                                   response_context_length=ids.shape[1])
    scored = np.asarray(logprobs_from_logits(logits, jnp.asarray(out), temp))
    # compare on real (pre-EOS) tokens only; positions after EOS hold pads
    for b in range(out.shape[0]):
        for t in range(T):
            if out[b, t] == PAD:
                break
            assert abs(lp[b, t] - scored[b, t]) < 1e-3, (b, t, lp[b, t], scored[b, t])
            if out[b, t] == EOS:
                break


def test_top_p_bisect_matches_sort_oracle(rng):
    """The sort-free bisection nucleus filter must produce the SAME keep
    mask as the sort-based oracle — peaked, flat, bf16-quantized (mass
    ties), and near-one-hot distributions. The decode loop's top_k=0 path
    (the r1-zero launcher default) rides the bisection variant."""
    import jax.numpy as jnp

    from nanorlhf_tpu.sampler.sampler import top_p_filter, top_p_filter_bisect

    cases = [
        rng.normal(size=(4, 512)).astype(np.float32),            # generic
        (rng.normal(size=(2, 512)) * 8).astype(np.float32),      # peaked
        np.zeros((1, 512), np.float32),                          # exact flat
        jnp.asarray(rng.normal(size=(2, 512)), jnp.bfloat16)     # bf16 ties
            .astype(jnp.float32),
    ]
    onehot = np.full((1, 512), -30.0, np.float32); onehot[0, 7] = 10.0
    cases.append(onehot)
    for i, logits in enumerate(cases):
        logits = jnp.asarray(logits)
        for p in (0.5, 0.9, 0.95, 0.99):
            want = np.asarray(top_p_filter(logits, p)) > -np.inf
            got = np.asarray(top_p_filter_bisect(logits, p)) > -np.inf
            # identical masks except possibly inside an exact float tie at
            # the boundary (the sort cannot order ties stably either):
            # every disagreement must sit at exactly the threshold prob
            if not np.array_equal(want, got):
                probs = np.asarray(jax.nn.softmax(logits, axis=-1))
                for b in range(logits.shape[0]):
                    dis = want[b] != got[b]
                    if dis.any():
                        kept = probs[b][want[b]]
                        assert np.allclose(
                            probs[b][dis], kept.min(), rtol=1e-6
                        ), f"case {i} p={p}: non-tie disagreement"
            # the kept mass must reach p either way (nucleus property)
            probs = np.asarray(jax.nn.softmax(logits, axis=-1))
            for b in range(logits.shape[0]):
                assert probs[b][got[b]].sum() >= p - 1e-5


# --------------------------------------------------------------------- #
# the decode read's static extents (core/model.py::decode_read_extents)
# --------------------------------------------------------------------- #

def _whole_read_generate(params, config, ids, mask, key, sampling, eos):
    """`generate_tokens` as it was before the loop named extents: the same
    prefill, then ONE while_loop whose steps read the whole cache."""
    from functools import partial

    from nanorlhf_tpu.sampler import sampler as S

    kw = dict(max_tokens=sampling.max_tokens, eos_token_id=eos,
              pad_token_id=PAD, temperature=sampling.temperature,
              top_p=sampling.top_p, greedy=sampling.greedy, lora_scale=1.0,
              top_k=sampling.top_k, capture_logprobs=True,
              approx_top_k=sampling.approx_top_k)

    @jax.jit
    def run(params, ids, mask, key):
        state = S._prefill_state(params, config, ids, mask, key,
                                 prompt_fanout=sampling.n, **kw)
        body = partial(S._decode_body, params, config, Tp=ids.shape[1], **kw)
        state = jax.lax.while_loop(
            lambda s: (s[0] < sampling.max_tokens) & ~jnp.all(s[5]),
            body, state)
        return state[1], state[2]

    return run(params, ids, mask, key)


def _first_seen(row, lo, hi):
    """Indices in [lo, hi) at which `row` holds a token for the first time."""
    return [k for k in range(lo, hi) if row[k] not in row[:k]]


def test_read_loops_take_every_step_once_under_its_extent(tiny):
    """Whatever the prompt's width and the number of new tokens (a cache one
    slot past a block among them: its last slot is never written, so it
    earns no loop), the loops' stops rise to `max_tokens`, each step's
    write slot lies under its loop's extent, every extent but the last is
    whole blocks, and there are never more than three."""
    from nanorlhf_tpu.sampler.sampler import _read_loops

    config, _ = tiny
    for Tp in (1, 5, 64, 113, 127, 128, 129, 200, 256, 300):
        for new in range(1, 700):
            loops = _read_loops(config, Tp, new)
            extents, stops = zip(*loops)
            assert len(loops) <= 3 and loops[-1] == (Tp + new, new)
            assert all(a < b for a, b in zip(stops, stops[1:])), (Tp, new)
            assert all(e % 128 == 0 for e in extents[:-1])
            assert all(Tp + stop - 2 < e <= Tp + stop - 1 or e == Tp + new
                       for e, stop in loops), (Tp, new)
            if Tp + new <= 128:
                assert len(loops) == 1
    assert _read_loops(config, 256, 512) == [(512, 257), (640, 385),
                                             (768, 512)]


@pytest.mark.parametrize("case", ["dense", "olmoe", "eos_before_a_boundary",
                                  "eos_at_a_boundary", "one_past_a_block",
                                  "thinned_one_past_a_block"])
def test_bounded_decode_read_matches_the_whole_read(tiny, case):
    """Past one 128-slot block the monolithic loop runs as one loop an
    extent, each reading the cache up to a static bound no row's write has
    passed. The slots it leaves out are masked ones (exp(-inf) = 0 in the
    softmax), so greedy tokens equal the whole read's and the captured
    logprobs agree to float32 roundoff; rows that all end early leave the
    later loops no step and the output padded as ever; and the counter
    says what the extents say. A cache one slot past a block (its last slot
    is never written) runs too, with its natural extents and with more
    natural extents than a loop may have."""
    from nanorlhf_tpu.sampler.sampler import _read_loops, attn_read_frac

    config, params = tiny
    if case == "olmoe":
        config = ModelConfig.olmoe_tiny(vocab_size=128)
        params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    Tp, new = {"one_past_a_block": (200, 313),
               "thinned_one_past_a_block": (64, 449)}.get(case, (64, 200))
    key = jax.random.PRNGKey(5)
    sampling = SamplingParams(greedy=True, n=2, max_tokens=new,
                              capture_logprobs=True)
    if "eos" not in case:
        # rows of unlike pad widths, and nothing ends them
        ids, mask = _left_pad([list(range(5, 69)), list(range(9, 40)),
                               [70, 71, 72]], Tp)
        eos, ends_at = -1, new - 1
    else:
        # identical rows end together: greedy, and EOS is whichever token
        # the row holds first at the step wanted
        ids, mask = _left_pad([[5, 6, 7, 8, 9]] * 2, Tp)
        ref, _ = _whole_read_generate(params, config, ids, mask, key,
                                      sampling, -1)
        ref = np.asarray(ref)
        assert (ref == ref[0]).all()
        if case == "eos_before_a_boundary":
            ends_at = _first_seen(ref[0], 20, 40)[0]
        else:
            # the first loop's last step writes slot 127: move the prompt's
            # width so that the row's end falls on it
            ends_at = _first_seen(ref[0], 50, 100)[0]
            Tp = 128 - ends_at
            ids, mask = _left_pad([[5, 6, 7, 8, 9]] * 2, Tp)
        eos = int(ref[0, ends_at])
    loops = _read_loops(config, Tp, new)
    first, second = {"one_past_a_block": (256, 384),
                     "thinned_one_past_a_block": (256, 384)}.get(
                         case, (128, 256))
    assert loops == [(first, first + 1 - Tp), (second, second + 1 - Tp),
                     (Tp + new, new)]

    want, want_lp = _whole_read_generate(params, config, ids, mask, key,
                                         sampling, eos)
    got, got_lp = generate(params, config, ids, mask, key, sampling,
                           eos_token_id=eos, pad_token_id=PAD)
    want, got = np.asarray(want), np.asarray(got)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(np.asarray(got_lp), np.asarray(want_lp),
                               rtol=0, atol=1e-5)
    assert got.shape == (2 * ids.shape[0], new)
    if eos >= 0:
        assert (got[:, ends_at] == eos).all() and (got[:, :ends_at] != eos).all()
        assert (got[:, ends_at + 1:] == PAD).all()
        assert (np.asarray(got_lp)[:, ends_at + 1:] == 0).all()

    # steps 1 .. ends_at, each under the first extent its write slot is in
    read = sum(min(e for e, _ in loops if Tp + s - 1 < e)
               for s in range(1, ends_at + 1))
    frac = attn_read_frac(config, sampling, Tp, got, eos)
    assert frac == pytest.approx(read / (ends_at * (Tp + new)))
    if eos >= 0:
        assert frac == pytest.approx(128 / (Tp + new))   # one loop ran
    # ... and 1.0 where the loop names no extent
    if Tp < 128:
        one_block = SamplingParams(greedy=True, max_tokens=128 - Tp)
        assert _read_loops(config, Tp, 128 - Tp) == [(128, 128 - Tp)]
        assert attn_read_frac(config, one_block, Tp, got[:, :128 - Tp],
                              eos) == 1.0
    paged = SamplingParams(greedy=True, max_tokens=new, page_size=16)
    assert attn_read_frac(config, paged, Tp, got, eos) == 1.0
