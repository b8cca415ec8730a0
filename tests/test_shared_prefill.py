"""Shared-prompt-KV prefill (SamplingParams.shared_prompt_prefill).

The n>1 fanout must be a pure optimization: prefilling each prompt once and
fanning the KV/first-logits out to its N samples has to reproduce the
repeat-×N path's token streams EXACTLY (same [B*N] shapes and the same
fold_in key stream reach the categorical either way). Reference capability:
vLLM's prefix sharing for `SamplingParams(n=4)` requests
(`/root/reference/GRPO/grpo_trainer.py:127`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler import SamplingParams, generate

EOS, PAD = 3, 0


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.qwen2_tiny(vocab_size=128)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _prompts():
    # varied left-padding: per-row prompt_len must fan out correctly
    ids = jnp.asarray([
        [PAD, PAD, 5, 6],
        [PAD, 7, 8, 9],
        [10, 11, 12, 13],
        [PAD, PAD, PAD, 14],
    ], jnp.int32)
    return ids, (ids != PAD)


def _gen(model, shared, **kw):
    cfg, params = model
    ids, mask = _prompts()
    sp = SamplingParams(n=4, max_tokens=10, shared_prompt_prefill=shared, **kw)
    return generate(params, cfg, ids, mask, jax.random.PRNGKey(42), sp,
                    eos_token_id=EOS, pad_token_id=PAD)


def test_tokens_match_repeat_path(model):
    a = _gen(model, True)
    b = _gen(model, False)
    assert a.shape == b.shape == (16, 10)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_siblings_diverge(model):
    """Fanout must NOT collapse the N samples of a prompt onto one stream —
    checked PER PROMPT (a per-shard fanout bug could collapse some prompts
    while others escape)."""
    out = np.asarray(_gen(model, True))
    rows = out.reshape(4, 4, -1)
    # at temperature 1 / top_p .95 over an untrained model, every prompt
    # should have at least one divergent sibling pair
    for p in range(4):
        assert any(
            not np.array_equal(rows[p, i], rows[p, j])
            for i in range(4) for j in range(i + 1, 4)
        ), f"prompt {p}: all 4 siblings emitted identical streams"


def test_capture_logprobs_match(model):
    ta, la = _gen(model, True, capture_logprobs=True)
    tb, lb = _gen(model, False, capture_logprobs=True)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
    # the two paths are different compiled programs; XLA fusion choices move
    # the f32 logsumexp by a few ulp even though every sampled token matches
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=1e-5)


def test_exact_nucleus_path(model):
    a = _gen(model, True, top_k=0)
    b = _gen(model, False, top_k=0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_greedy_fanout(model):
    """Greedy n>1: all siblings must emit the prompt's argmax stream."""
    out = np.asarray(_gen(model, True, greedy=True))
    ref = np.asarray(_gen(model, False, greedy=True))
    np.testing.assert_array_equal(out, ref)
    rows = out.reshape(4, 4, -1)
    for p in range(4):
        for j in range(1, 4):
            np.testing.assert_array_equal(rows[p, 0], rows[p, j])


def test_n1_unaffected(model):
    cfg, params = model
    ids, mask = _prompts()
    kw = dict(eos_token_id=EOS, pad_token_id=PAD)
    a = generate(params, cfg, ids, mask, jax.random.PRNGKey(1),
                 SamplingParams(n=1, max_tokens=8, shared_prompt_prefill=True),
                 **kw)
    b = generate(params, cfg, ids, mask, jax.random.PRNGKey(1),
                 SamplingParams(n=1, max_tokens=8, shared_prompt_prefill=False),
                 **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
