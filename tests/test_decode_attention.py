"""Prefix-bounded Pallas decode-attention kernel vs the XLA oracle."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.ops import decode_attention as dec
from nanorlhf_tpu.ops.decode_attention import (
    decode_attention,
    reference_decode_attention,
)


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def test_decode_attention_matches_reference(rng):
    B, H, KV, T, hd = 3, 8, 2, 512, 64
    q = _rand(rng, (B, H, hd))
    k = _rand(rng, (B, KV, T, hd))
    v = _rand(rng, (B, KV, T, hd))
    # per-row prefix windows: mixed left-pad offsets + fill levels
    start = jnp.asarray([0, 17, 300], jnp.int32)
    filled = jnp.asarray([512, 200, 400], jnp.int32)
    got = decode_attention(q, k, v, start, filled, block_k=128)
    want = reference_decode_attention(q, k, v, start, filled)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_single_slot(rng):
    """Smallest valid window: one slot (first decode step of a 1-token prompt)."""
    B, H, KV, T, hd = 2, 4, 4, 256, 32
    q = _rand(rng, (B, H, hd))
    k = _rand(rng, (B, KV, T, hd))
    v = _rand(rng, (B, KV, T, hd))
    start = jnp.asarray([0, 5], jnp.int32)
    filled = jnp.asarray([1, 6], jnp.int32)
    got = decode_attention(q, k, v, start, filled, block_k=128)
    want = reference_decode_attention(q, k, v, start, filled)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_unaligned_t(rng):
    """T_max not a block multiple: internal padding must not leak."""
    B, H, KV, T, hd = 2, 6, 2, 200, 64  # G=3 (< sublane), odd T
    q = _rand(rng, (B, H, hd))
    k = _rand(rng, (B, KV, T, hd))
    v = _rand(rng, (B, KV, T, hd))
    start = jnp.asarray([3, 0], jnp.int32)
    filled = jnp.asarray([77, 200], jnp.int32)
    got = decode_attention(q, k, v, start, filled, block_k=128)
    want = reference_decode_attention(q, k, v, start, filled)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_generate_pallas_decode_matches_xla(rng):
    """End-to-end: greedy generate with attention_impl='pallas' (flash prefill
    + prefix-bounded decode) emits the same tokens as the XLA path."""
    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.sampler import SamplingParams, generate

    cfg_xla = ModelConfig.qwen2_tiny(vocab_size=128)
    cfg_pl = dataclasses.replace(cfg_xla, attention_impl="pallas")
    params = init_params(cfg_xla, jax.random.PRNGKey(0), jnp.float32)
    PAD, EOS = 0, 3
    ids = np.full((2, 6), PAD, np.int32)
    ids[0, 2:] = [5, 6, 7, 8]
    ids[1, 4:] = [9, 10]
    mask = jnp.asarray((ids != PAD).astype(np.int32))
    sp = SamplingParams(greedy=True, max_tokens=8, n=1)
    out_xla = generate(params, cfg_xla, jnp.asarray(ids), mask,
                       jax.random.PRNGKey(1), sp, eos_token_id=EOS,
                       pad_token_id=PAD)
    out_pl = generate(params, cfg_pl, jnp.asarray(ids), mask,
                      jax.random.PRNGKey(1), sp, eos_token_id=EOS,
                      pad_token_id=PAD)
    np.testing.assert_array_equal(np.asarray(out_xla), np.asarray(out_pl))


# --------------------------- the in-place paged read, by the pages an item holds

@pytest.mark.parametrize("C,held,KV,dtype,tol", [
    (4, 1, 2, jnp.float32, 2e-5), (4, 2, 2, jnp.float32, 2e-5),
    (4, 3, 2, jnp.float32, 2e-5), (4, 4, 2, jnp.float32, 2e-5),
    (2, 1, 8, jnp.float32, 2e-5), (2, 2, 8, jnp.float32, 2e-5),
    (4, 1, 2, jnp.bfloat16, 2e-2), (2, 2, 8, jnp.bfloat16, 2e-2)])
def test_paged_read_of_items_by_the_pages_they_hold(rng, monkeypatch, C, held,
                                                    KV, dtype, tol):
    """Rows whose LAST item holds `held` of an item's `C` pages: the only
    item of a row from slot 0 (its last page part filled, and filled to the
    page's end), the item after a whole one, and both again on a row whose
    first item is cut by `start` (items are aligned from `start // P`), dead
    rows among them; four items a step of the kernel's loop, so that a step
    holds whole and short items, items of three rows, a row's end in its
    middle and, the second step, fewer items than it takes. Against the
    gathered-view oracle, and bit for bit against the kernel taking one item
    a step as it did before ISSUE 61: the same folds in the same order."""
    monkeypatch.setattr(dec, "_PAGED_ITEM_PAGES", C)
    L, hd, P, G = 2, 16, 8, 6
    nb = 2 * C + 1
    rows = [  # (start, filled, live), the items each has
        ((0, held * P - 3, True), 1),
        ((0, (C + held) * P - 5, True), 2),
        ((4, 4 + held * P, False), 0),
        ((P + 3, (1 + held) * P - 2, True), 1),
        ((P + 3, (1 + C + held) * P - 1, True), 2),
        ((0, held * P, True), 1),
        ((2, 9, False), 0),
    ]
    works = [0, 1, 3, 4, 5]
    B = len(rows)
    N = B * nb
    table = jnp.asarray(rng.permutation(N).reshape(B, nb).astype(np.int32))
    k_pool, v_pool = (
        jnp.asarray(rng.standard_normal((L, N, KV, P, hd)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), dtype)
    start, filled, live = (jnp.asarray(c) for c in zip(*(r for r, _ in rows)))
    start, filled = start.astype(jnp.int32), filled.astype(jnp.int32)
    assert dec.paged_pages_per_item(k_pool) == C
    plan = dec.paged_decode_plan(table, start, filled, page_size=P,
                                 num_pages=N, pages_per_item=C, live=live)
    np.testing.assert_array_equal(np.diff(np.asarray(plan.row_off)),
                                  [n for _, n in rows])

    def read(items_a_step):
        monkeypatch.setattr(dec, "_PAGED_STEP_ITEMS", items_a_step)
        assert dec._paged_items_per_step(k_pool, C) == items_a_step
        return np.asarray(dec.paged_decode_attention(
            q, k_pool, v_pool, jnp.int32(1), plan, interpret=True), np.float32)

    got = read(4)
    want = np.asarray(dec.reference_paged_decode_attention(
        q, k_pool[1], v_pool[1], table, start, filled), np.float32)
    np.testing.assert_allclose(got[works], want[works], rtol=tol, atol=tol)
    assert not got[[2, 6]].any()
    np.testing.assert_array_equal(got, read(1))


@pytest.mark.parametrize("KV,item_pages,want", [
    (1, 4, 4), (2, 4, 4), (4, 4, 1), (8, 2, 1), (16, 1, 1)])
def test_small_items_are_folded_several_a_step(KV, item_pages, want):
    """What the chip's pools get: an item of half `_PAGED_ITEM_BYTES` of K
    or less (Qwen2.5's 2 KV heads: 4 pages of 64 KB) is one of four a step,
    an item that fills it (SmallThinker's, LFM2's and SDAR's 4 heads,
    Trinity's 8, OLMoE's 16) is a step by itself."""
    pool = jax.ShapeDtypeStruct((2, 8, KV, 128, 128), jnp.bfloat16)
    assert dec.paged_pages_per_item(pool) == item_pages
    assert dec._paged_items_per_step(pool, item_pages) == want
