"""The pattern model's T > 1 paged read as a flash kernel over the pages in
place (ops/paged_prefill_attention.py, ISSUE 37), in interpret mode, against
its two oracles: the XLA block walk it stands in for on a TPU
(`core/model._attend_paged_blocks`, handed the mask `_kind_views` builds) and
plain attention over the row as it was written, slot by slot
(`reference_attention` for a global layer; the same sums under the window's
mask for a window layer). Tiny widths: pages of 8, a window of 32, blocks of
16 queries and items of 2 pages so that every case crosses several of each.
The chip's compiler is asked in tests/test_chip_compile.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import ModelConfig
from nanorlhf_tpu.core import model as M
from nanorlhf_tpu.ops.attention import reference_attention
from nanorlhf_tpu.ops.paged_prefill_attention import paged_prefill_attention
from nanorlhf_tpu.sampler.paged.pages import RingPages, ring_blocks

P, NB, W, KV, G, HD, L, LAYER = 8, 16, 32, 2, 3, 16, 2, 1
S = NB * P
CFG = ModelConfig.smallthinker_tiny(window=W)

# (start, fill) a row, T, real queries (None: all), the row's last block
CASES = {
    # the first piece of a row, from a `start` inside a page
    "first_piece": dict(rows=[(5, 5)], T=32),
    # keys and queries all closer than a window
    "inside_window": dict(rows=[(3, 11)], T=16),
    # the first query already past the window: the ring has wrapped
    "past_window": dict(rows=[(3, 67)], T=32),
    # a bucketed suffix: 24 slots of which 12 are the prompt's, the rest pad
    # tokens past the row's budget (blocks 14, 15 are the sentinel)
    "ragged_last_piece": dict(rows=[(0, 100)], T=24, real=12, last_block=13),
    # two rows at unequal depths, one of them from inside a page
    "two_rows": dict(rows=[(5, 21), (0, 70)], T=32),
    # one query: the smallest bucket
    "one_token": dict(rows=[(2, 45)], T=1),
}


def written_row(rng, rows, T, window, last_block, dtype):
    """Pools, tables and the rows as they were written: K/V [B, KV, S, hd]
    by slot. The pool starts as noise; each row's slots `[start, fill + T)`
    are written through its table block by block in order, so a wrapped
    ring holds the later block, as on the device."""
    B = len(rows)
    ring = ring_blocks(W, P, 32)
    n_pages = B * NB + 3 if not window else B * ring + 3
    pools = [rng.standard_normal((L, n_pages, KV, P, HD)).astype(np.float32)
             for _ in "kv"]
    rowkv = [rng.standard_normal((B, KV, S, HD)).astype(np.float32)
             for _ in "kv"]
    if window:
        pages = RingPages(n_pages, B, NB, ring)
        pages._free = list(rng.permutation(n_pages))
        for r, (start, _) in enumerate(rows):
            pages.claim(r, start // P, last_block)
        table = pages.table.copy()
    else:
        table = np.full((B, NB), n_pages, np.int32)
        free = rng.permutation(n_pages)
        for r, (start, _) in enumerate(rows):
            n = last_block + 1 - start // P
            table[r, start // P:last_block + 1] = free[r * NB:r * NB + n]
    for r, (start, fill) in enumerate(rows):
        for blk in range(start // P, min((fill + T - 1) // P, NB - 1) + 1):
            if table[r, blk] < n_pages:
                for pool, row in zip(pools, rowkv):
                    pool[LAYER, table[r, blk]] = row[r, :, blk * P:(blk + 1) * P]
    cast = lambda a: jnp.asarray(a).astype(dtype)  # noqa: E731
    return [cast(p) for p in pools], jnp.asarray(table), [cast(a) for a in rowkv]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", ["global", "window"])
def test_kernel_is_the_walk_and_the_plain_read(kind, case, dtype):
    spec = CASES[case]
    rows, T = spec["rows"], spec["T"]
    real, last_block = spec.get("real") or T, spec.get("last_block", NB - 1)
    window = W if kind == "window" else 0
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(sorted(CASES).index(case))
    pools, table, (k_row, v_row) = written_row(rng, rows, T, window, last_block,
                                               dtype)
    B = len(rows)
    q = jnp.asarray(rng.standard_normal((B, KV * G, T, HD))).astype(dtype)
    start = jnp.asarray([r[0] for r in rows], jnp.int32)
    fill = jnp.asarray([r[1] for r in rows], jnp.int32)

    # the mask and the bounds as `decode_verify` makes them for the layers
    slot = jnp.arange(S)[None, None, :]
    qi = jnp.arange(T)[None, :, None]
    key_mask = (slot[0] >= start[:, None]) & (slot[0] < fill[:, None])
    cand = (slot >= fill[:, None, None]) & (slot <= fill[:, None, None] + qi)
    mask = (key_mask[:, None, :] | cand)[:, None]
    w = int(kind == "window")
    view = M._kind_views(CFG, mask, lambda: fill[:, None] + jnp.arange(T)[None],
                         verify=(start, fill))[w]
    mask, (first, bound) = view.mask, view.verify

    got = paged_prefill_attention(q, *pools, LAYER, table, first, bound, window,
                                  block_q=16, pages_per_item=2, interpret=True)
    walk = M._attend_paged_blocks(pools, LAYER, table, P, mask, first,
                                  bound + (T - 1), q)
    # plain attention over the row by slot, under the same mask
    qg = q.reshape(B, KV, G, T, HD)
    s = jnp.einsum("bkgqh,bkth->bkgqt", qg, k_row).astype(jnp.float32)
    s = jnp.where(mask[:, :, None], s / np.sqrt(HD), -1e30)
    plain = jnp.einsum("bkgqt,bkth->bkgqh",
                       jax.nn.softmax(s, -1).astype(dtype), v_row)
    plain = plain.reshape(B, KV * G, T, HD)

    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, want in (("walk", walk), ("plain", plain)):
        np.testing.assert_allclose(
            np.asarray(got[:, :, :real], np.float32),
            np.asarray(want[:, :, :real], np.float32), atol=tol, rtol=tol,
            err_msg=name)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    if kind == "global":
        # and the repo's own reference, a row at a time: queries at the
        # row's tail, keys valid from its start
        for r, (st, fl) in enumerate(rows):
            n = fl + real
            q_row = jnp.zeros((1, KV * G, n, HD), dtype).at[:, :, fl:].set(
                q[r:r + 1, :, :real])
            want = reference_attention(q_row, k_row[r:r + 1, :, :n],
                                       v_row[r:r + 1, :, :n],
                                       jnp.arange(n)[None] >= st)[:, :, fl:]
            np.testing.assert_allclose(
                np.asarray(got[r:r + 1, :, :real], np.float32),
                np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_default_blocks_cover_a_table_shorter_than_an_item():
    """The chip's block sizes on a small table: one item holds every page
    (`pages_per_item` is cut to the table), one block every query."""
    rng = np.random.default_rng(9)
    pools, table, (k_row, v_row) = written_row(rng, [(3, 40)], 24, 0, NB - 1,
                                               jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, KV * G, 24, HD)), jnp.float32)
    got = paged_prefill_attention(q, *pools, LAYER, table, jnp.asarray([3]),
                                  jnp.asarray([40]), interpret=True)
    q_row = jnp.zeros((1, KV * G, 64, HD)).at[:, :, 40:].set(q)
    want = reference_attention(q_row, k_row[:, :, :64], v_row[:, :, :64],
                               jnp.arange(64)[None] >= 3)[:, :, 40:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
