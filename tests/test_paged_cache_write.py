"""The paged K/V write in its three forms (ISSUE 41, docs/PAGED_CACHE.md "The
write"): the row scatter (`core/model._paged_row_scatter`: the plain form,
and the oracle here), the write by page (`_paged_page_write`: what
`_paged_cache_update` takes for a page of tokens or more) and the decode
step's live rows in place (ops/paged_cache_write, in interpret mode). Every
form, and the one slice the identity table's write is (ISSUE 52), must
leave the pool BIT-identical to the row scatter's: the write copies
values and computes nothing (the one exception is named where it is tested:
the slot a DONE row would rewrite, which the live-row kernel skips). The chip's compiler is asked in
tests/test_chip_compile.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import model as M
from nanorlhf_tpu.ops.paged_cache_write import (
    paged_row_write, paged_write_plan, sublanes,
)
from nanorlhf_tpu.sampler.paged.pages import RingPages, ring_blocks

L, LAYER = 2, 1


def bits(x):
    """The array's bits, whatever its dtype: NaN-proof equality."""
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def noise(seed, shape, dtype=jnp.bfloat16):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    if dtype == jnp.int8:
        return (x * 40).astype(jnp.int8)
    return x.astype(dtype)


def own_pages(rng, B, nb, extra=3):
    """A table of B x nb distinct pages in a pool of `extra` more."""
    n = B * nb + extra
    return rng.permutation(n)[:B * nb].reshape(B, nb).astype(np.int32), n


def both(pool, new, table, starts, P, update=M._paged_cache_update):
    """(the row scatter's pool, `update`'s), jitted with the layer traced."""
    args = (pool, new, jnp.int32(LAYER), jnp.asarray(table), starts)
    return (jax.jit(M._paged_row_scatter, static_argnums=5)(*args, P),
            jax.jit(update, static_argnums=5)(*args, P))


# (page size, KV heads, head width): pages of 4 and of 128; one latent "head"
# 512 wide (A.X-K1's c_kv leaf); LFM2's eight heads of 64 packed in pairs
GEOMETRIES = {"p4_kv1": (4, 1, 128), "p4_kv2": (4, 2, 128),
              "p4_kv4": (4, 4, 128), "p128_kv2": (128, 2, 128),
              "p128_latent512": (128, 1, 512), "p128_lfm2_packed": (128, 4, 128)}
LENGTHS = {"T1": lambda P: 1, "T3": lambda P: 3, "Tpage": lambda P: P,
           "T2pages1": lambda P: 2 * P + 1, "T1024": lambda P: 1024}


@pytest.mark.parametrize("rows", ["one_row", "four_rows_each_its_slot"])
@pytest.mark.parametrize("start", ["page_aligned", "slot_7"])
@pytest.mark.parametrize("length", list(LENGTHS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_pool_is_the_row_scatters_bit_for_bit(geometry, length, start, rows):
    P, KV, hd = GEOMETRIES[geometry]
    T = LENGTHS[length](P)
    B = 1 if rows == "one_row" else 4
    first = 0 if start == "page_aligned" else 7
    # per-row slots: the first row at `first`, the others pages further on
    # and (from slot 7) each at another offset of its page
    starts = first + np.arange(B) * (P + (1 if first else 0))
    nb = (int(starts.max()) + T + P - 1) // P + 1
    rng = np.random.default_rng(0)
    table, n_pages = own_pages(rng, B, nb)
    if geometry == "p128_lfm2_packed":
        # the packed layout as the model makes it: 8 heads of 64 -> 4 of 128
        q = noise(9, (B, 16, T, 64))
        _, new, _ = M._pack_heads(q, noise(1, (B, 8, T, 64)),
                                  noise(2, (B, 8, T, 64)), 2)
    else:
        new = noise(1, (B, KV, T, hd))
    pool = noise(0, (L, n_pages, KV, P, hd))
    want, got = both(pool, new, table, jnp.asarray(starts, jnp.int32), P)
    assert M._page_write_takes(pool, T, P, nb) == (T >= P or T * KV >= 32)
    np.testing.assert_array_equal(bits(got), bits(want))
    assert not np.array_equal(bits(want), bits(pool))
    # and nothing outside the layer's own T x B slots moved
    assert np.array_equal(bits(got[0]), bits(pool[0]))


def _sentinels(rng):
    """Sentinel entries inside the written range and past it: those blocks'
    tokens drop, the others land."""
    P, T = 4, 19
    table, n = own_pages(rng, 2, 8)
    table[0, 2] = table[1, 1] = table[1, 5] = n
    return dict(P=P, table=table, n=n, starts=[5, 3], T=T)


def _past_budget(rng):
    """PR 34's review case: a bucket of 32 written from slot 62 by a row
    whose budget ends 8 tokens on (its last block is 8): the bucket's pad
    tokens reach blocks 9-11, which hold the sentinel, and past the table."""
    P = 8
    table, n = own_pages(rng, 1, 11)
    table[0, 9:] = n
    return dict(P=P, table=table, n=n, starts=[62], T=32)


def _past_table(rng):
    """The write runs off the table's end: blocks past it drop."""
    P = 4
    table, n = own_pages(rng, 2, 6)
    return dict(P=P, table=table, n=n, starts=[14, 17], T=12)


def _wrapped_ring(rng):
    """A window ring that has wrapped: the row is far past its window, the
    piece's blocks live in ring entries that held earlier blocks."""
    P, window, T = 4, 16, 12
    ring = ring_blocks(window, P, T)
    pages = RingPages(2 * ring + 3, 2, 40, ring)
    pages._free = list(rng.permutation(2 * ring + 3))
    pages.claim(0, 1, 39)
    pages.claim(1, 0, 30)
    return dict(P=P, table=pages.table.copy(), n=pages.num_pages,
                starts=[97, 70], T=T)


def _shared_slot(rng):
    """One scalar slot for every row (the rollout's prefill and decode)."""
    P = 4
    table, n = own_pages(rng, 3, 6)
    return dict(P=P, table=table, n=n, starts=5, T=9)


def _narrow_table(rng):
    """A table with fewer blocks than one write touches cannot hold its
    pages apart: the row scatter stays."""
    P = 4
    table, n = own_pages(rng, 2, 3)
    return dict(P=P, table=table, n=n, starts=[0, 2], T=16, by_page=False)


def _int8(rng):
    """The int8 pool's values go row by row beside their scales."""
    P = 4
    table, n = own_pages(rng, 2, 6)
    return dict(P=P, table=table, n=n, starts=[3, 8], T=9, dtype=jnp.int8,
                by_page=False)


SPECIAL = {f.__name__.strip("_"): f for f in (
    _sentinels, _past_budget, _past_table, _wrapped_ring, _shared_slot,
    _narrow_table, _int8)}


@pytest.mark.parametrize("case", list(SPECIAL))
def test_what_the_row_scatter_drops_the_page_write_drops(case):
    c = SPECIAL[case](np.random.default_rng(1))
    P, T, table = c["P"], c["T"], c["table"]
    B, dtype = table.shape[0], c.get("dtype", jnp.bfloat16)
    pool = noise(0, (L, c["n"], 2, P, 128), dtype)
    new = noise(1, (B, 2, T, 128), dtype)
    starts = c["starts"]
    starts = (jnp.int32(starts) if isinstance(starts, int)
              else jnp.asarray(starts, jnp.int32))
    want, got = both(pool, new, table, starts, P)
    assert M._page_write_takes(pool, T, P, table.shape[1]) == c.get(
        "by_page", True)
    np.testing.assert_array_equal(bits(got), bits(want))
    assert not np.array_equal(bits(want), bits(pool))


@pytest.mark.parametrize("KV,T,by_page", [(2, 16, True), (2, 15, False),
                                          (4, 8, True), (1, 31, False),
                                          (1, 32, True), (2, 1, False)])
def test_a_write_shorter_than_a_page_goes_by_page_from_32_rows(KV, T, by_page):
    """`_PAGE_WRITE_MIN_ROWS`: where the two forms crossed on the chip. A
    short suffix bucket at or over it reads and writes back the two pages
    it can touch, and leaves the same pool."""
    P = 128
    table, n = own_pages(np.random.default_rng(2), 2, 3)
    pool, new = noise(0, (L, n, KV, P, 128)), noise(1, (2, KV, T, 128))
    assert M._page_write_takes(pool, T, P, 3) == by_page
    want, got = both(pool, new, table, jnp.asarray([120, 250], jnp.int32), P)
    np.testing.assert_array_equal(bits(got), bits(want))


# --------------------------------------------------------------------------- #
# the decode step's live rows, in place (ops/paged_cache_write), interpreted
# --------------------------------------------------------------------------- #

def _kernel(pools, news, table, starts, P, live=None):
    plan = paged_write_plan(jnp.asarray(table), starts, page_size=P,
                            num_pages=pools[0].shape[1], live=live)
    return jax.jit(paged_row_write)(
        *pools, news[0][:, :, 0], news[1][:, :, 0], jnp.int32(LAYER), plan)


def scattered(pools, news, table, starts, P):
    """The K and V pools as the row scatter leaves them."""
    return [M._paged_row_scatter(pool, new, jnp.int32(LAYER),
                                 jnp.asarray(table), starts, P)
            for pool, new in zip(pools, news)]


ROW_CASES = {
    # rows 1 and 3 released: their table rows are the sentinel
    "sentinel_rows": dict(starts=[5, 40, 17, 9], released=[1, 3]),
    # a slot in each half of a bf16 tile's sixteen rows, and at both ends
    "both_halves_of_a_tile": dict(starts=[3, 11, 16, 31]),
    # done rows hold their pages until released and are skipped: the one
    # place where the pool may differ from the scatter's is their own slot
    "done_rows": dict(starts=[0, 15, 33, 62], done=[0, 2]),
    "every_row_done": dict(starts=[0, 15, 33, 62], done=[0, 1, 2, 3]),
    # a row past its budget: the slot's block is past the table
    "past_the_table": dict(starts=[7, 64, 70, 20]),
    "one_shared_slot": dict(starts=21, B=3),
    "float32_pool": dict(starts=[3, 11, 16, 31], dtype=jnp.float32),
    "four_heads": dict(starts=[5, 40, 17, 9], KV=4),
    "one_row": dict(starts=[37]),
}


@pytest.mark.parametrize("case", list(ROW_CASES))
def test_live_row_kernel_leaves_the_row_scatters_pool(case):
    c = ROW_CASES[case]
    P, KV, dtype = 16, c.get("KV", 2), c.get("dtype", jnp.bfloat16)
    shared = isinstance(c["starts"], int)
    B = c["B"] if shared else len(c["starts"])
    starts = jnp.asarray(c["starts"], jnp.int32)
    table, n = own_pages(np.random.default_rng(3), B, 4)
    table[c.get("released", [])] = n
    live = np.ones((B,), bool)
    live[c.get("done", [])] = False
    pools = [noise(i, (L, n, KV, P, 128), dtype) for i in (0, 1)]
    news = [noise(i, (B, KV, 1, 128), dtype) for i in (2, 3)]
    got = _kernel(pools, news, table, starts, P,
                  jnp.asarray(live) if "done" in c else None)
    # the scatter where the done rows' table rows are the sentinel IS the
    # pool with every live row's slot written and nothing else
    table = table.copy()
    table[~live] = n
    for pool, want, out in zip(pools, scattered(pools, news, table, starts, P),
                               got):
        np.testing.assert_array_equal(bits(out), bits(want))
        assert live.any() == (not np.array_equal(bits(want), bits(pool)))


def test_live_row_kernel_works_through_its_rows_in_batches(monkeypatch):
    """More rows than one batch holds in VMEM: two batches of two of the
    four rows that write, the last row's alone."""
    from nanorlhf_tpu.ops import paged_cache_write

    P, KV, B = 16, 2, 6
    monkeypatch.setattr(paged_cache_write, "_BATCH_BYTES",
                        2 * 2 * KV * sublanes(jnp.bfloat16) * 128 * 2)
    table, n = own_pages(np.random.default_rng(4), B, 4)
    table[2] = n
    starts = jnp.asarray([1, 18, 35, 52, 63, 40], jnp.int32)
    pools = [noise(i, (L, n, KV, P, 128)) for i in (0, 1)]
    news = [noise(i, (B, KV, 1, 128)) for i in (2, 3)]
    got = _kernel(pools, news, table, starts, P)
    for want, out in zip(scattered(pools, news, table, starts, P), got):
        np.testing.assert_array_equal(bits(out), bits(want))


def test_write_plan_lists_the_rows_that_write_first():
    table = np.full((6, 2), 9, np.int32)
    table[[1, 3, 4]] = [[0, 1], [2, 3], [4, 5]]
    plan = paged_write_plan(
        jnp.asarray(table), jnp.asarray([3, 5, 0, 9, 2, 1], jnp.int32),
        page_size=4, num_pages=9,
        live=jnp.asarray([True, True, True, True, False, True]))
    # row 3's slot 9 is past its table of two blocks; row 4 is done
    assert int(plan.n[0]) == 1 and int(plan.row[0]) == 1
    assert (int(plan.page[1]), int(plan.off[1])) == (1, 1)
    plan = paged_write_plan(jnp.asarray(table), jnp.int32(6), page_size=4,
                            num_pages=9)
    assert int(plan.n[0]) == 3
    assert plan.row[:3].tolist() == [1, 3, 4]
    assert plan.page[jnp.asarray([1, 3, 4])].tolist() == [1, 3, 5]
    assert set(plan.off.tolist()) == {2}


@pytest.mark.parametrize("pool_kind", ["bf16_pages_of_16", "pages_of_4",
                                       "int8", "wide_rows_are_taken_too",
                                       "float32_pages_of_8"])
def test_a_decode_step_takes_the_kernel_only_where_it_can(pool_kind,
                                                          monkeypatch):
    """`decode_step` under `use_paged_decode_kernel`'s rule
    (`attention_impl="pallas"` here) hands `_cache_write` a plan where the
    pool is one the kernel takes: a (k, v) pool of whole 128-lane rows and
    whole tiles a page. `_kind_views` decides; `_cache_write` follows
    the plan it is given and agrees with the scatter bit for bit."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig

    P, hd, dtype = 16, 128, jnp.bfloat16
    if pool_kind == "pages_of_4":
        P = 4
    if pool_kind == "float32_pages_of_8":
        P, dtype = 8, jnp.float32
    if pool_kind == "wide_rows_are_taken_too":
        hd = 512
    B = 3
    table, n = own_pages(np.random.default_rng(5), B, 4)
    starts = jnp.asarray([2, 9, 13], jnp.int32)
    if pool_kind == "int8":
        stacks = (noise(0, (L, n, 2, P, hd), jnp.int8),
                  noise(1, (L, n, 2, 8, P)),
                  noise(2, (L, n, 2, P, hd), jnp.int8),
                  noise(3, (L, n, 2, 8, P)))
    else:
        stacks = tuple(noise(i, (L, n, 2, P, hd), dtype) for i in (0, 1))
    cfg = dataclasses.replace(
        ModelConfig.qwen2_tiny(), attention_impl="pallas",
        kv_cache_quant="int8" if pool_kind == "int8" else "none")
    def view(cfg):
        """The step's `KindView` as `decode_step` makes it."""
        key_mask = jnp.arange(4 * P)[None, :] <= starts[:, None]
        return M._kind_views(
            cfg, key_mask[:, None, None, :], lambda: starts[:, None],
            kv_caches=stacks, index=starts,
            decode=(jnp.zeros((B,), jnp.int32), starts + 1),
            page_table=jnp.asarray(table), page_size=P)[0]

    planned = view(cfg)
    taken = pool_kind in ("bf16_pages_of_16", "wide_rows_are_taken_too",
                          "float32_pages_of_8")
    assert (planned.write_plan is not None) == taken
    assert planned.cache == ("int8" if pool_kind == "int8" else "exact")
    scattered = view(dataclasses.replace(cfg, attention_impl="xla"))
    assert scattered.write_plan is None
    if pool_kind == "int8":
        return
    news = tuple(noise(i, (B, 2, 1, hd), dtype) for i in (2, 3))
    write = lambda view: lambda s, nw: M._cache_write(   # noqa: E731
        s, nw, jnp.int32(LAYER), view)
    assert ("pallas_call" in str(jax.make_jaxpr(write(planned))(stacks, news))
            ) == taken
    for a, b in zip(jax.jit(write(planned))(stacks, news),
                    jax.jit(write(scattered))(stacks, news)):
        np.testing.assert_array_equal(bits(a), bits(b))


@pytest.mark.parametrize("slot", [0, 7, 16, 37, 47])
@pytest.mark.parametrize("pool_kind", ["bf16_pages_of_16", "bf16_pages_of_128",
                                       "float32_pages_of_8", "pages_of_4",
                                       "kv16"])
def test_the_identity_tables_slot_write_is_the_row_scatters(pool_kind, slot):
    """Under the dense identity table with every row at one slot (the
    one-jit rollout's decode step, ISSUE 52) the write is one slice through
    the `[L, B, nb, KV, tiles, sublanes, hd]` view of the pool
    (`_identity_slot_write`), whatever the page holds in tiles: the pool
    comes out the row scatter's bit for bit, in the layer asked for and no
    other, and `decode_step(identity_table=True)` hands `_cache_write` that
    form wherever it would have handed it a live-row plan."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig
    from nanorlhf_tpu.sampler.paged.pages import full_table

    P, KV, dtype = {"bf16_pages_of_16": (16, 2, jnp.bfloat16),
                    "bf16_pages_of_128": (128, 2, jnp.bfloat16),
                    "float32_pages_of_8": (8, 2, jnp.float32),
                    "pages_of_4": (4, 2, jnp.bfloat16),
                    "kv16": (16, 16, jnp.bfloat16)}[pool_kind]
    B, hd = 3, 128
    nb = -(-48 // P)
    table = full_table(B, nb)
    stacks = tuple(noise(i, (L, B * nb, KV, P, hd), dtype) for i in (0, 1))
    news = tuple(noise(i, (B, KV, 1, hd), dtype) for i in (2, 3))
    at = jnp.int32(slot)
    for pool, new in zip(stacks, news):
        want = jax.jit(M._paged_row_scatter, static_argnums=5)(
            pool, new, jnp.int32(LAYER), table, at, P)
        got = jax.jit(M._identity_slot_write)(
            pool, new, jnp.int32(LAYER), at)
        np.testing.assert_array_equal(bits(got), bits(want))
        assert not np.array_equal(bits(got[LAYER]), bits(pool[LAYER]))
        np.testing.assert_array_equal(bits(got[0]), bits(pool[0]))

    def view(cfg, identity):
        key_mask = jnp.broadcast_to(jnp.arange(nb * P)[None, :] <= slot,
                                    (B, nb * P))
        return M._kind_views(
            cfg, key_mask[:, None, None, :],
            lambda: jnp.full((B, 1), slot), kv_caches=stacks, index=at,
            decode=(jnp.zeros((B,), jnp.int32), jnp.full((B,), slot + 1)),
            page_table=table, page_size=P, identity_table=identity)[0]

    cfg = dataclasses.replace(ModelConfig.qwen2_tiny(),
                              attention_impl="pallas")
    taken = pool_kind != "pages_of_4"    # what the live-row kernel takes
    assert (view(cfg, True).write_plan is M.IDENTITY_SLOT) == taken
    assert (view(cfg, False).write_plan is not None) == taken
    assert view(cfg, False).write_plan is not M.IDENTITY_SLOT
    xla = dataclasses.replace(cfg, attention_impl="xla")
    assert view(xla, True).write_plan is None
    write = lambda view: lambda s, nw: M._cache_write(   # noqa: E731
        s, nw, jnp.int32(LAYER), view)
    jaxpr = str(jax.make_jaxpr(write(view(cfg, True)))(stacks, news))
    assert "pallas_call" not in jaxpr and "scatter" not in jaxpr or not taken
    for a, b in zip(jax.jit(write(view(cfg, True)))(stacks, news),
                    jax.jit(write(view(xla, False)))(stacks, news)):
        np.testing.assert_array_equal(bits(a), bits(b))


# --------------------------------------------------------------------------- #
# the precondition: the pages one write touches are distinct
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("window,P,longest", [
    (4096, 128, 1024),      # the serving cell: a ring of 42, a piece of nine
    (4096, 128, 16384), (16, 4, 12), (16, 4, 1), (32, 8, 32), (5, 4, 7),
    (1, 4, 4), (100, 16, 17)])
def test_ring_blocks_keeps_the_pages_of_the_longest_write_distinct(
        window, P, longest):
    """`_paged_page_write`'s precondition for a window ring: the blocks one
    write of up to `longest` tokens touches, from any start, lie in distinct
    pages of the ring `ring_blocks` sizes (or in none: the sentinel)."""
    ring = ring_blocks(window, P, longest)
    n = M._touched_blocks(longest, P)
    assert n <= ring
    nb = 3 * ring + 5
    pages = RingPages(ring + 2, 1, nb, ring)
    for first, last in ((0, nb - 1), (2, nb - 3), (1, 3)):
        pages.claim(0, first, last)
        row = pages.table[0]
        for lb in range(nb):
            touched = row[lb:lb + n]
            real = touched[touched < pages.num_pages]
            assert len(set(real.tolist())) == len(real), (first, last, lb)


def test_touched_blocks_counts_the_partial_first_and_last_page():
    """A serving piece does NOT start page-aligned (a prompt is left-padded,
    its first real token sits at slot `Tp - len`): 1,024 tokens of pages of
    128 touch NINE pages, the first and the last in part."""
    assert M._touched_blocks(1024, 128) == 9
    assert M._touched_blocks(1, 128) == 1
    assert M._touched_blocks(128, 128) == 2
    assert M._touched_blocks(2, 4) == 2
    for T in (1, 2, 3, 4, 5, 9, 64):
        for P in (2, 4, 8):
            most = max((s + T - 1) // P - s // P + 1 for s in range(3 * P))
            assert M._touched_blocks(T, P) == most
