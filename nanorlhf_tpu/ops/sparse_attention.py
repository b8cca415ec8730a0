"""The sparse layer's decode read over pages (docs/SALA.md): single-token
attention that fetches the pages of the blocks a row CHOSE and no others.

`ops/decode_attention.paged_decode_attention` walks a row's pages `[start //
P, (filled - 1) // P]`; here a row's work is a list of RUNS of slots, made
from the layer's own selection (`core/sala.select_blocks`), and differs by
KV head, since a KV head's group of query heads selects for itself. So the
kernel's "rows" are `(row, KV head)` pairs, an item is up to C consecutive
pages of ONE head (`pool[layer, page, head]`, `[P, hd]` contiguous) with
its own slot bounds `[lo, hi)`, and a pair's first and last items are named
by the work list, not by its bounds. The fold is
`decode_attention._paged_item_fold`, the ring of buffers and the copies
ahead are `_paged_decode_kernel`'s.

A row that does not select (fewer keys than `sparse_dense_len`) is the one
run `[start, filled)` and reads what the dense kernel reads. A selecting
row's runs: the local window as one run (`sparse_window_size /
sparse_block_size` blocks that end at the query's own, up to `filled`), and
each other chosen block as a run of its own (one item of one or two pages:
blocks are cut by POSITION, and a served row's position 0 lies at an
arbitrary slot). A chosen block thus costs the fetch of the pages it
touches, whole."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.attention import NEG_INF, _interpret_default
from nanorlhf_tpu.ops.decode_attention import (
    _PAGED_TILE_BYTES, _paged_item_fold,
)

# pages one work item covers at most
_ITEM_PAGES = 4


class SparseDecodePlan(NamedTuple):
    """One layer's work list of one decode step (`sparse_decode_plan`): a
    flat list of items over the live rows' `(row, KV head)` pairs, pairs in
    order; item i reads slots `[lo[i], hi[i])` of the pages from logical
    block `blk[i]` on."""
    pair_off: jnp.ndarray   # [B KV + 1] items of pair p: [off[p], off[p + 1])
    item_pair: jnp.ndarray  # [B KV M]
    item_blk: jnp.ndarray   # same: the item's first logical block
    item_lo: jnp.ndarray    # same: its first slot
    item_hi: jnp.ndarray    # same: one past its last slot
    table: jnp.ndarray      # [B, nb] the block table (sentinel = num_pages)


def plan_items(config, page_size: int) -> tuple:
    """`(C, M)`: pages an item covers at most, and the most items a `(row,
    KV head)` pair has: a selecting pair's chosen blocks and its local
    window's run, or a dense pair's `sparse_dense_len` slots."""
    P, block, C = page_size, config.sparse_block_size, _ITEM_PAGES
    if (block + P - 2) // P + 1 > C:
        raise ValueError(
            f"a block of {block} slots (sparse_block_size) can span more "
            f"than the {C} pages of {P} one work item reads")
    items = lambda slots: -(-(-(-slots // P) + 1) // C)          # noqa: E731
    return C, max(config.sparse_topk + items(config.sparse_window_size),
                  items(config.sparse_dense_len))


def plan_rows(start, filled, live, table, *, page_size: int, num_pages: int):
    """[B] bool, the rows a step's work list has items for: something valid
    (`filled > start`), the row's last block not the sentinel (a released
    row's is), and `live` ([B] bool or None)."""
    last_blk = jnp.clip((filled - 1) // page_size, 0, table.shape[1] - 1)
    has = (filled > start) & (
        jnp.take_along_axis(table, last_blk[:, None], axis=1)[:, 0] < num_pages)
    return has if live is None else has & live


def sparse_decode_plan(config, idx, ok, start, filled, selects, live, table,
                       *, page_size: int, num_pages: int) -> SparseDecodePlan:
    """The work list of one sparse layer's decode step. `idx`, `ok` [B, KV,
    k]: each pair's chosen blocks (`select_blocks`); `start`, `filled` [B]:
    the row's first valid slot and one past its last; `selects` [B] bool: the
    rows that read their chosen blocks (the others read `[start, filled)`);
    `live` [B] bool or None; `table` [B, nb]. A row with nothing valid, a
    released row (its last block is the sentinel) and a row not live have no
    items and read zero."""
    P, block = page_size, config.sparse_block_size
    local = config.sparse_window_size // block
    C, M = plan_items(config, P)
    B, KV, k = idx.shape
    nb = table.shape[1]
    i32 = jnp.int32
    start, filled = start.astype(i32), filled.astype(i32)
    last_blk = jnp.clip((filled - 1) // P, 0, nb - 1)
    has = plan_rows(start, filled, live, table, page_size=P,
                    num_pages=num_pages)
    own = (filled - 1 - start) // block                     # [B] the query's
    near = jnp.maximum(own - local + 1, 0)                  # first local block
    # a selecting pair: its chosen blocks outside the local window, one item
    # each, then the local window's run in items of C pages
    far_lo = start[:, None, None] + block * idx             # [B, KV, k]
    far_on = ok & (idx < near[:, None, None])
    run_lo = start + block * near                           # [B]
    m = jnp.arange(M - k, dtype=i32)[None, :]
    run_blk = (run_lo // P)[:, None] + C * m                # [B, M - k]
    run_on = run_blk <= last_blk[:, None]
    pair = lambda a: jnp.broadcast_to(a[:, None, :], (B, KV, a.shape[1]))  # noqa: E731
    s_lo = jnp.concatenate([far_lo, pair(jnp.broadcast_to(
        run_lo[:, None], run_blk.shape))], axis=2)
    s_hi = jnp.concatenate([far_lo + block, pair(jnp.broadcast_to(
        filled[:, None], run_blk.shape))], axis=2)
    s_blk = jnp.concatenate([far_lo // P, pair(run_blk)], axis=2)
    s_on = jnp.concatenate([far_on, pair(run_on)], axis=2)
    # any other pair: `[start, filled)` in items of C pages
    m = jnp.arange(M, dtype=i32)[None, :]
    d_blk = (start // P)[:, None] + C * m                   # [B, M]
    d_on = d_blk <= last_blk[:, None]
    sel = selects[:, None, None]
    blk = jnp.where(sel, s_blk, pair(d_blk))
    lo = jnp.where(sel, s_lo, start[:, None, None])
    hi = jnp.where(sel, s_hi, filled[:, None, None])
    on = jnp.where(sel, s_on, pair(d_on)) & has[:, None, None]
    # a pair's items first, in order
    order = jnp.argsort(~on, axis=2, stable=True)
    blk, lo, hi = (jnp.take_along_axis(a, order, axis=2).reshape(B * KV, M)
                   for a in (blk, lo, hi))
    n = jnp.sum(on, axis=2, dtype=i32).reshape(B * KV)
    pair_off = jnp.concatenate([jnp.zeros((1,), i32),
                                jnp.cumsum(n, dtype=i32)])
    i = jnp.arange(B * KV * M, dtype=i32)
    p = jnp.minimum(jnp.sum(i[:, None] >= pair_off[None, 1:], axis=1,
                            dtype=i32), B * KV - 1)
    at = jnp.clip(i - pair_off[p], 0, M - 1)
    return SparseDecodePlan(pair_off, p, jnp.clip(blk[p, at], 0, nb - 1),
                            lo[p, at], hi[p, at], table.astype(i32))


def reference_sparse_decode(q, k_pool, v_pool, layer, plan: SparseDecodePlan,
                            KV: int):
    """The plan's read in plain jnp (the kernel's oracle): every pair's
    slots gathered through the table under the union of its items' bounds."""
    B, H, hd = q.shape
    P = k_pool.shape[3]
    nb = plan.table.shape[1]
    pairs = B * KV
    slot = jnp.arange(nb * P, dtype=jnp.int32)
    i = jnp.arange(plan.item_pair.shape[0], dtype=jnp.int32)
    real = i < plan.pair_off[-1]
    C = _ITEM_PAGES
    inside = ((slot[None, :] >= plan.item_lo[:, None])
              & (slot[None, :] < plan.item_hi[:, None])
              & (slot[None, :] // P >= plan.item_blk[:, None])
              & (slot[None, :] // P < plan.item_blk[:, None] + C)
              & real[:, None])
    valid = jnp.zeros((pairs, nb * P), jnp.int32).at[plan.item_pair].add(
        inside.astype(jnp.int32)) > 0
    g = jnp.minimum(plan.table, k_pool.shape[1] - 1)
    view = lambda pool: pool[layer, g].transpose(0, 2, 1, 3, 4).reshape(  # noqa: E731
        pairs, nb * P, hd)
    qg = q.reshape(pairs, H // KV, hd)
    s = jnp.einsum("pgh,pth->pgt", qg, view(k_pool),
                   preferred_element_type=jnp.float32) / hd ** 0.5
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jnp.where(valid[:, None], jnp.exp(s - s.max(-1, keepdims=True)), 0)
    out = jnp.einsum("pgt,pth->pgh", w.astype(v_pool.dtype), view(v_pool),
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    return out.reshape(B, H, hd).astype(q.dtype)


def _kernel(layer_ref, off_ref, pair_ref, blk_ref, lo_ref, hi_ref, table_ref,
            q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *, scale: float,
            n_pairs: int, KV: int):
    """One tile of `(row, KV head)` pairs: walk the tile's items
    (`decode_attention._paged_decode_kernel`'s loop, with an item's own
    bounds and head)."""
    tile, _, Gp, _ = q_ref.shape
    slots, _, C, P, hd = kbuf.shape
    ahead = slots - 1
    num_pages, nb = k_hbm.shape[1], table_ref.shape[1]
    p0 = pl.program_id(0) * tile
    first = off_ref[p0]
    end = off_ref[jnp.minimum(p0 + tile, n_pairs)]
    layer = layer_ref[0]

    def pages(i, act):
        slot, pair, blk = i % slots, pair_ref[i], blk_ref[i]
        n = (hi_ref[i] - 1) // P - blk + 1
        for c in range(C):
            @pl.when(c < n)
            def _page():
                page = jnp.minimum(
                    table_ref[pair // KV, jnp.minimum(blk + c, nb - 1)],
                    num_pages - 1)
                for j, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    act(pltpu.make_async_copy(
                        pool.at[layer, page, pair % KV], buf.at[slot, 0, c],
                        sem.at[j, slot]))

    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)

    for d in range(ahead):
        @pl.when(first + d < end)
        def _first_fetch():
            pages(first + d, lambda copy: copy.start())

    def item(i, state):
        @pl.when(i + ahead < end)
        def _next_fetch():
            pages(i + ahead, lambda copy: copy.start())

        pair, blk, slot = pair_ref[i], blk_ref[i], i % slots
        r = pair - p0
        state = tuple(jnp.where(i == off_ref[pair], fresh, x) for fresh, x in
                      zip((NEG_INF, 0.0, 0.0), state))
        pages(i, lambda copy: copy.wait())
        pos = blk * P + jax.lax.broadcasted_iota(jnp.int32, (Gp, C * P), 1)
        state = _paged_item_fold(
            q_ref[r], kbuf[slot].reshape(1, C * P, hd),
            vbuf[slot].reshape(1, C * P, hd),
            (pos >= lo_ref[i]) & (pos < hi_ref[i]), state, scale)

        @pl.when(i + 1 == off_ref[pair + 1])
        def _finalize():
            _, l, acc = state
            o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        return state

    jax.lax.fori_loop(first, end, item, (
        jnp.full((1, Gp, 1), NEG_INF, jnp.float32),
        jnp.zeros((1, Gp, 1), jnp.float32),
        jnp.zeros((1, Gp, hd), jnp.float32)))


def sparse_paged_decode_attention(q, k_pool, v_pool, layer,
                                  plan: SparseDecodePlan,
                                  interpret: bool | None = None):
    """Single-token attention over the runs of `plan`, K and V pages read
    from the stacked pools `[L, N, KV, P, hd]` in place, a head of a page a
    copy. `q` [B, H, hd]. Pairs without items read zero. Returns [B, H,
    hd]."""
    B, H, hd = q.shape
    _, _, KV, P, _ = k_pool.shape
    C = _ITEM_PAGES
    G = H // KV
    pairs = B * KV
    sub = 32 // q.dtype.itemsize
    Gp = sub * pl.cdiv(G, sub)
    tile = max(1, min(pairs, _PAGED_TILE_BYTES // (Gp * hd * q.dtype.itemsize)))
    n_tiles = pl.cdiv(pairs, tile)
    qg = jnp.pad(q.reshape(pairs, 1, G, hd),
                 [(0, n_tiles * tile - pairs), (0, 0), (0, Gp - G), (0, 0)])
    rows_spec = pl.BlockSpec((tile, 1, Gp, hd), lambda t, *_: (t, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / (hd ** 0.5), n_pairs=pairs,
                          KV=KV),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(n_tiles,),
            in_specs=[rows_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows_spec,
            scratch_shapes=[
                pltpu.VMEM((3, 1, C, P, hd), k_pool.dtype),
                pltpu.VMEM((3, 1, C, P, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 3)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *plan, qg, k_pool, v_pool)
    return out[:pairs, :, :G, :].reshape(B, H, hd)
