"""The decode step's paged K/V write: the live rows, in place, in one call.

A decode step writes ONE new slot a row into the page pool `[L, num_pages,
KV, P, hd]`. As XLA's scatter that is `B x KV` updates of one `[hd]` row a
leaf a layer, which the TPU's compiler runs one after another over a `[rows,
hd]` view of the whole leaf at ~100 ns each, released and empty rows
included (`core/model._paged_row_scatter`; 0.72 ms of the 6.04 ms step of
`serve-1.5b-chat` for 0.9 MB of new K and V, PERF.md PR 38 / PR 41). This
kernel is the same write on the chip:

- **In place.** The K and V leaves are operands WHOLE, in HBM
  (`memory_space=ANY`), aliased to the call's two outputs; the layer and
  every row's `(page, offset)` ride in scalar prefetch. Nothing but the
  touched tiles moves.
- **The live rows only.** The step's plan (`paged_write_plan`, made once in
  XLA for every layer, as `decode_attention.paged_decode_plan` is) lists the
  rows that write, first: a row whose page is the table's sentinel
  (released, empty, past its budget: what `mode="drop"` drops) or that the
  caller marks done costs the kernel nothing. A done row's write would land
  in a slot of its own that nothing reads; that slot is the one place where
  the pool may differ from the scatter's.
- **A tile group a row.** A slot is one sublane row of a packed tile
  (`sub` = 16 rows of bf16, 8 of float32), which no DMA can address alone:
  the row's aligned `[KV, sub, hd]` group is read into VMEM, the slot is
  patched there and the group goes back. All reads of a batch of rows start
  before the first wait, and so do the writes.

Rows that hold a page hold it alone (docs/PAGED_CACHE.md "The write"), so no
two groups of a call overlap. Every slot a live row owns comes out
bit-identical to the row scatter's (the whole pool, where no row is done),
which stays the plain form (`"xla"`, off the TPU, under a mesh,
the int8 pool, a latent pool) and this kernel's oracle
(tests/test_paged_cache_write.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.attention import _interpret_default

# rows whose tile groups are in VMEM at once: two buffers of
# rows x KV x sub x hd (64 x 2 x 16 x 128 bf16 = 0.5 MB each in the chat cell)
_BATCH_BYTES = 2 << 20


def sublanes(dtype) -> int:
    """Rows of one tile of `dtype`: what a slot shares a DMA's unit with."""
    return 32 // jnp.dtype(dtype).itemsize


class PagedWritePlan(NamedTuple):
    """A decode step's work list for `paged_row_write`, the same for every
    layer: the rows that write, first, and where each row's slot lies."""
    n: jnp.ndarray       # [1] int32: rows that write
    row: jnp.ndarray     # [B] int32: those rows, in order, first
    page: jnp.ndarray    # [B] int32, by row: the slot's page
    off: jnp.ndarray     # [B] int32, by row: the slot's offset in its page


def rows_first(has):
    """`(n [1] int32, row [B] int32)`: how many of `has` [B] bool are set,
    and those rows' indices, in order, first (what a kernel's work list
    rides in scalar prefetch as)."""
    B = has.shape[0]
    ends = jnp.cumsum(has, dtype=jnp.int32)     # writers among rows 0..b
    i = jnp.arange(B, dtype=jnp.int32)
    # the i-th writer is the first row with more than i writers up to it
    row = jnp.minimum(
        jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1)
    return ends[-1:], row


def paged_write_plan(table, cache_index, *, page_size: int, num_pages: int,
                     live=None) -> PagedWritePlan:
    """The plan of one decode step: row b writes slot `cache_index[b]` (a
    scalar: every row's) unless the slot's block is past the table or holds
    the sentinel `num_pages` (released, empty, past its budget: what the
    scatter's `mode="drop"` drops) or the caller marks the row not `live`
    (a done row rewrites a slot of its own that nothing reads). table:
    [B, nb] int32; live: [B] bool or None."""
    B, nb = table.shape
    slot = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
    lb = slot // page_size
    page = jnp.where(
        lb < nb, jnp.take_along_axis(
            table, jnp.clip(lb, 0, nb - 1)[:, None], axis=1)[:, 0], num_pages)
    has = page < num_pages
    if live is not None:
        has = has & live
    return PagedWritePlan(*rows_first(has), page.astype(jnp.int32),
                          slot % page_size)


def _write_kernel(layer_ref, n_ref, row_ref, page_ref, off_ref, k_new, v_new,
                  k_hbm, v_hbm, k_out, v_out, kbuf, vbuf, sem, *, batch: int):
    sub = kbuf.shape[2]
    layer, n = layer_ref[0], n_ref[0]
    lanes = ((k_hbm, k_out, k_new, kbuf), (v_hbm, v_out, v_new, vbuf))

    def group(pool, b):
        start = pl.multiple_of((off_ref[b] // sub) * sub, sub)
        return pool.at[layer, page_ref[b], :, pl.ds(start, sub), :]

    def copies(b, i, k, act):
        """The batch's k-th round of copies for row b: 0 reads its groups
        into slot i, 1 writes them back. A round shares a semaphore a leaf,
        so a round is waited for WHOLE before what it moved is used."""
        for j, (src, dst, _, buf) in enumerate(lanes):
            ends = (group(src, b), buf.at[i]) if k == 0 else (
                buf.at[i], group(dst, b))
            act(pltpu.make_async_copy(*ends, sem.at[k, j]))

    def patch(b, i):
        mine = jax.lax.broadcasted_iota(
            jnp.int32, kbuf.shape[1:], 1) == off_ref[b] % sub
        for _, _, new, buf in lanes:
            buf[i] = jnp.where(mine, new[b][:, None, :], buf[i])
        copies(b, i, 1, lambda c: c.start())

    def one_batch(first, carry):
        def rows(fn):
            def row(i, c):
                fn(row_ref[first + i], i)
                return c
            jax.lax.fori_loop(0, jnp.minimum(batch, n - first), row, None)

        rows(lambda b, i: copies(b, i, 0, lambda c: c.start()))
        rows(lambda b, i: copies(b, i, 0, lambda c: c.wait()))
        rows(patch)
        rows(lambda b, i: copies(b, i, 1, lambda c: c.wait()))
        return carry

    jax.lax.fori_loop(
        0, pl.cdiv(n, batch), lambda t, c: one_batch(t * batch, c), None)


def paged_row_write(
    k_pool: jnp.ndarray,   # [L, N, KV, P, hd], the WHOLE stacked page pool
    v_pool: jnp.ndarray,   # [L, N, KV, P, hd]
    k_new: jnp.ndarray,    # [B, KV, hd], the step's new key of every row
    v_new: jnp.ndarray,    # [B, KV, hd]
    layer,                 # scalar int32: which layer of the stack
    plan: PagedWritePlan,
    interpret: bool | None = None,
):
    """`(k_pool, v_pool)` with `k_new[b]`, `v_new[b]` at `[layer, page[b], :,
    off[b], :]` for the plan's rows, written where the pools lie. Pages of
    distinct rows are distinct."""
    B, KV, hd = k_new.shape
    sub = sublanes(k_pool.dtype)
    batch = max(1, min(B, _BATCH_BYTES
                       // (2 * KV * sub * hd * k_pool.dtype.itemsize)))
    new_spec = pl.BlockSpec((B, KV, hd), lambda i, *_: (0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(1,),
        in_specs=[new_spec, new_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
        scratch_shapes=[
            pltpu.VMEM((batch, KV, sub, hd), k_pool.dtype),
            pltpu.VMEM((batch, KV, sub, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_write_kernel, batch=batch),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands count from the scalar-prefetch ones: the pools are 7, 8
        input_output_aliases={7: 0, 8: 1},
        interpret=_interpret_default() if interpret is None else interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *plan, k_new, v_new,
      k_pool, v_pool)
