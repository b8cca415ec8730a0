"""Page allocator + per-row block tables for the paged KV cache.

The paged layout replaces the per-row contiguous `[T_max]` cache slab with a
global pool of fixed-size pages (`core/model.py:init_paged_kv_cache`,
`[L, num_pages, KV, page_size, hd]`) plus one int32 block table `[rows,
blocks_per_row]` shared by every layer: logical cache slot `t` of row `r`
lives at page `table[r, t // page_size]`, offset `t % page_size`.  Rows that
finish early hand their pages back to a free list so the continuous-batching
scheduler (`sampler/paged/scheduler.py`) can prefill the next queued prompt
into the freed pool mid-loop instead of draining the batch to its slowest row.

Everything here is pure, static-shape, and jittable:

  * `PageState` is a pytree of three arrays — a free-list stack `free` (the
    first `top` entries are free page ids), the scalar stack pointer `top`,
    and the block `table` itself.
  * `alloc_row` / `release_row` are functional updates returning a new
    `PageState`; `n_blocks` may be a traced value, so the scheduler can run
    them inside jit without retracing per allocation size.
  * Unallocated / released table entries hold the sentinel `num_pages`:
    writes through the table use `mode="drop"` scatters, reads clamp to
    `num_pages - 1`, so a sentinel entry can never corrupt a live page.

Allocation policy is full-budget-at-admission: a row claims
`blocks_per_row(prompt_len + max_tokens, page_size)` pages up front and
releases them all on EOS.  That keeps the allocator out of the jitted decode
carry entirely (no per-step allocation) at the cost of not reclaiming the
unreached tail of short rows until they finish — see docs/PAGED_CACHE.md for
the trade.
"""

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np


class PageState(NamedTuple):
    """Free-list + block-table state.  `free[:top]` are free page ids (a
    stack: allocation pops from index `top - 1` downward); entries at or
    beyond `top` are dead storage.  `table[r, j]` is the physical page id of
    row `r`'s j-th logical block, or the sentinel `num_pages` when
    unallocated."""
    free: jnp.ndarray   # [num_pages] int32
    top: jnp.ndarray    # scalar int32 — number of free pages
    table: jnp.ndarray  # [rows, blocks_per_row] int32


def blocks_per_row(tokens: int, page_size: int) -> int:
    """Pages a row needs to hold `tokens` logical cache slots."""
    return -(-int(tokens) // int(page_size))


def full_table(rows: int, n_blocks: int) -> jnp.ndarray:
    """Dense identity table: row `r` owns pages `[r*n_blocks, (r+1)*n_blocks)`.

    Used by the monolithic (non-queued) paged path, where the pool is exactly
    `rows * n_blocks` pages and never recycles — this makes the paged cache a
    pure re-layout of the contiguous one, which is what the bit-parity test
    pins down."""
    return jnp.arange(rows * n_blocks, dtype=jnp.int32).reshape(rows, n_blocks)


def init_page_state(num_pages: int, rows: int, n_blocks: int) -> PageState:
    """All pages free, all table entries sentinel."""
    return PageState(
        free=jnp.arange(num_pages, dtype=jnp.int32),
        top=jnp.asarray(num_pages, jnp.int32),
        table=jnp.full((rows, n_blocks), num_pages, jnp.int32),
    )


def alloc_row(state: PageState, row, n_blocks) -> Tuple[PageState, jnp.ndarray]:
    """Pop `n_blocks` pages off the free stack into `table[row]`.

    Returns `(new_state, ok)`; on `ok == False` (free list too short) the
    state is returned unchanged — admission control in the scheduler gates on
    this flag.  `row` and `n_blocks` may be traced."""
    nb = state.table.shape[1]
    num_pages = state.free.shape[0]
    k = jnp.minimum(jnp.asarray(n_blocks, jnp.int32), nb)
    ok = k <= state.top
    idx = state.top - 1 - jnp.arange(nb, dtype=jnp.int32)
    take = jnp.arange(nb, dtype=jnp.int32) < k
    pages = jnp.where(take, state.free[jnp.clip(idx, 0, num_pages - 1)],
                      num_pages)
    new_row = jnp.where(ok, pages, state.table[row])
    return PageState(
        free=state.free,
        top=jnp.where(ok, state.top - k, state.top),
        table=state.table.at[row].set(new_row),
    ), ok


def release_row(state: PageState, row) -> Tuple[PageState, jnp.ndarray]:
    """Push `table[row]`'s live pages back onto the free stack and reset the
    row to sentinel.  Returns `(new_state, n_released)`.  Releasing an
    already-sentinel row is a no-op (returns 0), so the scheduler may release
    idempotently at every sync.

    Semantics under refcounting: a release decrements the row's hold AT MOST
    ONCE — the sentinel reset is what makes the second release of the same
    row a no-op rather than a double-free that would push the same page onto
    the free stack twice.  The host-side refcounted pool
    (`serving.radix.RefPagePool`) mirrors this contract at the row level:
    `RadixCache.release` skips sentinel entries, so releasing a row's table
    twice frees its refs exactly once, while a raw `RefPagePool.unref` past
    zero is a hard error (the invariant tests in tests/test_serving.py pin
    both)."""
    nb = state.table.shape[1]
    num_pages = state.free.shape[0]
    pages = state.table[row]
    valid = pages < num_pages
    m = jnp.sum(valid.astype(jnp.int32))
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    dest = jnp.where(valid, state.top + rank, num_pages)  # num_pages → drop
    return PageState(
        free=state.free.at[dest].set(pages, mode="drop"),
        top=state.top + m,
        table=state.table.at[row].set(
            jnp.full((nb,), num_pages, jnp.int32)),
    ), m


# --------------------------------------------------------------------------- #
# the window layers' pages (docs/SWA.md "a page pool of two kinds")
# --------------------------------------------------------------------------- #

def ring_blocks(window: int, page_size: int, longest_write: int) -> int:
    """Pages a row's RING needs so that no forward ever writes a slot one of
    its own queries (or any later one) still sees: a forward of
    `longest_write` tokens from slot f writes up to f + longest_write - 1
    while its first query sees from f - window + 1; block `lb` lives in ring
    entry `lb % n`, so the block being written must not be the home of a
    block that far back. `window + longest_write` slots, one page for each
    end that starts or stops inside a page."""
    return blocks_per_row(int(window) + int(longest_write), page_size) + 2


class RingPages:
    """Host-side pool and table of the WINDOW layers' pages. A row claims a
    ring of at most `ring` pages at admission and keeps it while it lives;
    its logical block `lb` (from its first real block on) lives in page
    `ring_pages[(lb - first) % n]`: a page behind the window is written
    again `n` blocks later, so a window layer keeps a window's pages and
    not a row's budget. `table` [rows, nb] int32 is that ring laid out over
    the row's logical blocks (sentinel `num_pages` before the first, past
    the last and where nothing is claimed), which is all the device ever
    sees: every read and write addresses it like the global layers' table.
    Past the row's last block the entries are the sentinel so that an
    admission forward's trailing pad tokens (a power-of-two bucket of the
    suffix) are dropped as the global table drops what lies past a row's
    budget, and do not wrap onto the row's own first pages. Allocation
    is a free list on the host (the radix tree has no part in it: it holds
    no window state, so such a model takes no prefix hit)."""

    def __init__(self, num_pages: int, rows: int, n_blocks: int, ring: int):
        self.num_pages, self.nb, self.ring = int(num_pages), int(n_blocks), int(ring)
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._held: list = [()] * int(rows)
        self._first = np.zeros((rows,), np.int64)
        self.table = np.full((rows, self.nb), self.num_pages, np.int32)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def claim(self, row: int, first_block: int, last_block: int) -> int:
        """A ring for blocks `[first_block, last_block]` of `row`:
        min(ring, that many) pages off the free list. Raises RuntimeError
        when the pool is short, before anything changed. Returns the ring's
        size."""
        self.release(row)
        n = min(self.ring, int(last_block) - int(first_block) + 1)
        if n > len(self._free):
            raise RuntimeError(
                f"window page pool exhausted: {n} wanted, "
                f"{len(self._free)} free")
        pages = np.asarray([self._free.pop() for _ in range(n)], np.int32)
        self._held[row], self._first[row] = tuple(int(p) for p in pages), first_block
        blocks = np.arange(first_block, int(last_block) + 1)
        self.table[row] = self.num_pages
        self.table[row, first_block:int(last_block) + 1] = \
            pages[(blocks - first_block) % n]
        return n

    def release(self, row: int) -> int:
        held, self._held[row] = self._held[row], ()
        self._free.extend(p for p in held if p < self.num_pages)
        self.table[row] = self.num_pages
        return len(held)

    def reused(self, row: int, last_block: int) -> int:
        """Times a page of `row`'s ring was written again behind the window,
        once the row has written up to `last_block`."""
        n = len(self._held[row])
        return max(0, int(last_block) - int(self._first[row]) + 1 - n) if n else 0
