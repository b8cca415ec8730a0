"""Pallas flash attention of T > 1 queries over a PAGED cache, read in place.

A chunked admission's prefill piece (and every suffix forward) of a pattern
model writes its T new tokens into the page pool and then attends over the
row's pages: T queries at slots `[fill, fill + T)` against the keys at
`[first, fill + T)`, causal among themselves, a window layer's no further
back than its window. `core/model.py::_attend_paged_blocks` is that read in
XLA: a `fori_loop` over key blocks that gathers each block's pages out of
the stacked pool and carries float32 scores through HBM between the online
softmax's fusions (11 % of the bf16 peak inside the piece program at
SmallThinker's widths, PERF.md PR 37). This kernel is the same read on the chip:

- **In place.** The pool leaves `[L, num_pages, KV, P, hd]` are operands
  WHOLE, the layer a scalar (as `decode_attention.paged_decode_attention`
  takes them: a layer's slab handed to a custom call would be copied out of
  the stack). A key ITEM is `pages_per_item` consecutive logical blocks,
  each its own operand of the call (the same buffer handed over several
  times: a BlockSpec fetches one page), so a grid step moves and multiplies
  more than one 32 KB page. What each block of queries reads (`_plan`: its
  first block, its items, the physical page of every step, the sentinel
  clamped as `_paged_kv_index_map` clamps it) is made once in XLA and rides
  in scalar prefetch with the layer and the bounds; the sixteen index maps
  only look a page up (with the arithmetic inside them a serving engine's
  twelve admission programs took ~15 s longer to trace and lower).
- **The mask from iota.** Query i sits at slot `fill + i` and sees key j iff
  `first <= j <= fill + i` and, in a window layer, `j > fill + i - window`:
  what `core/model._kind_views` hands the walk for a row whose valid keys are contiguous
  from `first` (every serving row). Items wholly outside a query block's
  `[lowest visible key, its last query]` are SKIPPED: a block's items start
  at the page of its lowest visible key, and the grid steps past its last
  item map to that item again (no DMA) and run nothing. A window layer's
  work therefore does not grow with the prompt, and a global layer's is the
  causal triangle, not the table. Items every query of the block sees whole
  skip the mask too.
- **GQA folded into M.** The G query heads of a KV head and a block of `bq`
  queries are one [G * bq, hd] operand of the score matmul.
- **The walk's arithmetic.** Operands in the cache's dtype, float32 scores,
  max, sum and accumulator in VMEM scratch across the items, probabilities
  cast to the value dtype before PV.

Off the TPU, under `"xla"` and under a mesh the walk stays: the plain form,
and this kernel's oracle (tests/test_paged_prefill_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.attention import NEG_INF, _interpret_default

# keys one item holds (8 pages of 128) and queries one block holds: the
# scores of a grid step are [G * _BLOCK_Q, _ITEM_KEYS] float32 in VMEM, 14.7
# MB at G = 7. Chosen by a sweep of the kernel alone on a v5e (PERF.md PR 37,
# ms a window layer's piece past the window / a global layer's at slot 12 k):
# 256 x 512 1.00 / 2.73, 512 x 512 0.85 / 2.31, 256 x 1024 0.68 / 1.72,
# **512 x 1024 0.62 / 1.54**, 512 x 1280 0.70 / 1.73, 1024 x 1024 1.07 / 2.67
# (spills). What a step pays whatever its keys (the accumulator's rescale,
# the running max and sum: all of M rows) is paid once for more keys; past
# this size the scores leave the registers' reach.
_ITEM_KEYS = 1024
_BLOCK_Q = 512
_VMEM_LIMIT = 64 << 20


def _plan(table, first, fill, *, bq, n_q, n_k, T, window, P, C, N):
    """What each block of `bq` queries of each row reads, made once in XLA
    (the index maps and the kernel then only look it up): `lo_blk [B * n_q]`,
    the logical block of the lowest key the block's first query sees;
    `n [B * n_q]`, its items of C blocks from there up to the block of its
    last real query, 0 for a block with nothing to see (its row's `first`
    past its last query); `pages [B * n_q, n_k * C]`, the physical page of
    every block of every grid step, the sentinel clamped as
    `decode_attention._paged_kv_index_map` clamps it. Steps past a block's
    last item stay on that item (the same pages again: no new fetch), and a
    block past the table reads its last page, masked by its logical
    position."""
    B, nb = table.shape
    q0 = fill[:, None] + jnp.arange(n_q, dtype=jnp.int32)[None, :] * bq
    lo = jnp.maximum(first[:, None], q0 - window + 1) if window else \
        jnp.broadcast_to(first[:, None], q0.shape)
    hi = jnp.minimum(q0 + bq, fill[:, None] + T) - 1
    lo_blk = jnp.clip(lo // P, 0, nb - 1)
    blocks = jnp.clip(hi // P, 0, nb - 1) - lo_blk + 1
    n = jnp.where(lo > hi, 0, (blocks + C - 1) // C)                # [B, n_q]
    item = jnp.minimum(jnp.arange(n_k, dtype=jnp.int32),
                       jnp.maximum(n - 1, 0)[..., None])            # [B, n_q, n_k]
    blk = (lo_blk[..., None, None] + item[..., None] * C
           + jnp.arange(C, dtype=jnp.int32))                        # [.., n_k, C]
    pages = jnp.take_along_axis(
        table[:, None, :], jnp.minimum(blk, nb - 1).reshape(B, n_q, n_k * C),
        axis=2)
    return (lo_blk.reshape(B * n_q), n.reshape(B * n_q),
            jnp.minimum(pages, N - 1).reshape(B * n_q, n_k * C))


def _kernel(layer_ref, lo_ref, n_ref, pages_ref, first_ref, fill_ref, q_ref,
            *refs, scale: float, window: int, C: int):
    del layer_ref, pages_ref
    k_refs, v_refs = refs[:C], refs[C:2 * C]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * C:]
    b, iq, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    G, bq, hd = q_ref.shape
    P = k_refs[0].shape[0]
    keys = C * P
    first, fill = first_ref[b], fill_ref[b]
    at = b * pl.num_programs(2) + iq
    n = n_ref[at]
    q0, k0 = fill + iq * bq, (lo_ref[at] + j * C) * P

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(masked: bool):
        q = q_ref[...].reshape(G * bq, hd)
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [G * bq, keys]
        if masked:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, keys), 0)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, keys), 1)
            see = (kpos >= first) & (kpos <= qpos)
            if window:
                see = see & (kpos > qpos - window)
            s = jnp.where(see[None], s.reshape(G, bq, keys),
                          NEG_INF).reshape(G * bq, keys)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # probabilities enter PV in the cache's dtype, as in the walk
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # every query of the block sees every key of the item: no mask to build
    whole = (k0 >= first) & (k0 + keys - 1 <= q0)
    if window:
        whole = whole & (k0 > q0 + bq - 1 - window)

    @pl.when((j < n) & whole)
    def _whole():
        attend(False)

    @pl.when((j < n) & jnp.logical_not(whole))
    def _edge():
        attend(True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).reshape(G, bq, hd).astype(o_ref.dtype)


# (jitted: the layers of one kind in a program trace and lower ONE kernel)
@functools.partial(jax.jit, static_argnames=("window", "block_q",
                                             "pages_per_item", "interpret"))
def paged_prefill_attention(
    q: jnp.ndarray,        # [B, H, T, hd]: query i of a row sits at slot fill + i
    k_pool: jnp.ndarray,   # [L, N, KV, P, hd], the WHOLE stacked page pool
    v_pool: jnp.ndarray,   # [L, N, KV, P, hd] (the T new tokens already written)
    layer,                 # scalar int32: which layer of the stack
    table: jnp.ndarray,    # [B, nb] int32 (sentinel N = no page)
    first: jnp.ndarray,    # [B] int32: the lowest slot the row's FIRST query sees
    fill: jnp.ndarray,     # [B] int32: the first query's own slot
    window: int = 0,       # static: a window layer's window; 0 for a global layer
    *,
    block_q: int | None = None,
    pages_per_item: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Flash attention of T queries over a row's pages, read from the stacked
    pool in place (module docstring). Query i sees key slot j iff
    `first <= j <= fill + i` and, with a `window`, `j > fill + i - window`
    (`first`, `fill` as the kind's `core/model.KindView.verify`
    gives them for the layer's kind). `block_q` and `pages_per_item` default
    to the chip's (`_BLOCK_Q` queries, `_ITEM_KEYS` keys); tests shrink them.
    Returns [B, H, T, hd]."""
    B, H, T, hd = q.shape
    _, N, KV, P, _ = k_pool.shape
    nb = table.shape[1]
    G = H // KV
    C = max(1, min(pages_per_item or _ITEM_KEYS // P, nb))
    # a block of queries is whole sublane tiles of the query dtype, so that
    # [G, bq, hd] is [G * bq, hd] without a relayout
    sub = 32 // q.dtype.itemsize
    n_q = pl.cdiv(T, block_q or _BLOCK_Q)
    bq = sub * pl.cdiv(pl.cdiv(T, n_q), sub)
    n_q = pl.cdiv(T, bq)
    # pages a block's `[first query - window, last query]` can touch
    span = min(nb, pl.cdiv(window + bq - 2, P) + 1) if window else nb
    n_k = pl.cdiv(span, C)

    qg = q.reshape(B, KV, G, T, hd)
    if n_q * bq != T:       # pad queries see what the last real one may
        qg = jnp.pad(qg, [(0, 0)] * 3 + [(0, n_q * bq - T), (0, 0)])
    first, fill = first.astype(jnp.int32), fill.astype(jnp.int32)
    plan = _plan(table.astype(jnp.int32), first, fill, bq=bq, n_q=n_q,
                 n_k=n_k, T=T, window=window, P=P, C=C, N=N)

    def page_map(c):
        def index_map(b, kv, iq, j, layer_ref, lo_ref, n_ref, pages_ref, *_):
            return (layer_ref[0], pages_ref[b * n_q + iq, j * C + c], kv, 0, 0)
        return index_map

    rows = pl.BlockSpec((None, None, G, bq, hd),
                        lambda b, kv, iq, j, *_: (b, kv, 0, iq, 0))
    pages = [pl.BlockSpec((None, None, None, P, hd), page_map(c))
             for c in range(C)]
    kernel = functools.partial(_kernel, scale=1.0 / (hd ** 0.5),
                               window=int(window), C=C)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, KV, n_q, n_k),
            in_specs=[rows] + pages + pages,
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((G * bq, hd), jnp.float32),
                pltpu.VMEM((G * bq, 128), jnp.float32),
                pltpu.VMEM((G * bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default() if interpret is None else interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *plan, first, fill, qg,
      *([k_pool] * C), *([v_pool] * C))
    return out[:, :, :, :T].reshape(B, H, T, hd)
