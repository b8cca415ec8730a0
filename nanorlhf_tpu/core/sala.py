"""MiniCPM-SALA's two mixers (docs/SALA.md), which `core/model.py`'s layer
body calls for the layer kinds `"lightning"` and `"sparse"`.

`lightning_operator` is Lightning Attention-2 as a recurrence: a head keeps
a MATRIX state `S [hd, hd]` (float32) and no pages,

    S_t = lam S_(t-1) + k_t (outer) v_t        o_t = q_t S_t / sqrt(hd)

which is ops/ssm.py's selective recurrence with `d_t = 1`, a constant
`A = log lam`, `B = k`, `C = q`, `x = v` and one group a head: a piece goes
through `ssd_scan`, a cached decode step through `ssm_update_in_place`.

The sparse layer (InfLLM-V2) is `core/model._attention` with three things
of its own, all here: `compress_write` keeps the layer's COMPRESSED keys
(means over `sparse_kernel_size` keys every `sparse_kernel_stride`) beside
its K and V as they fill; `select_blocks` scores them for a query, sums a
group's heads, pools to blocks and takes the top `sparse_topk` with the
forced ones; `sparse_read` attends over the chosen blocks only: a decode
step over pages on a TPU through ops/sparse_attention.py's kernel, which
READS the chosen blocks' pages and no others; every other call (a prefill
piece, the contiguous cache, no cache, off the TPU) through `_attend_chosen`,
a walk of key blocks under the selection's mask that skips a key block no
query of the call chose. A row whose call holds fewer than
`sparse_dense_len` keys is read dense, by the same code under a mask that
chooses everything, or by `core/model._attention_read` where no row of the
call selects.

Everything a query needs of its row is in SLOT space: a served row is
left-padded, so position p of the row lies at slot `start + p`, blocks and
compressed keys are cut by POSITION, and neither is aligned to a page.
`view.span = (start, keys)`: each row's first slot and the keys of the call
the row's tokens belong to (a prompt's whole length for each of its pieces;
what the row holds for a decode step).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30
# the most float32 scores one block of queries may hold (selection: against
# every compressed key; the walk: against one block of keys)
_SCORE_BYTES = 256 << 20
# keys a block of `_attend_chosen`'s walk holds over arrays at hand
_KEY_BLOCK = 1024
# queries (`B KV Tq`) from which `select_blocks` takes its top k by
# `top_blocks`; below, by `lax.top_k` (tools/bench_block_pick.py)
_PICK_QUERIES = 16


# --------------------------------------------------------------------------- #
# the lightning layer
# --------------------------------------------------------------------------- #

def init_lightning(config, n: int, fan, dtype) -> dict:
    """The lightning mixers of `n` layers: `q_proj`, `k_proj`, `v_proj`,
    `z_proj` (the output gate's) `[n, D, H hd]`, `o_proj [n, H hd, D]`,
    `q_norm`, `k_norm` `[n, hd]` and `o_norm [n, H hd]`."""
    D = config.hidden_size
    W = config.lightning_heads * config.lightning_head_dim
    ones = lambda *shape: jnp.ones(shape, dtype)                # noqa: E731
    return {**{name: {"kernel": fan(n, D, W)}
               for name in ("q_proj", "k_proj", "v_proj", "z_proj")},
            "o_proj": {"kernel": fan(n, W, D)},
            "q_norm": ones(n, config.lightning_head_dim),
            "k_norm": ones(n, config.lightning_head_dim),
            "o_norm": ones(n, W)}


def _norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def _rotate(x, cos, sin):
    """Rotate-half RoPE on `x` [B, T, H, hd]; cos/sin [B, T, hd]."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x.astype(jnp.float32) * cos[:, :, None]
            + rotated.astype(jnp.float32) * sin[:, :, None]).astype(x.dtype)


def lightning_operator(config, x, h, lt, state_group, layer, view, cos, sin,
                       place):
    """A lightning layer's mixer on the normed state `h`, with its residual:
    `(x + r * branch, the updated state group | None)`.

    The layer's cache is `state_group = (S,)`, `[lightning layers, rows, H,
    hd(v), hd(k)]` FLOAT32. `view.table` names the rows and `view.conv_ctx =
    (valid, fresh)` means what it means for every state (docs/STATE.md): a
    token not `valid` neither decays nor feeds `S` (`d_t = 0`), so the state
    that leaves is the one after the row's last real token; a `fresh` row
    starts from zeros. `layer` is the layer's index among the lightning
    layers, its state's; `place` its place among ALL the model's layers (the
    model is one stack), which its decays go by
    (`config.lightning_log_decays`)."""
    from nanorlhf_tpu.ops import ssm as ops

    B, T, _ = h.shape
    H, hd = config.lightning_heads, config.lightning_head_dim
    f32 = jnp.float32
    state_rows, ctx = view.table, view.conv_ctx
    valid, fresh = ctx if ctx is not None else (None, None)
    with jax.named_scope("attn.linear"):
        with jax.named_scope("attn.linear.in"):
            q, k, v, z = (h @ lt[name]["kernel"] for name in (
                "q_proj", "k_proj", "v_proj", "z_proj"))
            heads = lambda a: a.reshape(B, T, H, hd)            # noqa: E731
            q = _rotate(_norm(heads(q), lt["q_norm"], config.rms_norm_eps),
                        cos, sin)
            k = _rotate(_norm(heads(k), lt["k_norm"], config.rms_norm_eps),
                        cos, sin)
            v = heads(v)
            dt = jnp.ones((B, T, H), f32)
            if valid is not None:
                dt = jnp.where(valid[..., None], dt, 0)
            A = jnp.asarray(config.lightning_log_decays(every_layer=True))[place]
            row = 0
            if state_group is not None:
                (s_stack,) = state_group
                row = 0 if state_rows is None else state_rows[0, 0]
            if state_group is None:
                before = jnp.zeros((B, H, hd, hd), f32)
            elif T > 1:     # (a cached step passes over `S` where it lies)
                before = jax.lax.dynamic_slice(
                    s_stack, (layer, row, 0, 0, 0), (1, B, H, hd, hd))[0]
                if fresh is not None:
                    before = jnp.where(fresh[:, None, None, None], 0, before)
        if T == 1:
            with jax.named_scope("attn.linear.update"):
                step = (v[:, 0], dt[:, 0], A, k[:, 0], q[:, 0])
                if state_group is None:
                    y, _ = ops.ssm_update(*step, before)
                else:
                    y, s_stack = ops.ssm_update_in_place(
                        s_stack, layer, row,
                        None if valid is None else valid[:, 0], fresh, *step)
                y = y[:, None]
        else:
            with jax.named_scope("attn.linear.scan"):
                y, after = ops.ssd_scan(v, dt, A, k, q, before,
                                        config.ssm_chunk)
        with jax.named_scope("attn.linear.gate"):
            y = y * (1.0 / hd ** 0.5)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + config.rms_norm_eps)
            y = y.reshape(B, T, H * hd) * lt["o_norm"].astype(f32)
            y = (y * jax.nn.sigmoid(z.astype(f32))).astype(h.dtype)
        new_group = None
        if state_group is not None:
            with jax.named_scope("attn.write"):
                new_group = (s_stack if T == 1 else
                             jax.lax.dynamic_update_slice(
                                 s_stack, after[None].astype(s_stack.dtype),
                                 (layer, row, 0, 0, 0)),)
        with jax.named_scope("attn.linear.out"):
            out = y @ lt["o_proj"]["kernel"]
            return x + out * jnp.asarray(config.residual_scale, out.dtype), \
                new_group


# --------------------------------------------------------------------------- #
# the sparse layer: compressed keys
# --------------------------------------------------------------------------- #

def _halves_to_keys(config, window, dtype):
    """`window` [B, KV, n * stride, hd]: consecutive keys from a compressed
    key's first on. The n - 1 compressed keys that start every `stride` of
    them: the mean of `kernel_size` = 2 strides of keys, summed in float32."""
    stride = config.sparse_kernel_stride
    B, KV, W, hd = window.shape
    half = window.astype(jnp.float32).reshape(
        B, KV, W // stride, stride, hd).sum(axis=3)
    return ((half[:, :, :-1] + half[:, :, 1:])
            * (1.0 / config.sparse_kernel_size)).astype(dtype)


def _rows_slice(a, at, size: int, axis: int):
    """`a[b]` from `at[b]` on, `size` long along `axis` (counted without the
    batch axis), a row at a time."""
    return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
        row, s, size, axis=axis))(a, at)


def compress_at_hand(config, k, start):
    """The compressed keys of keys at hand, `k` [B, KV, T, hd] in slot order
    with position 0 at slot `start` [B]: `[B, KV, ceil(T / stride), hd]` in
    POSITION order (key j the mean of positions `[stride j, stride j +
    kernel_size)`); one that hangs past the real tokens holds what lies
    there, and no real query may read it (`select_blocks`)."""
    stride = config.sparse_kernel_stride
    T = k.shape[2]
    n = -(-T // stride) + 1
    padded = jnp.pad(k, ((0, 0), (0, 0), (0, n * stride), (0, 0)))
    return _halves_to_keys(
        config, _rows_slice(padded, start, n * stride, 1), k.dtype)


def compress_write(config, kc_stack, k_stack, layer, view, T: int):
    """Write the compressed keys that this call's T tokens complete into
    `kc_stack`, from `k_stack`, the layer's K cache AFTER the call's write.

    A compressed key j of a row covers slots `[start + stride j, + kernel
    size)` and is stored at compressed slot `c = start // stride + j`: in a
    paged cache `[L, pages, KV * P / stride, hd]` (a page's heads and
    entries on ONE axis, head-major: at pages of 128 and 2 KV heads that is
    16 rows of 128 lanes, one bfloat16 tile; with the heads on an axis of
    their own the chip's compiler relaid the whole leaf at both ends of
    every decode chunk, compiled for a described v5e, PR 53) at entry `c %
    (P / stride)` of the page of logical block `c // (P / stride)`, which is
    the page of the key's FIRST slot (the row owns it); in a contiguous one
    `[L, B, KV, T_max / stride, hd]` at `c`. This call completes the keys whose last
    slot lies among its tokens, up to the row's last real one
    (`view.conv_ctx`'s `valid`, `view.live`): at most `T // stride + 1`,
    consecutive, so their keys are one window of the cache a row."""
    stride, ksize = config.sparse_kernel_stride, config.sparse_kernel_size
    start, _ = view.span
    B = start.shape[0]
    fill = jnp.broadcast_to(jnp.asarray(view.index, jnp.int32), (B,))
    valid = None if view.conv_ctx is None else view.conv_ctx[0]
    if valid is None:
        last = fill + (T - 1)
    else:       # the row's last real token of the call; none: nothing ends
        last = jnp.where(valid.any(axis=1),
                         fill + T - 1 - jnp.argmax(valid[:, ::-1], axis=1), -1)
    if view.live is not None:
        last = jnp.where(view.live, last, -1)
    J = T // stride + 1
    first = jnp.maximum(fill, start)
    j_lo = jnp.maximum(-(-(first - start - (ksize - 1)) // stride), 0)
    base = start + stride * j_lo                    # [B] the window's slot
    W = stride * (J + 1)
    if view.table is not None:
        from nanorlhf_tpu.core.model import _touched_blocks

        P = view.page_size
        n = _touched_blocks(W, P)
        num_pages, nb = k_stack.shape[1], view.table.shape[1]
        lb = (base // P)[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
        page = jnp.minimum(jnp.take_along_axis(
            view.table, jnp.clip(lb, 0, nb - 1), axis=1), num_pages - 1)
        got = k_stack[layer, page]                  # [B, n, KV, P, hd]
        KV, hd = got.shape[2], got.shape[4]
        got = got.transpose(0, 2, 1, 3, 4).reshape(B, KV, n * P, hd)
        window = _rows_slice(got, base % P, W, 1)
    else:
        slab = jax.lax.dynamic_index_in_dim(k_stack, layer, 0, keepdims=False)
        at = base[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
        window = jnp.take_along_axis(
            slab, jnp.clip(at, 0, slab.shape[2] - 1)[:, None, :, None], axis=2)
    new = _halves_to_keys(config, window, kc_stack.dtype)   # [B, KV, J, hd]
    j = j_lo[:, None] + jnp.arange(J, dtype=jnp.int32)[None]        # [B, J]
    done = (start[:, None] + stride * j + (ksize - 1)) <= last[:, None]
    c = (start // stride)[:, None] + j
    new = new.transpose(0, 2, 1, 3)                          # [B, J, KV, hd]
    if view.table is not None:
        per_page = P // stride
        lb = c // per_page
        page = jnp.where(
            done & (lb < nb),
            jnp.take_along_axis(view.table, jnp.clip(lb, 0, nb - 1), axis=1),
            num_pages)
        KV = new.shape[2]
        entry = (per_page * jnp.arange(KV, dtype=jnp.int32)[None, None, :]
                 + (c % per_page)[:, :, None])                  # [B, J, KV]
        return kc_stack.at[layer, page[:, :, None], entry, :].set(
            new, mode="drop")
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    c = jnp.where(done, c, kc_stack.shape[3])
    return kc_stack.at[layer, rows, :, c, :].set(new, mode="drop")


def compressed_keys(config, kc_stack, layer, view):
    """A call's rows' compressed keys out of the cache, `[B, KV, Nc, hd]` in
    POSITION order (`compress_write`'s compressed slot `c` is key `c - start
    // stride`)."""
    start, _ = view.span
    if view.table is not None:
        got = kc_stack[layer, jnp.minimum(view.table, kc_stack.shape[1] - 1)]
        B, nb, rows, hd = got.shape
        per = view.page_size // config.sparse_kernel_stride
        got = got.reshape(B, nb, rows // per, per, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, rows // per, nb * per, hd)
    else:
        got = jax.lax.dynamic_index_in_dim(kc_stack, layer, 0, keepdims=False)
    Nc = got.shape[2]
    padded = jnp.pad(got, ((0, 0), (0, 0), (0, Nc), (0, 0)))
    return _rows_slice(padded, start // config.sparse_kernel_stride, Nc, 1)


# --------------------------------------------------------------------------- #
# the sparse layer: selection
# --------------------------------------------------------------------------- #

def _blocks_of(config, compressed: int) -> int:
    """Blocks that `compressed` compressed keys span."""
    return -(-compressed * config.sparse_kernel_stride
             // config.sparse_block_size)


def select_blocks(config, q, kc, t):
    """The blocks each query reads: `(idx, ok)`, both `[B, KV, Tq, k]`, the
    position-space block ids a KV head's group of query heads shares and
    which of them count (`k = min(sparse_topk, blocks)`; a query with fewer
    candidate blocks than k has the rest not `ok`).

    `q` [B, H, Tq, hd]; `kc` [B, KV, Nc, hd] the row's compressed keys in
    position order; `t` [B, Tq] the queries' positions (negative: a pad,
    which chooses nothing). Compressed key j counts for a query once its
    window has ended, `stride j + kernel_size - 1 <= t`. A head's scores are
    softmaxed over those, summed over the group's heads, max-pooled to
    blocks (block b takes keys `[per b - 1, per b + per - 1]`, `per` =
    block / stride: pool `per + 1`, stride `per`, pad 1), and the top k are
    taken with the first `sparse_init_blocks` and the `sparse_window_size /
    sparse_block_size` blocks that end at the query's own forced in. Equal
    scores: the lower block first. The top k are `lax.top_k`'s, ties and
    order: by `top_blocks` where the call ranks `_PICK_QUERIES` queries (`B
    KV Tq`) or more, by `lax.top_k` itself (on the chip a full sort of the
    blocks) below, where that sort is the cheaper; the same bits either way."""
    stride, ksize = config.sparse_kernel_stride, config.sparse_kernel_size
    block = config.sparse_block_size
    per, local = block // stride, config.sparse_window_size // block
    B, H, Tq, hd = q.shape
    KV, Nc = kc.shape[1], kc.shape[2]
    NB = _blocks_of(config, Nc)
    f32 = jnp.float32
    s = jnp.einsum("bkgqh,bkch->bkgqc", q.reshape(B, KV, H // KV, Tq, hd), kc,
                   preferred_element_type=f32) * (1.0 / hd ** 0.5)
    ended = (stride * jnp.arange(Nc, dtype=jnp.int32) + (ksize - 1)
             )[None, None, :] <= t[:, :, None]                  # [B, Tq, Nc]
    p = jax.nn.softmax(jnp.where(ended[:, None, None], s, NEG_INF), axis=-1)
    mass = jnp.where(ended[:, None], jnp.sum(p, axis=2), 0)     # [B,KV,Tq,Nc]
    rows = jnp.pad(mass, ((0, 0),) * 3 + ((1, per * (NB + 1) - Nc - 1),)
                   ).reshape(B, KV, Tq, NB + 1, per)
    score = jnp.maximum(jnp.max(rows[..., :-1, :], axis=-1),
                        rows[..., 1:, 0])                       # [B,KV,Tq,NB]
    b = jnp.arange(NB, dtype=jnp.int32)[None, None, :]
    own = (t // block)[:, :, None]                              # [B, Tq, 1]
    seen = (b <= own) & (t >= 0)[:, :, None]
    forced = seen & ((b < config.sparse_init_blocks) | (b > own - local))
    ranked = jnp.where(forced[:, None], 2.0 * H,
                       jnp.where(seen[:, None], score, -1.0))
    k = min(config.sparse_topk, NB)
    pick = top_blocks if B * KV * Tq >= _PICK_QUERIES else jax.lax.top_k
    vals, idx = pick(ranked, k)
    return idx.astype(jnp.int32), vals >= 0


def top_blocks(ranked, k: int):
    """`jax.lax.top_k(ranked, k)` bit for bit, `(vals, idx)` `[..., k]` (the
    values descending, equal ones the lower index first), by SELECTION over
    the last axis `[..., NB]` and not by sorting it (`k <= NB`; no NaN, no
    -0.0: `select_blocks`' scores are -1, `[0, H]` and `2 H`):

    1. the floats become unsigned keys of the same order (the sign flip);
    2. each query's k-th largest key is found exactly by a search over the
       key's 32 bits, the highest first: a bit stays set where at least k
       keys reach the candidate;
    3. every key above that threshold is taken, and of those equal to it the
       lowest-numbered as far as k goes (counts before a block: a matmul
       with a triangle of ones, exact in float32);
    4. the taken blocks go to k places in block order (a `[..., k, NB]`
       compare-and-reduce) and a sort of width k orders the pairs by (value
       descending, block ascending)."""
    NB = ranked.shape[-1]
    u32, i32 = jnp.uint32, jnp.int32
    bits = jax.lax.bitcast_convert_type(ranked, u32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | u32(1 << 31))

    def bit(i, thr):
        cand = thr | (u32(1 << 31) >> i.astype(u32))
        reach = jnp.sum(key >= cand[..., None], axis=-1, dtype=i32)
        return jnp.where(reach >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(ranked.shape[:-1], u32))
    above, tied = key > thr[..., None], key == thr[..., None]
    b = jnp.arange(NB, dtype=i32)
    upto = (b[:, None] <= b[None]).astype(jnp.bfloat16)

    def count(m):       # [..., NB] bool: how many hold up to and at a block
        return jnp.einsum("...n,nm->...m", m.astype(jnp.bfloat16), upto,
                          preferred_element_type=jnp.float32).astype(i32)

    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=i32)
    taken = above | (tied & (count(tied) <= room))
    place = jnp.where(taken, count(taken) - 1, -1)
    at = place[..., None, :] == jnp.arange(k, dtype=i32)[:, None]
    idx = jnp.sum(jnp.where(at, b, 0), axis=-1)                 # [..., k]
    vals = jnp.max(jnp.where(at, ranked[..., None, :], -jnp.inf), axis=-1)
    down, idx = jax.lax.sort((-vals, idx), num_keys=2)
    return -down, idx


def _row_keys(config, kc_stack, layer, table, start, page_size: int):
    """One row's compressed keys out of the paged cache, `[1, KV, Nc, hd]`
    in POSITION order, read through its `table` [nb] from the page of its
    FIRST key on: key j lies at compressed slot `start // stride + j`
    (`compress_write`), so the pages shift by index and the entries inside a
    page by a slice one page wide. What lies past the row's table has not
    ended for any query of the row (`select_blocks`)."""
    per = page_size // config.sparse_kernel_stride
    nb = table.shape[0]
    c0 = start // config.sparse_kernel_stride
    blk = jnp.minimum(c0 // per + jnp.arange(nb + 1, dtype=jnp.int32), nb - 1)
    got = kc_stack[layer, jnp.minimum(table[blk], kc_stack.shape[1] - 1)]
    hd = got.shape[-1]                              # [nb + 1, KV per, hd]
    got = got.reshape(nb + 1, -1, per, hd).transpose(1, 0, 2, 3)
    return jax.lax.dynamic_slice_in_dim(
        got.reshape(1, got.shape[0], (nb + 1) * per, hd), c0 % per, nb * per,
        axis=2)


def select_needed(config, q, kc_stack, layer, view, t, need):
    """A paged decode step's selection over the rows that will USE it,
    `need` [B] bool (the rows `sparse_decode_plan` gives their chosen
    blocks' items): `(idx, ok)` `[B, KV, 1, k]`, what `select_blocks` gives
    a needed row, zeros and False for every other.

    A loop over the needed rows, as many trips as there are: a trip gathers
    ONE row's compressed keys through its table, scores, pools and ranks
    them, and writes the row's place. A step none of whose rows selects
    takes no trip."""
    start, _ = view.span
    per = view.page_size // config.sparse_kernel_stride
    shape = (q.shape[0], kc_stack.shape[2] // per, 1, min(
        config.sparse_topk, _blocks_of(config, view.table.shape[1] * per)))
    order = jnp.argsort(~need, stable=True).astype(jnp.int32)

    def one(i, found):
        row = order[i]
        at = lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1)  # noqa: E731
        kc = _row_keys(config, kc_stack, layer, view.table[row], start[row],
                       view.page_size)
        return tuple(jax.lax.dynamic_update_slice_in_dim(buf, a, row, 0)
                     for buf, a in zip(found, select_blocks(
                         config, at(q), kc, at(t))))

    return jax.lax.fori_loop(
        0, jnp.sum(need, dtype=jnp.int32), one,
        (jnp.zeros(shape, jnp.int32), jnp.zeros(shape, bool)))


def _in_query_blocks(fn, Tq: int, per_query_bytes: int, *arrays):
    """`fn(*arrays)` over blocks of queries (every array's axis 2) such that
    one block's float32 scores stay under `_SCORE_BYTES`; fn's result has
    the queries on axis 2 too."""
    fit = max(_SCORE_BYTES // max(per_query_bytes, 1), 8)
    bq = 1 << (fit.bit_length() - 1)
    if Tq <= bq:
        return fn(*arrays)
    n = -(-Tq // bq)

    def blocks(a):
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, n * bq - Tq)
        a = jnp.pad(a, pad)
        return jnp.moveaxis(
            a.reshape(a.shape[:2] + (n, bq) + a.shape[3:]), 2, 0)

    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks(a) for a in arrays))
    out = jnp.moveaxis(out, 0, 2)
    return out.reshape(out.shape[:2] + (n * bq,) + out.shape[4:])[:, :, :Tq]


# --------------------------------------------------------------------------- #
# the sparse layer: the read
# --------------------------------------------------------------------------- #

def _attend_chosen(config, q, t, kc, fetch, K: int, n_blocks: int, mask,
                   start, selects, last):
    """Attention of `q` [B, H, Tq, hd] over the keys its queries CHOSE, the
    plain form of the sparse read and the oracle of its kernel: in blocks
    of queries, `select_blocks` and then a walk of the key blocks `fetch(kb)
    -> (k, v)` `[B, KV, K, hd]` (slots `[kb K, kb K + K)`) with a float32
    online softmax, a key block no query of the block chose skipped. `t`
    [B, Tq] the queries' positions; `mask` [B, 1, Tq, >= n_blocks K] the
    call's own in SLOT space (causal, key-valid); a query of `selects` [B,
    Tq] reads a key only in a block it chose, any other every key its mask
    allows; `start` [B] each row's first slot, `last` [B] its last key's
    slot in this call."""
    block = config.sparse_block_size
    B, H, Tq, hd = q.shape
    KV = kc.shape[1]
    G = H // KV
    if K % block:
        raise ValueError(f"key blocks of {K} slots do not hold whole blocks "
                         f"of {block} (sparse_block_size)")
    nw = K // block + 1
    mask = jnp.pad(mask, ((0, 0),) * 3 + (
        (0, max(n_blocks * K - mask.shape[-1], 0)),))
    lo = jnp.clip(jnp.min(start) // K, 0, n_blocks)
    hi = jnp.clip(jnp.max(last) // K + 1, 0, n_blocks)
    scale = 1.0 / hd ** 0.5

    def some(qb, tb, sb, mb):
        """One block of queries: qb [B, H, bq, hd], tb and sb [B, 1, bq], mb
        [B, 1, bq, W]."""
        bq = qb.shape[2]
        with jax.named_scope("attn.select"):
            idx, ok = select_blocks(config, qb, kc, tb[:, 0])
            NB = _blocks_of(config, kc.shape[2])
            chosen = jnp.any(
                (idx[..., None] == jnp.arange(NB, dtype=jnp.int32))
                & ok[..., None], axis=-2)                   # [B, KV, bq, NB]
            chosen = jnp.pad(chosen, ((0, 0),) * 3 + ((nw, nw),))
        qg = qb.reshape(B, KV, G, bq, hd)

        def chosen_slots(row, first, kb):
            """`row` [KV, bq, NB + 2 nw] by block; the key block's K slots."""
            rel = kb * K - first
            b0 = jnp.clip(rel // block, -nw, NB)
            part = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                row, b0 + nw, nw, axis=-1), block, axis=-1)
            return jax.lax.dynamic_slice_in_dim(
                part, jnp.clip(rel - b0 * block, 0, block), K, axis=-1)

        def fold(kb, carry):
            valid = jax.lax.dynamic_slice_in_dim(mb, kb * K, K, axis=3)
            picked = jax.vmap(chosen_slots, in_axes=(0, 0, None))(
                chosen, start, kb)                          # [B, KV, bq, K]
            valid = valid & (picked | ~sb[..., None])

            def attend(carry):
                m_i, l_i, acc = carry
                kd, vd = fetch(kb)
                s = jnp.einsum("bkgqh,bkth->bkgqt", qg, kd,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid[:, :, None], s, NEG_INF)
                m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_i - m_new)
                p = jnp.where(valid[:, :, None], jnp.exp(s - m_new), 0)
                l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
                pv = jnp.einsum("bkgqt,bkth->bkgqh", p.astype(vd.dtype), vd,
                                preferred_element_type=jnp.float32)
                return m_new, l_new, acc * alpha + pv

            return jax.lax.cond(jnp.any(valid), attend, lambda c: c, carry)

        lead = (B, KV, G, bq)
        init = (jnp.full(lead + (1,), NEG_INF, jnp.float32),
                jnp.zeros(lead + (1,), jnp.float32),
                jnp.zeros(lead + (hd,), jnp.float32))
        with jax.named_scope("attn.read"):
            _, l, acc = jax.lax.fori_loop(lo, hi, fold, init)
            return (acc / jnp.maximum(l, 1e-30)).reshape(
                B, H, bq, hd).astype(q.dtype)

    per_query = B * H * max(K, kc.shape[2]) * 4
    return _in_query_blocks(some, Tq, per_query, q, t[:, None, :],
                            selects[:, None, :], mask)


def sparse_read(config, q, k, v, view, new_cache, kc_stack, layer,
                dense_read):
    """The sparse layer's attention contraction, `[B, H, T, hd]`: over
    `new_cache` (the layer's K and V stacks, which hold this call's tokens)
    and `kc_stack` (its compressed keys, which hold the ones this call
    completed), or over `k`, `v` alone where there is no cache or a prefill
    from slot 0 has every key at hand. `dense_read()` is the plain layer's
    read of the same call (`core/model._attention_read`): what a call of
    T > 1 tokens takes when none of its rows selects."""
    from nanorlhf_tpu.core import model as M

    B, H, T, hd = q.shape
    start, keys = view.span     # (keys: a row's [B], or a query's [B, T])
    selects = keys >= config.sparse_dense_len
    cached = new_cache is not None
    paged = cached and view.table is not None
    decode = view.decode is not None
    mask = view.mask
    if decode:
        filled = (view.decode.filled if hasattr(view.decode, "filled")
                  else view.decode[1])
        slots = (filled - 1)[:, None]
    elif view.verify is not None:
        slots = view.verify[1][:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    else:
        slots = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    t = slots - start[:, None]
    if view.conv_ctx is not None and view.conv_ctx[0] is not None:
        t = jnp.where(view.conv_ctx[0], t, -1)
    last = jnp.max(slots, axis=1)

    if paged and decode and T == 1 and M.use_paged_decode_kernel(config):
        # the chosen blocks' pages, read from the stacks in place
        from nanorlhf_tpu.ops.sparse_attention import (
            plan_rows, sparse_decode_plan, sparse_paged_decode_attention,
        )

        with jax.named_scope("attn.select"):
            need = selects & plan_rows(start, filled, view.live, view.table,
                                       page_size=view.page_size,
                                       num_pages=new_cache[0].shape[1])
            idx, ok = select_needed(config, q, kc_stack, layer, view, t, need)
            plan = sparse_decode_plan(
                config, idx[:, :, 0], ok[:, :, 0], start, filled, selects,
                view.live, view.table, page_size=view.page_size,
                num_pages=new_cache[0].shape[1])
        with jax.named_scope("attn.read"):
            return sparse_paged_decode_attention(
                q[:, :, 0], *new_cache, layer, plan)[:, :, None, :]

    at_hand = not cached or (T > 1 and not decode and view.verify is None)
    selects = jnp.broadcast_to(selects.reshape(B, -1), (B, T))

    def over_arrays(keys, values):
        """`(fetch, K, key blocks)` of `_attend_chosen` over K and V arrays
        `[B, KV, width, hd]`: blocks of at most `_KEY_BLOCK` slots, whole
        selection blocks each, the last padded."""
        width, block = keys.shape[2], config.sparse_block_size
        K = min(_KEY_BLOCK, -(-width // block) * block)
        n_blocks = -(-width // K)
        pad = ((0, 0), (0, 0), (0, n_blocks * K - width), (0, 0))
        padded = tuple(jnp.pad(a, pad) for a in (keys, values))
        return (lambda kb: tuple(jax.lax.dynamic_slice_in_dim(
            a, kb * K, K, axis=2) for a in padded)), K, n_blocks

    def chosen_read():
        if at_hand:
            with jax.named_scope("attn.compress"):
                kc = compress_at_hand(config, k, start)
            local = mask[..., :T] if cached else mask
            return _attend_chosen(config, q, t, kc, *over_arrays(k, v),
                                  local, start, selects, last)
        with jax.named_scope("attn.select"):
            kc = compressed_keys(config, kc_stack, layer, view)
        if paged:
            bp, P = M._PAGED_BLOCK_PAGES, view.page_size
            nb = view.table.shape[1]
            n_blocks = -(-nb // bp)
            table = jnp.pad(view.table, ((0, 0), (0, n_blocks * bp - nb)),
                            constant_values=new_cache[0].shape[1])
            fetch = lambda kb: tuple(M._paged_view(                 # noqa: E731
                pool, layer, jax.lax.dynamic_slice_in_dim(
                    table, kb * bp, bp, axis=1), bp * P)
                for pool in new_cache)
            return _attend_chosen(config, q, t, kc, fetch, bp * P, n_blocks,
                                  mask, start, selects, last)
        slabs = (M._layer_slab(c, layer) for c in new_cache)
        return _attend_chosen(config, q, t, kc, *over_arrays(*slabs), mask,
                              start, selects, last)

    if T == 1:
        return chosen_read()
    return jax.lax.cond(jnp.any(selects), chosen_read, dense_read)
