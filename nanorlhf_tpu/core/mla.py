"""Latent attention (MLA) as A.X-K1 / DeepSeek-V3 publish it: docs/MLA.md.

`h` is the normed residual stream, per layer:

    c_q  = rmsnorm(h W_qa)                      [q_lora_rank]
    [q_nope | q_r] = c_q W_qb    per head       [qk_nope | qk_rope]
    [c_kv | k_r]   = h W_kva                    [kv_lora_rank | qk_rope]
    c_kv = rmsnorm(c_kv);  RoPE on q_r and on k_r, which every head shares

The cache holds `[c_kv | k_r]` only, `ModelConfig.latent_width` values a
token a layer, under a single "head", addressed by the K/V caches' own
`(layer, row or page, slot)` and riding the same carries:

- contiguous (`generate`'s rollout): ONE stacked array `[L, B, 1, T_max, W]`
  (core/model.py `_cache_write`, `_layer_slab`);
- paged (the session's pool): TWO arrays whose minor axes are whole 128-lane
  tiles, `c_kv` `[L, num_pages, 1, page_size, kv_lora_rank]` and `k_r`
  `[L, num_pages, 1, page_size / pack, pack x qk_rope_head_dim]`, `pack`
  tokens' rotary keys side by side in a row (A.X-K1: two 64-wide keys).
  The same bytes as one 576-wide array, but 576 is 4.5 tiles: for
  `[.., 128, 576]` the TPU's default layout at a program's boundary is the
  transposed, padding-free one while the scatter that writes a token wants
  the token's values minor, so every session program relaid the WHOLE pool
  on the way in and on the way out (two 2.8 GB copies a call and 16.2 GB
  live; storing pages latent-major only moved the two copies; compiled for
  a v5e, PR 31). Lane-aligned leaves have one layout everywhere.

Two forms of the same attention (equal in exact arithmetic), chosen by
whether a cache is read at T = 1:

- **expanded** (the uncached forward, prefill, and every T > 1 read of the
  cache: chunked prefill, suffix prefill, speculative verify):
  `[k_nope | v] = c_kv W_kvb` per head, scores `q_nope.k_nope + q_r.k_r`,
  float32 softmax, `P v`. Without a cache queries go in blocks
  (`_SCORE_BYTES`), so that a 4k-token scoring row never holds its whole
  [H, T, T] score array; against the paged cache KEYS go in blocks of
  `_BLOCK_PAGES` pages, over the blocks that hold the row's live slots only
  (`_attend_paged`: an online softmax; a 1,024-token chunk at the start of a
  4,224-token prompt expands and attends 1,024 keys, not the row's 8,704).
- **absorbed** (the single-token decode step): `W_kvb` splits per head into
  `W_uk` and `W_uv`; `q~ = q_nope W_uk^T` lives in the latent space, scores
  are `q~.c_kv + q_r.k_r` straight against the cached latents, and
  `o = (P c_kv) W_uv`. Nothing per head is ever built from the cache. The
  paged read is XLA over the same key blocks, bounded by the LIVE rows'
  slots (`ops/decode_attention`'s in-place kernel is a GQA read of
  equal-width K and V pages and refuses a 576-wide one:
  `core/model.use_paged_decode_kernel`).

Scale `s = (qk_nope + qk_rope)^-0.5 * mscale(factor, mscale_all_dim)^2`; YaRN
frequencies and the cos/sin multiplier are DeepSeek-V3's (`rope_tables`).

Spans: `mla.q`, `mla.latent`, `mla.expand`, `mla.absorb`, `mla.attend`,
`mla.out`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core.config import ModelConfig
from nanorlhf_tpu.core.model import (
    NEG_INF, _cache_write, _layer_slab, _paged_cache_update, _paged_slots,
    _paged_view, _proj, apply_rope, rms_norm,
)

# one block of queries' float32 scores [B, H, block, S] stays under this
_SCORE_BYTES = 512 << 20


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(config: ModelConfig) -> float:
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    if config.yarn is not None:
        factor, _, _, _, _, all_dim = config.yarn
        scale *= yarn_mscale(factor, all_dim) ** 2
    return scale


def rope_tables(config: ModelConfig, positions: jnp.ndarray):
    """cos/sin [B, T, qk_rope_head_dim] (f32, rotate-half layout) with
    DeepSeek-V3's YaRN: dimensions that turn more than `beta_fast` times
    within the original context keep their frequency, those under
    `beta_slow` turns are slowed by `factor`, a linear ramp between."""
    dim, base = config.qk_rope_head_dim, float(config.rope_theta)
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    mult = 1.0
    if config.yarn is not None:
        factor, original, beta_fast, beta_slow, mscale, all_dim = config.yarn

        def correction_dim(rotations):
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low)
            / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        mult = yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * mult, jnp.sin(angles) * mult


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random tree of an MLA model: `dense_layers` (the leading
    `num_dense_layers`, dense SwiGLU) and `layers` (the rest: router, the
    HELD routed experts `[L, held, D, F]`, the shared expert), both stacked;
    kernels normal / sqrt(fan-in), norms ones."""
    D, V, F = config.hidden_size, config.vocab_size, config.intermediate_size
    H, Fe = config.num_attention_heads, config.expert_width
    dq, r = config.q_lora_rank, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    Ld = config.num_dense_layers
    Le = config.num_hidden_layers - Ld
    keys = iter(jax.random.split(key, 40))

    def dense(shape, scale=None, fan_in=None):
        scale = scale if scale is not None else (fan_in or shape[-2]) ** -0.5
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def attention(L):
        return {
            "input_layernorm": jnp.ones((L, D), dtype),
            "q_a_proj": {"kernel": dense((L, D, dq))},
            "q_a_layernorm": jnp.ones((L, dq), dtype),
            "q_b_proj": {"kernel": dense((L, dq, H * (dn + dr)))},
            "kv_a_proj": {"kernel": dense((L, D, r + dr))},
            "kv_a_layernorm": jnp.ones((L, r), dtype),
            "kv_b_proj": {"kernel": dense((L, r, H * (dn + dv)))},
            "o_proj": {"kernel": dense((L, H * dv, D))},
            "post_attention_layernorm": jnp.ones((L, D), dtype),
        }

    def swiglu(lead, width):
        return {"gate_proj": {"kernel": dense(lead + (D, width))},
                "up_proj": {"kernel": dense(lead + (D, width))},
                "down_proj": {"kernel": dense(lead + (width, D))}}

    params = {"embed_tokens": dense((V, D), scale=0.02),
              "norm": jnp.ones((D,), dtype)}
    if Ld:
        params["dense_layers"] = {**attention(Ld), **swiglu((Ld,), F)}
    layers = attention(Le)
    if config.num_experts:
        layers["router"] = {"kernel": dense((Le, D, config.num_experts))}
        layers["experts"] = swiglu((Le, config.num_held_experts), Fe)
        if config.n_shared_experts:
            layers["shared_expert"] = swiglu(
                (Le,), Fe * config.n_shared_experts)
    else:
        layers.update(swiglu((Le,), F))
    params["layers"] = layers
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((D, V), scale=0.02)
    return params


def _query_block(B: int, H: int, T: int, S: int) -> int:
    """Queries a block: all of them when their scores fit `_SCORE_BYTES`,
    else the largest power of two that does (at least 8)."""
    if B * H * T * S * 4 <= _SCORE_BYTES:
        return T
    fit = max(_SCORE_BYTES // (B * H * S * 4), 8)
    return 1 << (fit.bit_length() - 1)


def _attend_expanded(q_nope, q_r, k_nope, k_r, v, mask, scale):
    """q_nope [B, H, T, dn], q_r [B, H, T, dr]; k_nope [B, H, S, dn], k_r
    [B, S, dr] (shared by the heads), v [B, H, S, dv]; mask [B, 1, T, S].
    Float32 scores and softmax, probabilities in the values' dtype for the
    PV product (as `gqa_attention`). Returns [B, H, T, dv]."""

    def block(qn, qr, m):
        s = (jnp.einsum("bhqd,bhkd->bhqk", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhqd,bkd->bhqk", qr, k_r,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(m, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    B, H, T, _ = q_nope.shape
    S = k_nope.shape[2]
    bq = _query_block(B, H, T, S)
    if bq >= T:
        return block(q_nope, q_r, mask)
    n = -(-T // bq)
    pad = n * bq - T    # padded queries attend to nothing and are cut off

    def blocks(x, axis):
        x = jnp.pad(x, [(0, pad if a == axis else 0) for a in range(x.ndim)])
        shape = x.shape[:axis] + (n, bq) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    out = jax.lax.map(lambda a: block(*a), (blocks(q_nope, 2), blocks(q_r, 2),
                                            blocks(mask, 2)))
    out = jnp.moveaxis(out, 0, 2)                       # [B, H, n, bq, dv]
    return out.reshape(B, H, n * bq, -1)[:, :, :T]


def rope_pack(config: ModelConfig, page_size: int) -> int:
    """Tokens whose rotary keys share a row of the paged `k_r` pool: as many
    as fill 128 lanes, halved until they divide the page."""
    pack = max(128 // config.qk_rope_head_dim, 1)
    while page_size % pack:
        pack //= 2
    return pack


def paged_cache_shapes(config: ModelConfig, num_pages: int,
                       page_size: int) -> tuple:
    """Shapes of the paged latent cache's two leaves (module docstring)."""
    pack = rope_pack(config, page_size)
    lead = (config.num_hidden_layers, num_pages, 1)
    return (lead + (page_size, config.kv_lora_rank),
            lead + (page_size // pack, pack * config.qk_rope_head_dim))


@jax.named_scope("attn.write")     # as `core/model._cache_write` is
def _paged_latent_write(pools, c_kv, k_r, layer, table, cache_index,
                        page_size):
    """Write `c_kv` [B, T, r] and `k_r` [B, T, dr] through the block table
    into layer `layer` of the two pools, in place. Sentinel and over-budget
    slots drop, as in `core/model._paged_cache_update` (which writes
    `c_kv`). A rotary key is `dr` lanes of a row it shares with its
    neighbours, and a scatter of such part-rows goes one key at a time
    (1,024 keys a layer: 27 ms of a 217 ms prefill chunk; my chip run,
    PR 31), so the pages the T tokens touch are read, patched in token order
    and written back whole (a row writes only pages that are its own)."""
    B, T, dr = k_r.shape
    c_pool = _paged_cache_update(pools[0], c_kv[:, None], layer, table,
                                 cache_index, page_size)
    r_pool = pools[1]
    P, num_pages, nb = page_size, r_pool.shape[1], table.shape[1]
    first = _paged_slots(cache_index, B, 1)                      # [B, 1]
    touched = (T + P - 2) // P + 1      # pages T consecutive slots can span
    lb = first // P + jnp.arange(touched, dtype=jnp.int32)[None]     # [B, n]
    page = jnp.where(
        lb < nb, jnp.take_along_axis(table, jnp.clip(lb, 0, nb - 1), axis=1),
        num_pages)
    old = r_pool[layer, jnp.minimum(page, num_pages - 1), 0]
    token = (lb[:, :, None] * P + jnp.arange(P, dtype=jnp.int32)
             ).reshape(B, touched * P) - first           # index into the T new
    new = jnp.take_along_axis(k_r, jnp.clip(token, 0, T - 1)[..., None], axis=1)
    patched = jnp.where(((token >= 0) & (token < T))[..., None], new,
                        old.reshape(B, touched * P, dr))
    r_pool = r_pool.at[layer, page, 0].set(
        patched.reshape(old.shape), mode="drop")
    return c_pool, r_pool


def _paged_latent_view(pools, layer, table, page_size):
    """The latents of the table's pages, token-major, gathered through it:
    `(c_kv [B, n x page_size, r], k_r [B, n x page_size, dr])` for a table
    `[B, n]`. Sentinel entries clamp to the last page; the mask excludes
    their slots (`core/model._paged_view`)."""
    slots = table.shape[1] * page_size
    c = _paged_view(pools[0], layer, table, slots)[:, 0]
    r_pool = pools[1]
    g = r_pool[layer, jnp.minimum(table, r_pool.shape[1] - 1), 0]
    return c, g.reshape(g.shape[0], slots, -1)


# pages a key block of the paged reads holds (1,024 keys at pages of 128)
_BLOCK_PAGES = 8


def _attend_paged(pools, layer, table, page_size, mask, first, last, scale,
                  heads, out_width, scores_and_values):
    """Attention over a paged latent cache a block of `_BLOCK_PAGES` pages at
    a time, over the blocks that hold slots `[min(first), max(last)]` only:
    the rows' live keys, not every entry of every table (the whole-width
    read was 63 % of a prefill chunk and 62 % of a decode step; my chip
    runs, PR 31). Online softmax in float32 across the blocks, as a flash
    kernel does it. `scores_and_values(c_blk [B, K, r], k_r_blk [B, K, dr])`
    returns the block's raw scores `[B, H, T, K]` (float32, unscaled) and
    its values, `[B, H, K, out_width]` or, shared by the heads,
    `[B, K, out_width]`; `mask` [B, 1, T, width]; `first`
    / `last` [B] int32 bound the slots any row needs (a row that needs none
    passes an empty range). Returns [B, H, T, out_width] float32."""
    B, _, T, width = mask.shape
    nb = table.shape[1]
    K = _BLOCK_PAGES * page_size
    n_blocks = -(-nb // _BLOCK_PAGES)
    table = jnp.pad(table, ((0, 0), (0, n_blocks * _BLOCK_PAGES - nb)),
                    constant_values=pools[0].shape[1])
    mask = jnp.pad(mask, ((0, 0),) * 3 + ((0, n_blocks * K - width),))
    lo = jnp.clip(jnp.min(first) // K, 0, n_blocks)
    hi = jnp.clip(jnp.max(last) // K + 1, 0, n_blocks)

    def block(kb, carry):
        m_i, l_i, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, kb * _BLOCK_PAGES,
                                             _BLOCK_PAGES, axis=1)
        s, v = scores_and_values(
            *_paged_latent_view(pools, layer, pages, page_size))
        valid = jax.lax.dynamic_slice_in_dim(mask, kb * K, K, axis=3)
        s = jnp.where(valid, s * scale, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhtk,bkd->bhtd" if v.ndim == 3 else "bhtk,bhkd->bhtd",
                        p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    init = (jnp.full((B, heads, T, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, heads, T, 1), jnp.float32),
            jnp.zeros((B, heads, T, out_width), jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo, hi, block, init)
    return acc / jnp.maximum(l, 1e-30)      # a row with no block reads zero


def _kv_b_kernel(layer_params, lora_layer, lora_scale):
    """`W_kvb` [kv_lora_rank, H * (dn + dv)] as the absorbed form needs it:
    a weight, not a projection of something, so an adapter is folded in."""
    w = layer_params["kv_b_proj"]["kernel"]
    if lora_layer is not None and "kv_b_proj" in lora_layer:
        ab = lora_layer["kv_b_proj"]
        w = w + ((ab["a"] @ ab["b"]) * lora_scale).astype(w.dtype)
    return w


def mla_attention(config: ModelConfig, h, layer_params, lora_layer, lora_scale,
                  cos, sin, view, kv_cache, layer):
    """The attention half of an MLA layer on normed hidden states `h`
    [B, T, D]: returns `(attention output through W_o [B, T, D], the updated
    stacked cache or None)`. `view` is the call's `core/model.KindView` (its
    mask, bounds, table and slot), `layer` the layer's index into
    `kv_cache`, the 1-tuple of `init_kv_cache` / the pools of
    `init_paged_kv_cache`."""
    B, T, _ = h.shape
    mask, decode_bounds, verify_bounds = view.mask, view.decode, view.verify
    paged = None if view.table is None else (view.table, view.page_size)
    H, eps = config.num_attention_heads, config.rms_norm_eps
    dn, dr, dv, r = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim, config.kv_lora_rank)
    scale = softmax_scale(config)
    proj = lambda x, name: _proj(x, layer_params, lora_layer, name, lora_scale)  # noqa: E731

    with jax.named_scope("mla.q"):
        c_q = rms_norm(proj(h, "q_a_proj"), layer_params["q_a_layernorm"], eps)
        q = proj(c_q, "q_b_proj").reshape(B, T, H, dn + dr).transpose(0, 2, 1, 3)
        q_nope, q_r = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
    with jax.named_scope("mla.latent"):
        kv_a = proj(h, "kv_a_proj")
        c_kv = rms_norm(kv_a[..., :r], layer_params["kv_a_layernorm"], eps)
        k_r = apply_rope(kv_a[:, None, :, r:], cos, sin)[:, 0]     # [B, T, dr]

    new_cache = None
    if kv_cache is not None and paged is not None:
        new_cache = _paged_latent_write(kv_cache, c_kv, k_r, layer, paged[0],
                                        view.index, paged[1])
    elif kv_cache is not None:
        latent = jnp.concatenate([c_kv, k_r], axis=-1)[:, None]  # one "head"
        new_cache = _cache_write(kv_cache, (latent,), layer, view)

    def paged_read(first, last, out_width, scores_and_values):
        return _attend_paged(new_cache, layer, paged[0], paged[1], mask, first,
                             last, scale, H, out_width, scores_and_values)

    if new_cache is not None and T == 1 and verify_bounds is None:
        w = _kv_b_kernel(layer_params, lora_layer, lora_scale)
        w = w.reshape(r, H, dn + dv)
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, :, 0], w[..., :dn])

        def absorbed(c_src, k_r_src):
            """Raw scores [B, H, K] of the one query against cached latents."""
            return (jnp.einsum("bhr,bkr->bhk", q_lat, c_src,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bhd,bkd->bhk", q_r[:, :, 0], k_r_src,
                                 preferred_element_type=jnp.float32))

        with jax.named_scope("mla.attend"):
            if paged is not None:
                start, filled = decode_bounds if decode_bounds is not None else (
                    jnp.zeros((B,), jnp.int32),
                    jnp.full((B,), mask.shape[-1], jnp.int32))
                o_lat = paged_read(
                    start, filled - 1, r,
                    lambda c, kr: (absorbed(c, kr)[:, :, None], c),
                )[:, :, 0].astype(h.dtype)
            else:
                slab = _layer_slab(new_cache[0], layer)[:, 0]
                s = jnp.where(mask[:, 0], absorbed(slab[..., :r], slab[..., r:])
                              * scale, NEG_INF)
                p = jax.nn.softmax(s, axis=-1).astype(slab.dtype)
                o_lat = jnp.einsum("bhw,bwr->bhr", p, slab[..., :r])
        with jax.named_scope("mla.absorb"):
            out = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., dn:])[:, :, None]
    elif new_cache is not None and verify_bounds is not None and paged is not None:
        # T candidate or chunk tokens against the pages they just joined,
        # expanded a key block at a time
        def expanded(c_src, k_r_src):
            kv = proj(c_src, "kv_b_proj").reshape(
                B, -1, H, dn + dv).transpose(0, 2, 1, 3)
            return (jnp.einsum("bhqd,bhkd->bhqk", q_nope, kv[..., :dn],
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r_src,
                                 preferred_element_type=jnp.float32)
                    ), kv[..., dn:]

        start, fill = verify_bounds
        with jax.named_scope("mla.attend"):
            out = paged_read(start, fill + (T - 1), dv, expanded).astype(h.dtype)
    else:
        if new_cache is not None and verify_bounds is not None:
            # the same against a contiguous cache: the whole slab
            slab = _layer_slab(new_cache[0], layer)[:, 0]
            c_src, k_r_src, m = slab[..., :r], slab[..., r:], mask
        else:
            # the uncached forward, and a prefill from slot 0: the tokens at
            # hand are all there is to attend to
            c_src, k_r_src, m = c_kv, k_r, mask[..., :T]
        with jax.named_scope("mla.expand"):
            kv = proj(c_src, "kv_b_proj").reshape(
                B, -1, H, dn + dv).transpose(0, 2, 1, 3)
        with jax.named_scope("mla.attend"):
            out = _attend_expanded(q_nope, q_r, kv[..., :dn], k_r_src,
                                   kv[..., dn:], m, scale)
    with jax.named_scope("mla.out"):
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * dv)
        return proj(out, "o_proj"), new_cache
