"""Pallas decode-attention kernel: read only the FILLED cache prefix.

The round-1 decode step attended over the whole [B, KV, T_max, hd] cache
with masking every token (`core/model.py` decode path) — at 8k-token
responses that reads the full cache square-wise over the rollout while the
valid region grows linearly. This kernel is the TPU-native analogue of
vLLM's paged/decode attention (SURVEY.md §2.2 row 1, replacing the CUDA
kernels behind `/root/reference/GRPO/grpo_trainer.py:122-166`):

- **Scalar-prefetched bounds**: per-row `start` (left-pad offset) and
  `filled` (one past the last written slot) arrive as scalar-prefetch
  operands, so the KV BlockSpec index_map can CLAMP the block index to the
  valid range. Grid steps past the last valid block re-map to the same
  block; Pallas's revisiting optimization skips the re-fetch, so HBM traffic
  is proportional to the filled prefix, not T_max.
- **Online softmax** across kv blocks (same recipe as `ops/attention.py`),
  carried in VMEM scratch.
- **GQA layout**: queries are grouped [B, KV, G, hd] and each (batch, kv
  head) grid cell contracts its G query heads against one un-repeated KV
  block — no KV repeat materialization, identical to the train-time kernel.

Decode attention is HBM-bandwidth-bound (the MXU sees [G, block] matmuls);
the win is skipped traffic, not FLOPs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.attention import _interpret_default

NEG_INF = -1e30


def reference_decode_attention(q, k_cache, v_cache, start, filled):
    """XLA oracle: masked softmax over the cache. q: [B, H, hd];
    k/v: [B, KV, T, hd]; start/filled: [B] int32. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(T)[None, :]
    valid = (pos >= start[:, None]) & (pos < filled[:, None])  # [B, T]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgt,bkth->bkgh", p, v_cache)
    return out.reshape(B, H, hd)


def _decode_kernel(start_ref, filled_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, block_k: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    filled = filled_ref[b]
    first_blk = start // block_k
    last_blk = (filled - 1) // block_k
    actual_j = jnp.minimum(first_blk + j, last_blk)

    # grid steps beyond the valid range re-visit last_blk with compute skipped
    @pl.when(first_blk + j <= last_blk)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [Gp, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [block_k, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [Gp, block_k]
        pos = actual_j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where((pos >= start) & (pos < filled), s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def reference_decode_attention_q8(q, k_q, k_s, v_q, v_s, start, filled):
    """XLA oracle for the int8-cache kernel: dequantize, then the exact
    reference. k_q/v_q: [B, KV, T, hd] int8; k_s/v_s: [B, KV, 8, T] bf16
    (sublane-expanded scales, core/model.init_kv_cache)."""
    dt = q.dtype
    k = (k_q.astype(jnp.float32) * k_s[:, :, 0, :, None]).astype(dt)
    v = (v_q.astype(jnp.float32) * v_s[:, :, 0, :, None]).astype(dt)
    return reference_decode_attention(q, k, v, start, filled)


def _decode_q8_kernel(start_ref, filled_ref, q_ref, kq_ref, ks_ref, vq_ref,
                      vs_ref, o_ref, acc_ref, m_ref, l_ref,
                      *, scale: float, block_k: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    filled = filled_ref[b]
    first_blk = start // block_k
    last_blk = (filled - 1) // block_k
    actual_j = jnp.minimum(first_blk + j, last_blk)

    @pl.when(first_blk + j <= last_blk)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [Gp, hd]
        k = kq_ref[0, 0].astype(jnp.float32)             # [block_k, hd] int8→f32
        v = vq_ref[0, 0].astype(jnp.float32)
        ks = ks_ref[0, 0][:1, :]                         # [1, block_k]
        vs = vs_ref[0, 0][:1, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale * ks                                   # fold k scales into the score row
        pos = actual_j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where((pos >= start) & (pos < filled), s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # fold v scales into the probability row: Σ p·(v_q·vs) = (p·vs)@v_q
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p * vs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def decode_attention_q8(
    q: jnp.ndarray,      # [B, H, hd] — single decode position
    k_q: jnp.ndarray,    # [B, KV, T_max, hd] int8
    k_s: jnp.ndarray,    # [B, KV, 8, T_max] bf16 sublane-expanded scales
    v_q: jnp.ndarray,    # [B, KV, T_max, hd] int8
    v_s: jnp.ndarray,    # [B, KV, 8, T_max] bf16
    start: jnp.ndarray,  # [B] int32
    filled: jnp.ndarray, # [B] int32
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Prefix-bounded decode attention over the int8 KV cache. int8 value
    blocks + bf16 scale rows stream HBM→VMEM at 144/256 of the exact cache's
    bytes (hd=128); dequantization is two row-broadcast multiplies folded
    into the existing online-softmax math. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, T = k_q.shape[1], k_q.shape[2]
    G = H // KV
    Gp = max(8, G)
    block_k = min(block_k, max(128, 128 * pl.cdiv(T, 128)))

    qg = q.reshape(B, KV, G, hd)
    if Gp != G:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, Gp - G), (0, 0)])

    if T % block_k != 0:
        pad_t = block_k * pl.cdiv(T, block_k) - T
        k_q = jnp.pad(k_q, [(0, 0), (0, 0), (0, pad_t), (0, 0)])
        v_q = jnp.pad(v_q, [(0, 0), (0, 0), (0, pad_t), (0, 0)])
        k_s = jnp.pad(k_s, [(0, 0), (0, 0), (0, 0), (0, pad_t)])
        v_s = jnp.pad(v_s, [(0, 0), (0, 0), (0, 0), (0, pad_t)])
        T = T + pad_t
    n_blk = T // block_k

    kernel = functools.partial(
        _decode_q8_kernel, scale=1.0 / (hd ** 0.5), block_k=block_k
    )

    def kv_index_map(b, kv, j, start_ref, filled_ref):
        first = start_ref[b] // block_k
        # max(last, 0): filled==0 (no valid slots) would map to block -1 —
        # the @pl.when guard already skips compute, but the prefetch index
        # must still be in range
        last = jnp.maximum((filled_ref[b] - 1) // block_k, 0)
        return (b, kv, jnp.minimum(first + j, last), 0)

    def scale_index_map(b, kv, j, start_ref, filled_ref):
        first = start_ref[b] // block_k
        last = jnp.maximum((filled_ref[b] - 1) // block_k, 0)
        return (b, kv, 0, jnp.minimum(first + j, last))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_blk),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
            pl.BlockSpec((1, 1, 8, block_k), scale_index_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
            pl.BlockSpec((1, 1, 8, block_k), scale_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Gp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Gp, hd), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, hd), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), filled.astype(jnp.int32), qg, k_q, k_s, v_q, v_s)
    return out[:, :, :G, :].reshape(B, H, hd)


def reference_decode_verify_attention(q, k_cache, v_cache, start, fill):
    """XLA oracle for the k-query (speculative verify) variant: query i of a
    row attends over cache slots [start, fill + i + 1) — the valid prefix
    plus the candidate tokens up to and including itself (their KV is
    already written at slots [fill, fill + Tq)). q: [B, H, Tq, hd];
    k/v: [B, KV, T, hd]; start/fill: [B] int32. Returns [B, H, Tq, hd]."""
    B, H, Tq, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Tq, hd)
    s = jnp.einsum("bkgqh,bkth->bkgqt", qg, k_cache).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(T)[None, None, :]                       # [1, 1, T]
    qi = jnp.arange(Tq)[None, :, None]                       # [1, Tq, 1]
    valid = (pos >= start[:, None, None]) & (
        pos < fill[:, None, None] + qi + 1
    )                                                        # [B, Tq, T]
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqt,bkth->bkgqh", p, v_cache)
    return out.reshape(B, H, Tq, hd)


def _verify_kernel(start_ref, fill_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, block_k: int,
                   Tq: int):
    """k-query generalization of `_decode_kernel`: the query block carries
    G*Tq rows (row r = g*Tq + qi) and the per-row key bound becomes
    fill + qi + 1 — the causal-within-candidates rule. Same prefix-clamped
    grid + online softmax as the single-query kernel."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    fill = fill_ref[b]
    first_blk = start // block_k
    last_blk = (fill + Tq - 1) // block_k
    actual_j = jnp.minimum(first_blk + j, last_blk)

    @pl.when(first_blk + j <= last_blk)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [Rp, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [block_k, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [Rp, block_k]
        pos = actual_j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % Tq
        s = jnp.where((pos >= start) & (pos < fill + qi + 1), s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def decode_verify_attention(
    q: jnp.ndarray,        # [B, H, Tq, hd] — k+1 candidate positions
    k_cache: jnp.ndarray,  # [B, KV, T_max, hd] (candidate KV already written)
    v_cache: jnp.ndarray,  # [B, KV, T_max, hd]
    start: jnp.ndarray,    # [B] int32: first valid cache slot
    fill: jnp.ndarray,     # [B] int32: slot of candidate 0 (query i owns
                           # slot fill + i; it attends to [start, fill+i+1))
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Prefix-bounded decode attention for a BLOCK of Tq candidate queries —
    the speculative-verify variant of `decode_attention` (interpret fallback
    off-TPU, like every kernel here). One kernel pass scores all k+1
    candidates against the cache, so the dominant weight/cache HBM stream is
    paid once per verify step instead of once per token. Returns
    [B, H, Tq, hd]."""
    B, H, Tq, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    R = G * Tq
    Rp = 8 * pl.cdiv(R, 8)  # sublane-pad the flattened (group, query) rows
    block_k = min(block_k, max(128, 128 * pl.cdiv(T, 128)))

    # [B, KV, G, Tq, hd] -> [B, KV, G*Tq, hd]; row r = g*Tq + qi, so the
    # kernel recovers the query index as r % Tq (padded rows compute a
    # garbage qi and are sliced off after the call)
    qg = q.reshape(B, KV, G, Tq, hd).reshape(B, KV, R, hd)
    if Rp != R:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, Rp - R), (0, 0)])

    if T % block_k != 0:
        pad_t = block_k * pl.cdiv(T, block_k) - T
        padz = [(0, 0), (0, 0), (0, pad_t), (0, 0)]
        k_cache = jnp.pad(k_cache, padz)
        v_cache = jnp.pad(v_cache, padz)
        T = T + pad_t
    n_blk = T // block_k

    kernel = functools.partial(
        _verify_kernel, scale=1.0 / (hd ** 0.5), block_k=block_k, Tq=Tq
    )

    def kv_index_map(b, kv, j, start_ref, fill_ref):
        first = start_ref[b] // block_k
        last = jnp.maximum((fill_ref[b] + Tq - 1) // block_k, 0)
        return (b, kv, jnp.minimum(first + j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_blk),
        in_specs=[
            pl.BlockSpec((1, 1, Rp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Rp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Rp, hd), jnp.float32),
            pltpu.VMEM((Rp, 128), jnp.float32),
            pltpu.VMEM((Rp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Rp, hd), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), fill.astype(jnp.int32), qg, k_cache, v_cache)
    return out[:, :, :R, :].reshape(B, KV, G, Tq, hd).reshape(B, H, Tq, hd)


def decode_attention(
    q: jnp.ndarray,        # [B, H, hd] — single decode position
    k_cache: jnp.ndarray,  # [B, KV, T_max, hd]
    v_cache: jnp.ndarray,  # [B, KV, T_max, hd]
    start: jnp.ndarray,    # [B] int32: first valid cache slot (left-pad offset)
    filled: jnp.ndarray,   # [B] int32: one past the last valid slot
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Prefix-bounded decode attention. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    Gp = max(8, G)  # sublane-pad the tiny query-head dim
    block_k = min(block_k, max(128, 128 * pl.cdiv(T, 128)))

    qg = q.reshape(B, KV, G, hd)
    if Gp != G:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, Gp - G), (0, 0)])

    if T % block_k != 0:
        pad_t = block_k * pl.cdiv(T, block_k) - T
        padz = [(0, 0), (0, 0), (0, pad_t), (0, 0)]
        k_cache = jnp.pad(k_cache, padz)
        v_cache = jnp.pad(v_cache, padz)
        T = T + pad_t
    n_blk = T // block_k

    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)

    def kv_index_map(b, kv, j, start_ref, filled_ref):
        first = start_ref[b] // block_k
        # max(last, 0): filled==0 (no valid slots) would map to block -1 —
        # the @pl.when guard already skips compute, but the prefetch index
        # must still be in range
        last = jnp.maximum((filled_ref[b] - 1) // block_k, 0)
        return (b, kv, jnp.minimum(first + j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_blk),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Gp, hd), lambda b, kv, j, s, f: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Gp, hd), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, hd), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), filled.astype(jnp.int32), qg, k_cache, v_cache)
    return out[:, :, :G, :].reshape(B, H, hd)


# --------------------------------------------------------------------------- #
# paged variants (ISSUE 10): K/V live in a global page pool and are gathered
# through a per-row block table instead of sitting in a per-row slab
# --------------------------------------------------------------------------- #
#
# Pool layout (core/model.py:init_paged_kv_cache): [L, num_pages, KV,
# page_size, hd]; block table: [B, n_blocks] int32 mapping logical block j of
# row b to a physical page (sentinel num_pages = unallocated, clamped here).
#
# The single-token read (`paged_decode_attention`, ISSUE 28) takes the whole
# stacks and reads pages in place: its cost follows the live rows' live pages.
# The int8 and verify twins below take ONE layer's pool [num_pages, KV, P, hd]
# and keep the contiguous kernels' bodies UNCHANGED — positions are logical
# (`actual_j * block_k + iota` with block_k = page_size), only the BlockSpec
# index maps change: the table rides along as a third scalar-prefetch operand
# and the kv index map resolves logical block → physical page before the DMA
# is issued. The same clamp-to-last-valid-block trick applies, so revisited
# blocks still skip the re-fetch (the grid step itself is not skipped).
#
# NOTE on tiling: block_k here is the page size, so the pool's (page_size, hd)
# trailing dims must satisfy the dtype's min tile — page_size ≥ 8 for f32,
# ≥ 16 for bf16, and the int8 scale block (1, 1, 8, page_size) wants
# page_size ≥ 128 lanes on real hardware. CPU tests run in interpret mode
# where any page size works; pick page_size ≥ 128 for compiled TPU runs.


def _gather_pool(pool, table):
    """[N, KV, P, hd] pool + [B, nb] table → contiguous [B, KV, nb*P, hd]
    view (sentinel entries clamp to page N-1; callers mask those slots)."""
    N = pool.shape[0]
    g = pool[jnp.minimum(table, N - 1)]          # [B, nb, KV, P, hd]
    B, nb, KV, P, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, KV, nb * P, hd)


def _gather_scale_pool(spool, table):
    """[N, KV, 8, P] scale pool + [B, nb] table → [B, KV, 8, nb*P] view."""
    N = spool.shape[0]
    g = spool[jnp.minimum(table, N - 1)]         # [B, nb, KV, 8, P]
    B, nb, KV, e, P = g.shape
    return g.transpose(0, 2, 3, 1, 4).reshape(B, KV, e, nb * P)


def reference_paged_decode_attention(q, k_pool, v_pool, table, start, filled):
    """XLA oracle for `paged_decode_attention`: gather pages to a contiguous
    per-row view, then the exact reference. q: [B, H, hd]; pools:
    [N, KV, P, hd]; table: [B, nb] int32."""
    return reference_decode_attention(
        q, _gather_pool(k_pool, table), _gather_pool(v_pool, table),
        start, filled)


def reference_paged_decode_attention_q8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                                        table, start, filled):
    """int8 oracle: gather quant + scale pools, dequantize, exact reference."""
    return reference_decode_attention_q8(
        q, _gather_pool(kq_pool, table), _gather_scale_pool(ks_pool, table),
        _gather_pool(vq_pool, table), _gather_scale_pool(vs_pool, table),
        start, filled)


def reference_paged_decode_verify_attention(q, k_pool, v_pool, table, start,
                                            fill):
    """k-query (speculative verify) oracle over pages."""
    return reference_decode_verify_attention(
        q, _gather_pool(k_pool, table), _gather_pool(v_pool, table),
        start, fill)


def _paged_decode_q8_kernel(start_ref, filled_ref, table_ref, q_ref, kq_ref,
                            ks_ref, vq_ref, vs_ref, o_ref, acc_ref, m_ref,
                            l_ref, *, scale: float, block_k: int):
    del table_ref
    _decode_q8_kernel(start_ref, filled_ref, q_ref, kq_ref, ks_ref, vq_ref,
                      vs_ref, o_ref, acc_ref, m_ref, l_ref, scale=scale,
                      block_k=block_k)


def _paged_verify_kernel(start_ref, fill_ref, table_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                         block_k: int, Tq: int):
    del table_ref
    _verify_kernel(start_ref, fill_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, scale=scale, block_k=block_k, Tq=Tq)


def _paged_kv_index_map(num_pages, page_size, last_offset=-1):
    """Logical block → physical page index map for pool operands. The clamp
    chain: logical block clamps to the last valid block (revisit
    optimization, same as the contiguous kernels), then the table lookup
    clamps the sentinel `num_pages` to a real page (rows with released pages
    produce garbage that the caller discards — their writes were dropped and
    their outputs are masked).

    `last_offset`: the last readable slot relative to the prefetched bound —
    decode passes `filled` (one past the last slot, offset -1); verify
    passes `fill` (slot of candidate 0, offset Tq - 1)."""
    def kv_index_map(b, kv, j, start_ref, filled_ref, table_ref):
        first = start_ref[b] // page_size
        last = jnp.maximum((filled_ref[b] + last_offset) // page_size, 0)
        lb = jnp.minimum(first + j, last)
        page = jnp.minimum(table_ref[b, lb], num_pages - 1)
        return (page, kv, 0, 0)
    return kv_index_map


class PagedDecodePlan(NamedTuple):
    """What one decode step reads of the page pool, the same for every layer
    (`paged_decode_plan`): a flat list of work items over the LIVE rows' live
    blocks only, rows in order; an item is up to `paged_pages_per_item`
    consecutive blocks of one row."""
    row_off: jnp.ndarray    # [B + 1] items of row b are [row_off[b], row_off[b+1])
    item_row: jnp.ndarray   # [B * ceil(nb / C)]
    item_blk: jnp.ndarray   # same: the item's first logical block
    table: jnp.ndarray      # [B, nb] the block table (sentinel = num_pages)
    start: jnp.ndarray      # [B] first valid logical slot
    filled: jnp.ndarray     # [B] one past the last valid logical slot


# one work item's K (or V) pages fill at most this much of a buffer slot
_PAGED_ITEM_BYTES = 512 << 10
_PAGED_ITEM_PAGES = 4


def paged_pages_per_item(pool) -> int:
    """Pages one work item of `paged_decode_attention` covers, from the
    pool's geometry. An item's fixed cost (DMA issue and wait, the softmax's
    reductions, the branch on first/last) is paid once for all its pages, so
    small pages are taken several at a time (Qwen2.5: 64 KB a page, 4 an
    item); a page of many heads is an item by itself (OLMoE: 512 KB)."""
    _, _, KV, P, hd = pool.shape
    return max(1, min(_PAGED_ITEM_PAGES,
                      _PAGED_ITEM_BYTES // (KV * P * hd * pool.dtype.itemsize)))


def paged_item_counts(pages, pages_per_item: int) -> tuple:
    """`(items, short items)` of `paged_decode_plan`'s work list, counted
    on the HOST for rows that read `pages` blocks each (an integer array of
    any shape; 0: a row without work): a row's blocks cut into items of
    `pages_per_item`, its last one short where they do not fill it."""
    pages = np.asarray(pages)
    return (int((-(-pages // pages_per_item)).sum()),
            int((pages % pages_per_item != 0).sum()))


# items one step of the kernel's loop folds where an item is small
_PAGED_STEP_ITEMS = 4


def _paged_items_per_step(pool, pages_per_item: int) -> int:
    """Work items one step of `_paged_decode_kernel`'s loop folds, from the
    pool's geometry. An item's fold is a chain of dependent steps (scores,
    max, exp, sum, PV) whose latency hardly follows its width, and alone in
    its step it runs beside nothing: the copies' issue and waits sit in
    branches around it. An item of 1 MB of K and V hides that under its
    copies; an item of half that or less (Qwen2.5's 2 KV heads) does not
    when it is short, so such items are folded several a step, their chains
    in one block where the compiler runs them side by side
    (docs/PAGED_CACHE.md "The read's cost" has the chip's numbers)."""
    _, _, KV, P, hd = pool.shape
    item = pages_per_item * KV * P * hd * pool.dtype.itemsize
    return _PAGED_STEP_ITEMS if 2 * item <= _PAGED_ITEM_BYTES else 1


def paged_decode_plan(table, start, filled, *, page_size: int, num_pages: int,
                      pages_per_item: int, live=None) -> PagedDecodePlan:
    """Work list of `paged_decode_attention` for one decode step. A row
    contributes its blocks `[start // P, (filled - 1) // P]`, cut into items
    of `pages_per_item`, or nothing: a row with an empty range, a row whose
    last such block is the table's sentinel (released: it holds no pages)
    and a row the caller marks not `live` (its output is discarded anyway)
    cost the kernel no work and read zero. table: [B, nb] int32;
    start/filled: [B]; live: [B] bool or None."""
    B, nb = table.shape
    C = pages_per_item
    start, filled = start.astype(jnp.int32), filled.astype(jnp.int32)
    first = start // page_size
    last = jnp.clip((filled - 1) // page_size, 0, nb - 1)
    has = (filled > start) & (
        jnp.take_along_axis(table, last[:, None], axis=1)[:, 0] < num_pages)
    if live is not None:
        has = has & live
    n = jnp.where(has, (last - first) // C + 1, 0)
    row_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(n, dtype=jnp.int32)])
    i = jnp.arange(B * pl.cdiv(nb, C), dtype=jnp.int32)
    # rows whose items end at or before i; items past the last are never read
    row = jnp.minimum(
        jnp.sum(i[:, None] >= row_off[None, 1:], axis=1, dtype=jnp.int32),
        B - 1)
    blk = jnp.clip(first[row] + (i - row_off[row]) * C, 0, nb - 1)
    return PagedDecodePlan(row_off, row, blk, table.astype(jnp.int32), start,
                           filled)


def _paged_item_fold(q, k, v, valid, state, scale: float):
    """Fold one work item into its row's online softmax, every KV head at
    once: ONE batched product for the scores, one max / exp / sum over the
    whole block, one batched product with V. Head by head (PR 28's loop) the
    same arithmetic was `KV` dependent chains of product, reduce, exp,
    reduce, product that the copies waited beside: 2.33 µs an item at
    Trinity's 8 heads against 1.39 for its copies; at once it is 0.49, under
    them (docs/PAGED_CACHE.md "The read's cost"). q: [KV, Gp, hd]; k, v:
    [KV, S, hd], the item's S slots; valid: [Gp, S]; state: (m, l
    [KV, Gp, 1], acc [KV, Gp, hd]) float32. Returns the new state."""
    m_prev, l_prev, acc_prev = state
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale                                                # [KV, Gp, S]
    s = jnp.where(valid[None], s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    # probabilities enter the PV product in the cache's dtype, as in the
    # plain path (`gqa_attention`); the sums stay float32
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _paged_decode_kernel(layer_ref, off_ref, row_ref, blk_ref, table_ref,
                         start_ref, filled_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sem, *, scale: float, n_rows: int,
                         step_items: int):
    """One tile of rows: walk the tile's work items, `step_items` a step of
    the loop. An item is up to C consecutive pages of one row, each page one
    DMA for ALL kv heads ([KV, P, hd] is contiguous in the pool), fetched
    from the stack in HBM into a ring of buffer slots, as many steps' items
    ahead as there are slots to spare, while the items before them are
    folded (`_paged_item_fold`); the online softmax's state of the row in
    hand rides the loop. A step's folds stand in ONE block, no branch
    between them, so that one item's chain runs beside the next one's; the
    rows that end in the step are written after it."""
    tile_rows, KV, Gp, _ = q_ref.shape
    slots, _, C, P, hd = kbuf.shape
    U = step_items
    ahead = slots // U - 1              # steps whose copies are in flight
    num_pages, nb = k_hbm.shape[1], table_ref.shape[1]
    r0 = pl.program_id(0) * tile_rows
    lo = off_ref[r0]
    hi = off_ref[jnp.minimum(r0 + tile_rows, n_rows)]
    layer = layer_ref[0]

    def pages(i, act):
        """Start or wait for the copies of item i's pages into its slot."""
        slot, row, blk = i % slots, row_ref[i], blk_ref[i]
        n = (filled_ref[row] - 1) // P - blk + 1
        for c in range(C):
            @pl.when(c < n)
            def _page():
                page = jnp.minimum(
                    table_ref[row, jnp.minimum(blk + c, nb - 1)], num_pages - 1)
                for j, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    act(pltpu.make_async_copy(
                        pool.at[layer, page], buf.at[slot, :, c],
                        sem.at[j, slot]))

    def step_pages(first, act):
        """The same for the items of the step that starts at item `first`."""
        for u in range(U):
            @pl.when(first + u < hi)
            def _item():
                pages(first + u, act)

    # rows without items (dead, released, padding) read zero; a page an item
    # does not fetch is masked, so what the V buffer holds there must be finite
    o_ref[...] = jnp.zeros_like(o_ref)
    if C > 1:
        vbuf[...] = jnp.zeros_like(vbuf)

    for d in range(ahead):
        step_pages(lo + d * U, lambda copy: copy.start())

    def step(j, state):
        first = lo + j * U
        # before the wait for this step's items, into the slots the step
        # before has left
        step_pages(first + ahead * U, lambda copy: copy.start())
        step_pages(first, lambda copy: copy.wait())
        ended = []
        for u in range(U):
            # past the tile's last item: that item again, written nowhere
            i = jnp.minimum(first + u, hi - 1)
            row, blk, slot = row_ref[i], blk_ref[i], i % slots
            start, filled = start_ref[row], filled_ref[row]
            # a row's first item starts its softmax anew
            state = tuple(jnp.where(blk == start // P, fresh, x)
                          for fresh, x in zip((NEG_INF, 0.0, 0.0), state))
            pos = blk * P + jax.lax.broadcasted_iota(jnp.int32, (Gp, C * P), 1)
            state = _paged_item_fold(
                q_ref[row - r0], kbuf[slot].reshape(KV, C * P, hd),
                vbuf[slot].reshape(KV, C * P, hd),
                (pos >= start) & (pos < filled), state, scale)
            ended.append((row - r0, state, (first + u < hi)
                          & (blk + C > (filled - 1) // P)))
        for r, (_, l, acc), last in ended:
            @pl.when(last)
            def _finalize():
                o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        return state

    jax.lax.fori_loop(0, pl.cdiv(hi - lo, U), step, (
        jnp.full((KV, Gp, 1), NEG_INF, jnp.float32),
        jnp.zeros((KV, Gp, 1), jnp.float32),
        jnp.zeros((KV, Gp, hd), jnp.float32)))


# a tile of rows keeps its queries and outputs in VMEM: at most this many
# bytes each (Qwen2.5's 64 serving rows are one tile of 0.5 MB)
_PAGED_TILE_BYTES = 1 << 20


def paged_decode_attention(
    q: jnp.ndarray,        # [B, H, hd], the single decode position
    k_pool: jnp.ndarray,   # [L, N, KV, P, hd], the WHOLE stacked page pool
    v_pool: jnp.ndarray,   # [L, N, KV, P, hd]
    layer,                 # scalar int32: which layer of the stack
    plan: PagedDecodePlan,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-token decode attention that reads K and V pages from the
    stacked pool in place. The stacks stay in HBM as they are (a Pallas
    operand is a buffer: a layer's slab handed over would be copied out of
    the stack per layer per step) and the layer rides as a scalar; the
    kernel's cost follows `plan`'s items, live rows x live pages, not
    `B x KV x n_blocks`. Mask per row `[start, filled)`, float32 scores and
    softmax state. Rows without items read zero. `plan` is made with
    `paged_pages_per_item(k_pool)`. Returns [B, H, hd]."""
    B, H, hd = q.shape
    _, _, KV, P, _ = k_pool.shape
    C = paged_pages_per_item(k_pool)
    G = H // KV
    sub = 32 // q.dtype.itemsize          # sublanes of one tile of this dtype
    Gp = sub * pl.cdiv(G, sub)
    tile_rows = max(1, min(B, _PAGED_TILE_BYTES
                           // (KV * Gp * hd * q.dtype.itemsize)))
    n_tiles = pl.cdiv(B, tile_rows)

    qg = jnp.pad(q.reshape(B, KV, G, hd),
                 [(0, n_tiles * tile_rows - B), (0, 0), (0, Gp - G), (0, 0)])
    U = _paged_items_per_step(k_pool, C)
    # two steps of one item ahead (at items of 0.5 MB one item ahead left
    # the copies exposed), one step of several
    slots = 3 if U == 1 else 2 * U
    kernel = functools.partial(_paged_decode_kernel, scale=1.0 / (hd ** 0.5),
                               n_rows=B, step_items=U)
    rows_spec = pl.BlockSpec((tile_rows, KV, Gp, hd),
                             lambda t, *_: (t, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_tiles,),
        in_specs=[rows_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows_spec,
        scratch_shapes=[
            pltpu.VMEM((slots, KV, C, P, hd), k_pool.dtype),
            pltpu.VMEM((slots, KV, C, P, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, slots)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *plan, qg, k_pool, v_pool)
    return out[:B, :, :G, :].reshape(B, H, hd)


def paged_decode_attention_q8(
    q: jnp.ndarray,        # [B, H, hd]
    kq_pool: jnp.ndarray,  # [N, KV, P, hd] int8
    ks_pool: jnp.ndarray,  # [N, KV, 8, P] bf16 sublane-expanded scales
    vq_pool: jnp.ndarray,  # [N, KV, P, hd] int8
    vs_pool: jnp.ndarray,  # [N, KV, 8, P] bf16
    table: jnp.ndarray,    # [B, nb] int32
    start: jnp.ndarray,    # [B] int32
    filled: jnp.ndarray,   # [B] int32
    interpret: bool | None = None,
) -> jnp.ndarray:
    """int8-pool variant of `paged_decode_attention` (same folded-scale math
    as `decode_attention_q8`). Returns [B, H, hd]."""
    B, H, hd = q.shape
    N, KV, P, _ = kq_pool.shape
    nb = table.shape[1]
    G = H // KV
    Gp = max(8, G)

    qg = q.reshape(B, KV, G, hd)
    if Gp != G:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, Gp - G), (0, 0)])

    kernel = functools.partial(
        _paged_decode_q8_kernel, scale=1.0 / (hd ** 0.5), block_k=P
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, hd),
                         lambda b, kv, j, s, f, t: (b, kv, 0, 0)),
            # the scale block (1, 1, 8, P) shares the kv index map — both
            # resolve to (page, kv, 0, 0)
            pl.BlockSpec((1, 1, P, hd), _paged_kv_index_map(N, P)),
            pl.BlockSpec((1, 1, 8, P), _paged_kv_index_map(N, P)),
            pl.BlockSpec((1, 1, P, hd), _paged_kv_index_map(N, P)),
            pl.BlockSpec((1, 1, 8, P), _paged_kv_index_map(N, P)),
        ],
        out_specs=pl.BlockSpec((1, 1, Gp, hd),
                               lambda b, kv, j, s, f, t: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Gp, hd), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, hd), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), filled.astype(jnp.int32),
      table.astype(jnp.int32), qg, kq_pool, ks_pool, vq_pool, vs_pool)
    return out[:, :, :G, :].reshape(B, H, hd)


def paged_decode_verify_attention(
    q: jnp.ndarray,       # [B, H, Tq, hd] — k+1 candidate positions
    k_pool: jnp.ndarray,  # [N, KV, P, hd] (candidate KV already written)
    v_pool: jnp.ndarray,  # [N, KV, P, hd]
    table: jnp.ndarray,   # [B, nb] int32
    start: jnp.ndarray,   # [B] int32
    fill: jnp.ndarray,    # [B] int32: slot of candidate 0
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged k-query verify attention — `decode_verify_attention` with the
    kv stream routed through the block table. The grid covers logical blocks
    up to (fill + Tq - 1)//P so candidate writes straddling a page boundary
    are both visited. Returns [B, H, Tq, hd]."""
    B, H, Tq, hd = q.shape
    N, KV, P, _ = k_pool.shape
    nb = table.shape[1]
    G = H // KV
    R = G * Tq
    Rp = 8 * pl.cdiv(R, 8)

    qg = q.reshape(B, KV, G, Tq, hd).reshape(B, KV, R, hd)
    if Rp != R:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, Rp - R), (0, 0)])

    kernel = functools.partial(
        _paged_verify_kernel, scale=1.0 / (hd ** 0.5), block_k=P, Tq=Tq
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, Rp, hd),
                         lambda b, kv, j, s, f, t: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, P, hd), _paged_kv_index_map(N, P, last_offset=Tq - 1)),
            pl.BlockSpec((1, 1, P, hd), _paged_kv_index_map(N, P, last_offset=Tq - 1)),
        ],
        out_specs=pl.BlockSpec((1, 1, Rp, hd),
                               lambda b, kv, j, s, f, t: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Rp, hd), jnp.float32),
            pltpu.VMEM((Rp, 128), jnp.float32),
            pltpu.VMEM((Rp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Rp, hd), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), fill.astype(jnp.int32),
      table.astype(jnp.int32), qg, k_pool, v_pool)
    return out[:, :, :R, :].reshape(B, KV, G, Tq, hd).reshape(B, H, Tq, hd)
