"""Causal flash attention as a Pallas TPU kernel (+ XLA reference path).

TPU-native replacement for the reference's FlashAttention-2 dependency
(`attn_implementation="flash_attention_2"`, `/root/reference/GRPO/
grpo.py:219,223` — CUDA, SURVEY.md §2.2). Design:

- **Forward**: online-softmax blockwise kernel. Grid (B, H, q_blocks,
  kv_blocks); the kv axis iterates fastest, carrying running max / sum /
  accumulator in VMEM scratch across grid steps. Never materializes the
  [T, T] score matrix, streams K/V HBM→VMEM block by block. GQA is free: the
  K/V BlockSpec index maps query head h to kv head h // group, so grouped
  heads re-read the same KV block instead of materializing repeats.
- **Causal skip**: kv blocks entirely above the diagonal skip their compute
  under `pl.when` (half the FLOPs at long T).
- **Backward**: fused Pallas kernels (FlashAttention-2 style). The forward
  emits per-row LSE as a residual; `_dq_kernel` accumulates dQ over kv
  blocks, `_dkv_kernel` accumulates dK/dV over (group, q-block) — the GQA
  group sum happens in-scratch, so gradients come out already reduced to
  [B, KV, T, d]. No [T, T] probability matrix is ever materialized in either
  direction. `NANORLHF_FLASH_BWD=xla` switches the backward to an XLA
  reference recompute for hardware triage (values validated; anything else
  than pallas/xla raises).

Padding contract matches the model's mask recipe: `key_valid` is the [B, T]
attention mask; query rows that are padding produce garbage rows which the
caller's downstream masking discards (identical to the XLA path).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = -1e30
# TPU VREG tile: small per-row operands (mask, lse, delta) are carried
# sublane-/lane-expanded so every BlockSpec satisfies Mosaic's (8, 128)
# last-two-dims tiling rule on real hardware (interpret mode never checks).
_SUBLANES = 8
_LANES = 128


def _interpret_default() -> bool:
    """Interpret mode: forced via env, or automatic off-TPU (tests/CPU)."""
    env = os.environ.get("NANORLHF_PALLAS_INTERPRET")
    if env is not None:
        return env == "1"
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# XLA reference (also the backward path)
# ---------------------------------------------------------------------------


def reference_attention(q, k, v, key_valid, causal: bool = True):
    """Plain-jnp GQA attention. q: [B, H, T, d]; k/v: [B, KV, T, d];
    key_valid: [B, T] bool. Returns [B, H, T, d]."""
    B, H, T, d = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, T, d)
    s = jnp.einsum("bkgqh,bkth->bkgqt", qg, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d))
    mask = key_valid[:, None, None, None, :]
    if causal:
        causal_m = jnp.tril(jnp.ones((T, T), bool))[None, None, None, :, :]
        mask = mask & causal_m
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,bkth->bkgqh", p, v)
    return out.reshape(B, H, T, d)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref,
                  acc_ref, m_ref, l_ref,
                  *, scale: float, block_q: int, block_k: int, causal: bool):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)
    n_kv = pl.num_programs(3)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [Bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [Bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [Bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [Bq, Bk]
        key_ok = mask_ref[0, :1, :] > 0                 # [1, Bk]
        s = jnp.where(key_ok, s, NEG_INF)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[:, :1]                           # [Bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [Bq, Bk]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip kv blocks entirely above the diagonal (pure future): half the
        # FLOPs at long T
        pl.when(kv_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)            # fully-masked rows → 0/1
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)
        # lse = m + log(l): the backward residual (P = exp(S − lse)).
        # Stored lane-expanded [Bq, LANES] — Mosaic tiling requires the last
        # two block dims be (8k, 128k)-aligned or span the array dim, so a
        # [Bq]-vector output is not liftable on real TPU hardware.
        lse_ref[0, 0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l), lse_ref.shape[2:]
        )


def _flash_forward(q, k, v, key_valid, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    B, H, T, d = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = 1.0 / (d ** 0.5)
    n_q = pl.cdiv(T, block_q)
    n_kv = pl.cdiv(T, block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal,
    )
    # Mosaic tiling: the last two block dims must be (8, 128)-multiples or
    # span the array dim. A [B, T] mask with (1, block_k) blocks violates the
    # sublane rule, so the mask rides sublane-broadcast as [B, 8, T] (the
    # same recipe as jax's reference TPU flash kernel's segment ids), and lse
    # rides lane-expanded as [B, H, T, LANES].
    mask8 = jnp.broadcast_to(
        key_valid.astype(jnp.int32)[:, None, :], (B, _SUBLANES, T)
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h // G, j, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h // G, j, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, _SUBLANES, block_k), lambda b, h, i, j: (b, 0, j),
                         memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, T, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask8)
    # lse stays lane-expanded [B, H, T, LANES]: it is only ever a backward
    # residual, and the backward kernels read it in this layout — slicing to
    # [B, H, T] here would just force a re-broadcast (a 128x HBM round trip)
    # before the bwd pallas_calls.
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style)
#
# With P = exp(S − lse), D_i = Σ_j dO_ij · O_ij:
#   dV = Pᵀ @ dO        dP = dO @ Vᵀ        dS = P ⊙ (dP − D)
#   dQ = dS @ K · scale          dK = dSᵀ @ Q · scale
# Two kernels: dq iterates kv blocks per q block; dk/dv iterate q blocks per
# kv block (emitted per query head, summed over GQA groups outside).
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, out_ref,
               dq_out_ref, dq_acc_ref,
               *, scale: float, block_q: int, block_k: int, causal: bool):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)
    n_kv = pl.num_programs(3)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                       # [Bq, 1]
        # D_i = Σ_d dO·O, recomputed per block ([Bq, d] elementwise+reduce) —
        # cheaper than streaming a lane-expanded [B, H, T, 128] HBM array
        delta = jnp.sum(
            do * out_ref[0, 0].astype(jnp.float32), axis=-1, keepdims=True
        )                                                # [Bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        key_ok = mask_ref[0, :1, :] > 0                  # [1, Bk]
        s = jnp.where(key_ok, s, NEG_INF)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                             # [Bq, Bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc_ref[:] = dq_acc_ref[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    if causal:
        pl.when(kv_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        dq_out_ref[0, 0] = dq_acc_ref[:].astype(dq_out_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, out_ref,
                dk_out_ref, dv_out_ref, dk_acc_ref, dv_acc_ref,
                *, scale: float, block_q: int, block_k: int, causal: bool):
    # grid (B, KV, n_kv, G, n_q): q blocks fastest, then the GQA group — the
    # group sum accumulates in scratch, emitting dk/dv already [B, KV, T, d]
    q_idx = pl.program_id(4)
    g_idx = pl.program_id(3)
    kv_idx = pl.program_id(2)
    n_q = pl.num_programs(4)
    n_g = pl.num_programs(3)

    @pl.when((q_idx == 0) & (g_idx == 0))
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    q_start = q_idx * block_q
    kv_start = kv_idx * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = jnp.sum(                                 # see _dq_kernel
            do * out_ref[0, 0].astype(jnp.float32), axis=-1, keepdims=True
        )

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        key_ok = mask_ref[0, :1, :] > 0
        s = jnp.where(key_ok, s, NEG_INF)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        # dV += Pᵀ @ dO
        dv_acc_ref[:] = dv_acc_ref[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dK += dSᵀ @ Q · scale
        dk_acc_ref[:] = dk_acc_ref[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    if causal:
        pl.when(kv_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when((q_idx == n_q - 1) & (g_idx == n_g - 1))
    def _finalize():
        dk_out_ref[0, 0] = dk_acc_ref[:].astype(dk_out_ref.dtype)
        dv_out_ref[0, 0] = dv_acc_ref[:].astype(dv_out_ref.dtype)


def _flash_backward(q, k, v, key_valid, out, lse, g, causal, block_q, block_k,
                    interpret):
    B, H, T, d = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = 1.0 / (d ** 0.5)
    n_q = pl.cdiv(T, block_q)
    n_kv = pl.cdiv(T, block_k)
    # sublane-broadcast mask / lane-expanded lse: see _flash_forward (lse
    # arrives already lane-expanded; delta is recomputed per block in-kernel
    # from `out`, so no lane-expanded delta array exists)
    mask8 = jnp.broadcast_to(
        key_valid.astype(jnp.int32)[:, None, :], (B, _SUBLANES, T)
    )

    common_q_specs = dict(
        q=pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0),
                       memory_space=_VMEM),
        k=pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h // G, j, 0),
                       memory_space=_VMEM),
        v=pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h // G, j, 0),
                       memory_space=_VMEM),
        mask=pl.BlockSpec((1, _SUBLANES, block_k), lambda b, h, i, j: (b, 0, j),
                          memory_space=_VMEM),
        do=pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=_VMEM),
        lse=pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=_VMEM),
    )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(B, H, n_q, n_kv),
        in_specs=[common_q_specs["q"], common_q_specs["k"], common_q_specs["v"],
                  common_q_specs["mask"], common_q_specs["do"],
                  common_q_specs["lse"], common_q_specs["do"]],
        out_specs=common_q_specs["q"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, mask8, g, lse, out)

    # dk/dv: kv head and block outer; (group, q block) inner with q fastest.
    # Scratch accumulates across BOTH inner axes, so the GQA group sum happens
    # in-kernel and the outputs are already reduced to [B, KV, T, d] — no
    # G x-sized per-query-head gradient buffers in HBM.
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(B, KV, n_kv, G, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, kv, j, gq, i: (b, kv * G + gq, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, kv, j, gq, i: (b, kv, j, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, kv, j, gq, i: (b, kv, j, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b, kv, j, gq, i: (b, 0, j),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, kv, j, gq, i: (b, kv * G + gq, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, kv, j, gq, i: (b, kv * G + gq, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, kv, j, gq, i: (b, kv * G + gq, i, 0),
                         memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b, kv, j, gq, i: (b, kv, j, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, block_k, d), lambda b, kv, j, gq, i: (b, kv, j, 0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, mask8, g, lse, out)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: custom_vjp + shape handling
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention_core(q, k, v, key_valid, causal, block_q, block_k):
    out, _ = _flash_forward(q, k, v, key_valid, causal, block_q, block_k,
                            interpret=_interpret_default())
    return out


def _core_fwd(q, k, v, key_valid, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, key_valid, causal, block_q, block_k,
                              interpret=_interpret_default())
    return out, (q, k, v, key_valid, out, lse)


def _core_bwd(causal, block_q, block_k, residuals, g):
    q, k, v, key_valid, out, lse = residuals
    bwd_impl = os.environ.get("NANORLHF_FLASH_BWD", "pallas")
    if bwd_impl not in ("pallas", "xla"):
        raise ValueError(
            f"NANORLHF_FLASH_BWD={bwd_impl!r}: must be 'pallas' or 'xla'"
        )
    if bwd_impl == "xla":
        # triage escape hatch: recompute through the XLA reference
        _, vjp = jax.vjp(
            lambda q_, k_, v_: reference_attention(q_, k_, v_, key_valid, causal),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
    else:
        dq, dk, dv = _flash_backward(
            q, k, v, key_valid, out, lse, g, causal, block_q, block_k,
            interpret=_interpret_default(),
        )
    return dq, dk, dv, None


_flash_attention_core.defvjp(_core_fwd, _core_bwd)


def block_and_pad(block_q: int, block_k: int, T: int) -> tuple[int, int]:
    """The shared pad-up recipe (used here and by the flash ring): blocks
    must be 128-lane multiples, never larger than the padded sequence, and
    T pads UP to a block multiple — a non-aligned T is rejected by Mosaic,
    and an unpadded partial last block would read out-of-bounds keys that
    key_valid does not neutralize (silent wrong logprobs on silicon;
    interpret mode zero-fills and cannot catch it)."""
    block = max(block_q, block_k)
    block = max(128, (block // 128) * 128)
    block = min(block, 128 * int(pl.cdiv(T, 128)))
    T_pad = int(pl.cdiv(T, block) * block)
    return block, T_pad


def flash_attention(
    q: jnp.ndarray,          # [B, H, T, d]
    k: jnp.ndarray,          # [B, KV, T, d]
    v: jnp.ndarray,          # [B, KV, T, d]
    key_valid: jnp.ndarray,  # [B, T] bool
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """Blockwise flash attention; pads T up to a block multiple internally.

    Blocks are always multiples of 128 (lane width): a non-aligned T (e.g.
    100) pads UP to 128 rather than shrinking the block to a lane-unaligned
    size that Mosaic tiling may reject on real hardware (ADVICE r1). The
    key_valid padding neutralizes the extra columns; extra query rows are
    garbage the caller's masking discards.
    """
    B, H, T, d = q.shape
    block, T_pad = block_and_pad(block_q, block_k, T)
    block_q = block_k = block
    if T_pad != T:
        pad = [(0, 0), (0, 0), (0, T_pad - T), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        key_valid = jnp.pad(key_valid, [(0, 0), (0, T_pad - T)])
    out = _flash_attention_core(q, k, v, key_valid, causal, block_q, block_k)
    return out[:, :, :T, :]
