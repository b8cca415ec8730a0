"""Ring attention over an 8-device sequence axis vs single-device reference."""

import numpy as np
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from nanorlhf_tpu.ops.attention import reference_attention
from nanorlhf_tpu.parallel.ring_attention import ring_attention


def _run_ring(q, k, v, valid, causal, n_dev=8):
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("sp",))
    fn = shard_map(
        partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, "sp")),
        out_specs=P(None, None, "sp", None),
    )
    return jax.jit(fn)(q, k, v, valid)


def test_ring_matches_reference_causal(rng):
    B, H, KV, T, d = 2, 4, 2, 32, 8   # T sharded 8-way -> 4 tokens/device
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid = np.ones((B, T), bool)
    valid[0, :6] = False
    valid = jnp.asarray(valid)

    got = _run_ring(q, k, v, valid, causal=True)
    want = reference_attention(q, k, v, valid, causal=True)
    mask = np.asarray(valid)[:, None, :, None]
    np.testing.assert_allclose(
        np.asarray(got) * mask, np.asarray(want) * mask, rtol=2e-4, atol=2e-4
    )


def test_ring_matches_reference_non_causal(rng):
    B, H, KV, T, d = 1, 2, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid = jnp.ones((B, T), bool)
    got = _run_ring(q, k, v, valid, causal=False)
    want = reference_attention(q, k, v, valid, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_ring_gqa(rng):
    B, H, KV, T, d = 1, 8, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid = jnp.ones((B, T), bool)
    got = _run_ring(q, k, v, valid, causal=True)
    want = reference_attention(q, k, v, valid, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_ring_matches_einsum_ring(rng):
    """Forward-only flash ring (per-chunk Pallas flash + lse merge) vs the
    einsum ring and the single-device reference — causal, partial key mask,
    GQA. Interpret-mode Pallas on the CPU mesh; 2-way ring so each chunk
    spans multiple (clamped) blocks."""
    from nanorlhf_tpu.parallel.ring_attention import ring_attention_flash

    B, H, KV, T, d = 2, 4, 2, 256, 16      # 2-way ring -> 128 tokens/device
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid = jnp.asarray(np.arange(T)[None, :] < np.asarray([[T], [T - 60]]))

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    specs = dict(
        in_specs=(P(None, None, "sp", None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, "sp")),
        out_specs=P(None, None, "sp", None),
    )
    flash = jax.jit(shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True,
                block_q=64, block_k=64),
        mesh=mesh, check_vma=False, **specs,
    ))(q, k, v, valid)
    einsum = jax.jit(shard_map(
        partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh, **specs,
    ))(q, k, v, valid)
    ref = reference_attention(q, k, v, valid, causal=True)

    rows_valid = np.asarray(valid)
    for b in range(B):
        sel = rows_valid[b]
        np.testing.assert_allclose(
            np.asarray(flash)[b][:, sel], np.asarray(einsum)[b][:, sel],
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(flash)[b][:, sel], np.asarray(ref)[b][:, sel],
            rtol=2e-5, atol=2e-5,
        )


def test_flash_ring_backward_matches_einsum_and_reference(rng):
    """jax.grad through the flash ring (custom_vjp: per-chunk Pallas flash
    bwd with the GLOBAL lse, dk/dv riding the ring with their chunk) vs the
    einsum ring's autodiff and the single-device reference — causal,
    partial key mask, GQA, 2-way ring. The cotangent is zeroed on padding
    rows (the caller's masking contract)."""
    from nanorlhf_tpu.parallel.ring_attention import ring_attention_flash

    B, H, KV, T, d = 2, 4, 2, 256, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid_np = np.arange(T)[None, :] < np.asarray([[T], [T - 60]])
    valid = jnp.asarray(valid_np)
    w = jnp.asarray(
        rng.normal(size=(B, H, T, d)).astype(np.float32)
        * valid_np[:, None, :, None]
    )

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    specs = dict(
        in_specs=(P(None, None, "sp", None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, "sp")),
        out_specs=P(None, None, "sp", None),
    )
    flash_fn = shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True,
                block_q=64, block_k=64),
        mesh=mesh, check_vma=False, **specs,
    )
    einsum_fn = shard_map(
        partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh, **specs,
    )

    def loss(fn, q_, k_, v_):
        return (fn(q_, k_, v_, valid) * w).sum()

    g_flash = jax.jit(jax.grad(partial(loss, flash_fn), argnums=(0, 1, 2)))(
        q, k, v
    )
    g_einsum = jax.jit(jax.grad(partial(loss, einsum_fn), argnums=(0, 1, 2)))(
        q, k, v
    )
    g_ref = jax.grad(
        lambda q_, k_, v_: (reference_attention(q_, k_, v_, valid, True) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_einsum):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_flash_ring_backward_non_aligned_width(rng):
    """Backward through the pad-up path (T_local=192, not a 128-multiple):
    dq/dk/dv must slice the padding back off and match the reference."""
    from nanorlhf_tpu.parallel.ring_attention import ring_attention_flash

    B, H, KV, T, d = 1, 4, 2, 384, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid_np = np.arange(T)[None, :] < T - 50
    valid = jnp.asarray(valid_np)
    w = jnp.asarray(
        rng.normal(size=(B, H, T, d)).astype(np.float32)
        * valid_np[:, None, :, None]
    )

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    flash_fn = shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, None, "sp", None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, "sp")),
        out_specs=P(None, None, "sp", None),
    )
    g_flash = jax.jit(jax.grad(
        lambda q_, k_, v_: (flash_fn(q_, k_, v_, valid) * w).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g_ref = jax.grad(
        lambda q_, k_, v_: (reference_attention(q_, k_, v_, valid, True) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_flash_ring_non_aligned_width(rng):
    """T_local not a 128-multiple (384 global / 2-way ring = 192/shard):
    the pad-up recipe must kick in — Mosaic would reject the raw width on
    silicon, and an unpadded partial block would read out-of-bounds keys."""
    from nanorlhf_tpu.parallel.ring_attention import ring_attention_flash

    B, H, KV, T, d = 1, 4, 2, 384, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, KV, T, d)).astype(np.float32))
    valid = jnp.asarray(np.arange(T)[None, :] < T - 50)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    flash = jax.jit(shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, None, "sp", None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, "sp")),
        out_specs=P(None, None, "sp", None),
    ))(q, k, v, valid)
    ref = reference_attention(q, k, v, valid, causal=True)
    sel = np.asarray(valid)[0]
    np.testing.assert_allclose(
        np.asarray(flash)[0][:, sel], np.asarray(ref)[0][:, sel],
        rtol=2e-5, atol=2e-5,
    )
