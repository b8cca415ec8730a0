"""Sparse-expert MLP (OLMoE): router, token→expert dispatch, grouped expert
matmul, weighted combine. docs/MOE.md has the equations and the shapes.

    p = softmax_f32(h W_r)               over E experts
    (w, e) = top_k(p)                    w /= Σ w only if norm_topk_prob
    y = Σ_j w_j · W_down[e_j]( silu(h W_gate[e_j]) ⊙ (h W_up[e_j]) )

Dropless: every token reaches all k of its experts, whatever the load. The
N x k assignments are sorted by expert, the token rows gathered in that
order, and the three expert matmuls run as grouped matmuls over the sorted
rows (`jax.lax.ragged_dot`, `group_sizes` = assignments per expert); the
outputs go back to token order and are summed with their router weights.

Two implementations of the grouped matmul, resolved under the model's
`attention_impl` like the attention kernels (`core/model.use_expert_kernel`):

- **plain** (`"xla"`, every backend but the TPU, and any multi-device mesh):
  `jax.lax.ragged_dot`. The v5e compiler lowers it to its own grouped-matmul
  custom call (`ragged-dot-none`: 2·M·D·F operations, not a product over all
  experts), but with a row tile of 512: at decode's 8 rows a group every
  group pays a 512-row tile's products, and a call took 0.85 ms where its
  bytes need 0.33 (my chip run, PR 27: 2.6 x the floor, 5.2 s of a 7.9 s
  update).
- **kernel** (`"auto"` on a TPU, `"pallas"`): `megablox.gmm`, the grouped
  matmul Pallas kernel that ships with jax: row tile 128 at decode (0.41 ms
  a call), faster than `ragged_dot` at scoring's shapes too (1.1 against
  1.8 ms at 24,576 rows), empty groups skipped, and the transposed kernel
  read in place for the backward
  (`transpose_rhs`), which is all the adapter's gradient needs: the expert
  kernels and the router are frozen under LoRA (core/lora.py).

A custom call's operands are physical buffers (both implementations are
custom calls on the TPU), so a layer's `[E, D, F]` slice of the stacked
kernels `[L, E, D, F]` would be COPIED out of the stack before the call
(0.27 GB a kernel at OLMoE's widths: three times the bytes a weight-bound
decode step has to move). With `layer=` the op therefore takes the whole
stack, a free reshape to `[L·E, D, F]`, with the layer's group sizes at
`layer·E` and zeros elsewhere: empty groups take no grid step, and nothing
is copied. The cached forwards, which are never differentiated, always do
that; the uncached forward does it with the kernel, whose backward reads
the same buffer, and keeps the layer's slice with `ragged_dot`, whose
transpose would relay out the WHOLE stack per kernel.

The four parts run under `jax.named_scope("moe.router" | "moe.dispatch" |
"moe.experts" | "moe.combine")`, so the device trace attributes expert time
by name after any refactor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _gmm_tiling(m: int, k: int, n: int, groups: int) -> tuple:
    """megablox tiles (rows, contracted, out) by the rows a group has in the
    mean, from a sweep on the v5e at OLMoE's widths (PERF.md, PR 27). Few
    rows a group are weight-bound: a small row tile (a group pays whole
    tiles) and a whole kernel's columns in flight, 0.41 ms a call at 8 rows
    a group against a byte floor of 0.33. Many rows are compute-bound: a
    group that straddles row tiles pays for both, so the tile grows with the
    group. The contracted width is never split at these widths."""
    per_group = m // max(groups, 1)
    tm = 128 if per_group <= 64 else 256 if per_group <= 768 else 512
    return tm, min(k, 2048), min(n, 1024 if tm < 512 else 512)


def _grouped_matmul(rows, w, group_sizes, kernel: bool, experts: int):
    """rows [M, K] sorted by group, w [G, K, N], group_sizes [G] -> [M, N]:
    each group's rows times its own matrix; `experts` of the G groups (one
    layer's) have rows."""
    if not kernel:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from nanorlhf_tpu.ops.attention import _interpret_default

    M, K = rows.shape
    tiling = _gmm_tiling(M, K, w.shape[2], experts)
    pad = -M % tiling[0]    # rows past the last group are computed by no one
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = megablox.gmm(rows, w, group_sizes, rows.dtype, tiling, None, None,
                       False, _interpret_default())
    return out[:M] if pad else out


def moe_mlp(h, router, gate, up, down, top_k: int, norm_topk_prob: bool,
            layer=None, kernel: bool = False):
    """h [..., D]; router [D, E]; gate, up [E, D, F]; down [E, F, D]. With
    `layer` (a traced index) the three expert kernels are the stacks of
    every layer, `[L, E, ...]`, addressed in place; `kernel` picks the
    grouped matmul (module docstring).

    Returns `(y [..., D], aux)`. `aux` is what the counters are made of
    (`router_stats`): `experts` [..., k] int32, the chosen experts of every
    token; `entropy` [...] f32, the router distribution's entropy in nats;
    `dropped` [] int32, assignments no expert computed (0: there is no
    capacity; the guard that stays 0 when someone adds one). Unused, XLA
    removes them."""
    lead, D = h.shape[:-1], h.shape[-1]
    E = router.shape[-1]
    x = h.reshape(-1, D)
    N = x.shape[0]

    with jax.named_scope("moe.router"):
        # bf16 operands, float32 products and sums: the float32 router
        logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)          # [N, k]
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        entropy = -jnp.sum(probs * jnp.log(jnp.maximum(probs, 1e-30)), axis=-1)

    with jax.named_scope("moe.dispatch"):
        flat = experts.reshape(N * top_k)
        order = jnp.argsort(flat, stable=True)       # assignment ids, by expert
        rows = x[order // top_k]                     # [N*k, D], grouped
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        computed = jnp.sum(group_sizes)
        if layer is not None:
            gate, up, down = (w.reshape((-1,) + w.shape[2:])
                              for w in (gate, up, down))
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((gate.shape[0],), jnp.int32), group_sizes,
                (layer * E,))

    with jax.named_scope("moe.experts"):
        g = _grouped_matmul(rows, gate, group_sizes, kernel, E)
        u = _grouped_matmul(rows, up, group_sizes, kernel, E)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(rows.dtype) * u
        out = _grouped_matmul(act, down, group_sizes, kernel, E)  # [N*k, D]

    with jax.named_scope("moe.combine"):
        # back to token order (the inverse permutation is a gather), then the
        # weighted sum over each token's k outputs in float32
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * top_k, dtype=order.dtype))
        per_token = out[inverse].reshape(N, top_k, D).astype(jnp.float32)
        y = jnp.sum(per_token * weights[..., None], axis=1).astype(h.dtype)

    aux = {
        "experts": experts.reshape(lead + (top_k,)).astype(jnp.int32),
        "entropy": entropy.reshape(lead),
        "dropped": jnp.int32(N * top_k) - computed,
    }
    return y.reshape(h.shape), aux


def router_stats(aux, token_mask, num_experts: int):
    """Per-row sums of the layer scan's stacked `aux` (leading layer axis)
    over the real tokens of each row, small enough to leave the device with
    the logprobs: `load` [B, L, E] assignments per expert, `entropy` [B]
    (summed over layers and tokens), `tokens` [B], `dropped` []. Per row, so
    a caller that padded its last chunk with repeated rows can leave them
    out (`moe_counters`)."""
    m = token_mask.astype(jnp.float32)                            # [B, T]
    # compare, weigh and reduce in one fusion: no [L, B, T, k, E] array
    chosen = aux["experts"][..., None] == jnp.arange(num_experts)
    load = jnp.sum(chosen * m[None, :, :, None, None], axis=(2, 3))
    return {"load": load.transpose(1, 0, 2),
            "entropy": jnp.einsum("lbt,bt->b", aux["entropy"], m),
            "tokens": jnp.sum(m, axis=1),
            "dropped": jnp.sum(aux["dropped"])}


def moe_counters(stats: list) -> dict:
    """The trainer's row from the scored chunks' `router_stats` (host side,
    numpy trees already sliced to their real rows): `moe/load_max_over_mean`
    = tokens of the fullest expert over the mean, the maximum over layers;
    `moe/router_entropy` = mean over tokens and layers, in nats;
    `moe/dropped_tokens`."""
    import numpy as np

    load = sum(np.asarray(s["load"], np.float64).sum(axis=0) for s in stats)
    tokens = sum(float(np.sum(s["tokens"])) for s in stats)
    entropy = sum(float(np.sum(s["entropy"])) for s in stats)
    mean = np.maximum(load.mean(axis=-1), 1e-9)                   # [L]
    return {
        "moe/load_max_over_mean": float(np.max(load.max(axis=-1) / mean)),
        "moe/router_entropy": entropy / max(tokens * load.shape[0], 1.0),
        "moe/dropped_tokens": float(sum(int(s["dropped"]) for s in stats)),
    }
