"""Sparse-expert MLP (OLMoE, A.X-K1): router, token→expert dispatch, grouped
expert matmul, weighted combine. docs/MOE.md has the equations and the shapes.

    p = softmax_f32(h W_r)               over E experts  (A.X-K1: sigmoid)
    (w, e) = top_k(p)                    w /= Σ w only if norm_topk_prob
                                         w *= routed_scale  (A.X-K1: 2.5)
    y = Σ_j w_j · W_down[e_j]( silu(h W_gate[e_j]) ⊙ (h W_up[e_j]) )

LFM2 (docs/STATE.md) selects with a bias and weighs without it
(`select_bias`): `e = top_k(p + b)`, `w = p[e]`, renormalised with an epsilon
of 1e-6 under the sum (`norm_eps`).

SmallThinker (docs/SWA.md) differs in two places: the router reads another
state than the experts do (`router_h`: the layer's pre-attention normed
state), and the gate is `relu`, not `silu` (`activation`).

A.X-K1's shared expert, added to every token, is core/model.py's (`_mlp`,
span `moe.shared`): a dense SwiGLU, nothing of this module's.

**The chip's share** (`held=(count, offset)`): the program holds experts
`[offset, offset + count)` of a layer that an expert-parallel group shares.
The router keeps its full width and its k; an assignment to an absent expert
is not dispatched (its rows sort behind every held group, where no grouped
matmul reaches them) and adds nothing; the held ones are computed as ever.
The sum over the group's shares is the whole layer (tests/test_axk1.py).
Absent assignments are counted (`aux["absent"]`), apart from `dropped`.

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
    tk = min(k, 2048)
    if k % tk:      # A.X-K1's 7,168: the widest whole number of tiles
        tk = next((t for t in range(2048, 0, -128) if k % t == 0), tk)
    return tm, tk, min(n, 1024 if tm < 512 else 512)


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
            layer=None, kernel: bool = False, scoring: str = "softmax",
            routed_scale: float = 1.0, held=None, live=None,
            router_h=None, activation: str = "silu", select_bias=None,
            norm_eps: float = 0.0):
    """h [..., D]; router [D, E]; gate, up [G, D, F]; down [G, F, D], G = E
    or, with `held=(G, offset)`, the chip's share of the E. With
    `layer` (a traced index) the three expert kernels are the stacks of
    every layer, `[L, G, ...]`, addressed in place; `kernel` picks the
    grouped matmul (module docstring).

    Returns `(y [..., D], aux)`. `aux` is what the counters are made of
    (`router_stats`): `experts` [..., k] int32, the chosen experts of every
    token; `entropy` [...] f32, the router distribution's entropy in nats;
    `dropped` [] int32, assignments no expert computed (0: there is no
    capacity; the guard that stays 0 when someone adds one); with `held`
    also `absent` [] int32, assignments to experts another chip holds,
    `absent_by_token` [N] (for a caller that padded the tokens), and
    `reached` [] int32, the held experts that got any row: the routed kernels
    this call had to read. `live` [...] bool (with `held`: the serving step's
    rows) marks the tokens someone listens to; the others' assignments are
    left out like absent ones: a decode step runs every resident row, and
    the rows without a request otherwise reach held experts of their own,
    whose kernels every step then reads. Which ones changes with the seed's
    weights, and with it the step's time, and with that which requests
    decode beside a prefill chunk: six seeds gave `tpot_p95_ms` 23.6-23.8
    four times and 42.1 and 43.7 (the empty rows all fed the pad token), and
    3.6 % of spread with each repeating its last token, against 1.4 % with
    none of them dispatched (my chip runs, PR 31); a cell is admitted under
    1.75 %. A model that holds every expert passes `held=(E, 0)` with `live`
    for the same reason.
    Unused, XLA removes them.
    `router_h` [..., D], where given, is what the router reads instead of
    `h`; `activation` is the experts' gate, "silu" or "relu".
    `select_bias` [E] float32, where given, is added to the scores for the
    SELECTION only: the weights are the chosen experts' own scores
    (renormalised over `sum + norm_eps`), and `aux["bias_changed"]` [...]
    bool marks the tokens whose chosen set is not the top k of the scores
    alone (`moe/bias_changed_choice`)."""
    lead, D = h.shape[:-1], h.shape[-1]
    E = router.shape[-1]
    x = h.reshape(-1, D)
    N = x.shape[0]

    with jax.named_scope("moe.router"):
        # bf16 operands, float32 products and sums: the float32 router
        routed = x if router_h is None else router_h.reshape(-1, D)
        logits = jnp.dot(routed, router, preferred_element_type=jnp.float32)
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits.astype(jnp.float32))
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            scores = probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        bias_changed = None
        if select_bias is None:
            weights, experts = jax.lax.top_k(scores, top_k)     # [N, k]
        else:
            _, experts = jax.lax.top_k(
                scores + select_bias.astype(jnp.float32), top_k)
            weights = jnp.take_along_axis(scores, experts, axis=-1)
            # the unbiased top k, as a set: the k-th largest score's rank
            kth = jax.lax.top_k(scores, top_k)[0][:, -1:]
            bias_changed = jnp.any(weights < kth, axis=-1)
        if norm_topk_prob:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            weights = weights / (total + norm_eps if norm_eps else total)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        entropy = -jnp.sum(probs * jnp.log(jnp.maximum(probs, 1e-30)), axis=-1)

    with jax.named_scope("moe.dispatch"):
        flat = experts.reshape(N * top_k)
        G, absent, here = E, None, None
        if held is not None:
            # held experts count from 0; an absent one is group G, which
            # sorts last, has no size and no matrix
            G, offset = held
            flat = flat - offset
            kept = (flat >= 0) & (flat < G)
            if live is not None:
                kept = kept & jnp.repeat(live.reshape(N), top_k)
            flat = jnp.where(kept, flat, G)
            weights = jnp.where(flat.reshape(N, top_k) < G, weights, 0.0)
        order = jnp.argsort(flat, stable=True)       # assignment ids, by expert
        rows = x[order // top_k]                     # [N*k, D], grouped
        group_sizes = jnp.zeros((G,), jnp.int32).at[flat].add(1, mode="drop")
        computed = jnp.sum(group_sizes)
        if held is not None:
            reached = jnp.sum(group_sizes > 0, dtype=jnp.int32)
            here = (flat < G)[order]
            absent = jnp.int32(N * top_k) - jnp.sum(here, dtype=jnp.int32)
        if layer is not None:
            gate, up, down = (w.reshape((-1,) + w.shape[2:])
                              for w in (gate, up, down))
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((gate.shape[0],), jnp.int32), group_sizes,
                (layer * G,))

    with jax.named_scope("moe.experts"):
        # the tiles go by the rows a group is to expect: of N*k assignments
        # a held expert gets its share of all E, not of the G held
        g = _grouped_matmul(rows, gate, group_sizes, kernel, E)
        u = _grouped_matmul(rows, up, group_sizes, kernel, E)
        gated = (jax.nn.relu(g) if activation == "relu" else
                 jax.nn.silu(g.astype(jnp.float32)).astype(rows.dtype))
        act = gated * u
        out = _grouped_matmul(act, down, group_sizes, kernel, E)  # [N*k, D]
        if here is not None:    # rows no group computed hold anything
            out = jnp.where(here[:, None], out, 0)

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
    if bias_changed is not None:
        aux["bias_changed"] = bias_changed.reshape(lead)
    if absent is not None:
        aux["dropped"] = aux["dropped"] - absent
        aux["absent"] = absent
        aux["reached"] = reached
        aux["absent_by_token"] = jnp.sum(
            flat.reshape(N, top_k) == G, axis=-1, dtype=jnp.int32)
    return y.reshape(h.shape), aux


def router_stats(aux, token_mask, num_experts: int):
    """Per-row sums of the layer scan's stacked `aux` (leading layer axis)
    over the real tokens of each row, small enough to leave the device with
    the logprobs: `load` [B, L, E] assignments per expert, `entropy` [B]
    (summed over layers and tokens), `tokens` [B], `dropped` []; for a
    chip's share also `absent` [] (`moe_mlp`; over every token, pads too,
    as `dropped` is). Per row, so
    a caller that padded its last chunk with repeated rows can leave them
    out (`moe_counters`)."""
    m = token_mask.astype(jnp.float32)                            # [B, T]
    # compare, weigh and reduce in one fusion: no [L, B, T, k, E] array
    chosen = aux["experts"][..., None] == jnp.arange(num_experts)
    load = jnp.sum(chosen * m[None, :, :, None, None], axis=(2, 3))
    stats = {"load": load.transpose(1, 0, 2),
             "entropy": jnp.einsum("lbt,bt->b", aux["entropy"], m),
             "tokens": jnp.sum(m, axis=1),
             "dropped": jnp.sum(aux["dropped"])}
    if "absent" in aux:
        stats["absent"] = jnp.sum(aux["absent"])
    if "bias_changed" in aux:       # [B]: token-layers the bias re-chose
        stats["bias_changed"] = jnp.einsum(
            "lbt,bt->b", aux["bias_changed"].astype(jnp.float32), m)
    return stats


def moe_counters(stats: list, held=None) -> dict:
    """The trainer's row from the scored chunks' `router_stats` (host side,
    numpy trees already sliced to their real rows): `moe/load_max_over_mean`
    = tokens of the fullest expert over the mean, the maximum over layers;
    `moe/router_entropy` = mean over tokens and layers, in nats;
    `moe/dropped_tokens`. For a chip's share (`held=(count, offset)`) also
    `moe/held_experts`, `moe/absent_assignments` and `moe/routed_here_frac`,
    the share of the real tokens' assignments that land on held experts."""
    import numpy as np

    load = sum(np.asarray(s["load"], np.float64).sum(axis=0) for s in stats)
    tokens = sum(float(np.sum(s["tokens"])) for s in stats)
    entropy = sum(float(np.sum(s["entropy"])) for s in stats)
    mean = np.maximum(load.mean(axis=-1), 1e-9)                   # [L]
    out = {
        "moe/load_max_over_mean": float(np.max(load.max(axis=-1) / mean)),
        "moe/router_entropy": entropy / max(tokens * load.shape[0], 1.0),
        "moe/dropped_tokens": float(sum(int(s["dropped"]) for s in stats)),
    }
    if all("bias_changed" in s for s in stats):
        # tokens (a layer) whose chosen experts are not the top k of the
        # scores alone, and their share of all token-layers
        changed = sum(float(np.sum(s["bias_changed"])) for s in stats)
        out["moe/bias_changed_choice"] = changed
        out["moe/bias_changed_frac"] = changed / max(tokens * load.shape[0], 1.0)
    if held is not None:
        count, offset = held
        out["moe/held_experts"] = float(count)
        out["moe/absent_assignments"] = float(
            sum(int(s["absent"]) for s in stats))
        out["moe/routed_here_frac"] = float(
            load[:, offset:offset + count].sum() / max(load.sum(), 1.0))
    return out
