"""Sequence-parallel model forward: the whole decoder under shard_map.

Long-context as a first-class axis (the reference has none — SURVEY.md §5.7):
the sequence dimension is sharded over a mesh axis; attention runs as ring
attention (K/V rotating over ICI, `parallel/ring_attention.py`) while RMSNorm,
RoPE, projections and the MLP are position-local and need no communication.
Per-device memory for activations and attention state scales with T/n instead
of T, so contexts beyond a single device's HBM become trainable/scoreable.

Caveats (v1):
- `position_ids` must be precomputed globally and passed in sharded (the
  left-pad `cumsum` is a cross-shard scan, so it stays outside);
- the logit head runs locally per shard (vocab projection is position-local);
- sampling still uses the single-shard KV-cache path; SP targets the
  training/scoring passes where the O(T) activations live;
- `sp_forward_logits` closure-captures params (replicated over the sp mesh):
  right for dedicated-SP meshes. For fsdp×sp meshes use
  `sp_fsdp_forward_logits` / `sp_score_logprobs(fsdp_axis=...)` below —
  params stay sharded at rest and gather one layer at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from nanorlhf_tpu.core.config import ModelConfig
from nanorlhf_tpu.core.model import _hidden_from_inputs, _logits, use_flash
from nanorlhf_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_flash,
)


def _ring_attn_fn(key_valid, axis_name, attn_impl: str, t_local: int):
    """Pick the ring implementation: the Pallas flash ring
    (`ring_attention_flash`, differentiable via its global-lse custom_vjp)
    when `attn_impl` resolves to flash at this local width, the einsum ring
    otherwise. Scoring and update callers should pass the SAME attn_impl so
    exp(new−old) ratios carry no kernel-mismatch offset (ADVICE r3)."""
    if use_flash(attn_impl, t_local):
        return lambda q, k, v: ring_attention_flash(
            q, k, v, key_valid, axis_name=axis_name, causal=True
        )
    return lambda q, k, v: ring_attention(
        q, k, v, key_valid, axis_name=axis_name, causal=True
    )


def _sp_hidden_local(params, config: ModelConfig, input_ids, attention_mask,
                     position_ids, axis_name, lora_scale, remat,
                     attn_impl: str = "xla"):
    """Runs inside shard_map: the shared forward recipe up to the final
    hidden states, attention routed around the ring."""
    key_valid = attention_mask.astype(bool)
    ring_attn = _ring_attn_fn(key_valid, axis_name, attn_impl,
                              input_ids.shape[1])
    return _hidden_from_inputs(
        params, config, jnp.where(key_valid, input_ids, 0), attention_mask,
        position_ids, lora_scale, remat, attn_fn=ring_attn,
    )


def _sp_forward_local(params, config: ModelConfig, input_ids, attention_mask,
                      position_ids, axis_name, lora_scale, remat,
                      attn_impl: str = "xla"):
    """Hidden states → vocab logits (no duplicated embed/scan logic)."""
    x = _sp_hidden_local(params, config, input_ids, attention_mask,
                         position_ids, axis_name, lora_scale, remat, attn_impl)
    return _logits(config, params, x)


def sp_forward_logits(
    params: dict,
    config: ModelConfig,
    input_ids: jnp.ndarray,       # [B, T] global (T divisible by the sp axis)
    attention_mask: jnp.ndarray,  # [B, T]
    position_ids: jnp.ndarray,    # [B, T] global positions
    mesh: Mesh,
    axis_name: str = "sp",
    lora_scale: float = 1.0,
    remat: bool = False,
) -> jnp.ndarray:
    """Full-model forward with the sequence dim sharded over `axis_name`.

    Returns global logits [B, T, V] (sharded over T on the mesh).
    """
    fn = shard_map(
        partial(
            _sp_forward_local, params, config,
            axis_name=axis_name, lora_scale=lora_scale, remat=remat,
        ),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name, None),
    )
    return fn(input_ids, attention_mask, position_ids)


# ---------------------------------------------------------------------------
# SP × FSDP: params sharded at rest, gathered per layer inside the scan
# ---------------------------------------------------------------------------


def _fsdp_specs(params, fsdp_axis: str):
    """Per-leaf PartitionSpecs for this mesh: keep the fsdp placements from
    the framework's sharding rules, drop the (absent) tensor axis."""
    from nanorlhf_tpu.parallel.mesh import param_sharding_rules

    rules = param_sharding_rules(params)

    def remap(spec):
        return P(*[fsdp_axis if a == "fsdp" else None for a in spec])

    return jax.tree.map(remap, rules, is_leaf=lambda x: isinstance(x, P))


def _gather_by_spec(tree, specs, axis_name: str, skip_leading_dim: bool = False):
    """all_gather each leaf along the dims its spec marks as fsdp-sharded.

    `skip_leading_dim=True` for per-layer slices inside the scan: their spec
    still names the stacked [L, ...] layout, whose leading dim the scan has
    already consumed.
    """

    def gather(leaf, spec):
        dims = list(spec)
        if skip_leading_dim:
            dims = dims[1:]
        for dim, ax in enumerate(dims):
            if ax == axis_name:
                leaf = jax.lax.all_gather(leaf, axis_name, axis=dim, tiled=True)
        return leaf

    return jax.tree.map(gather, tree, specs)


def _sp_fsdp_forward_local(config, specs, sp_axis, fsdp_axis, lora_scale, remat,
                           params_local, input_ids, attention_mask, position_ids,
                           attn_impl: str = "xla", head: str = "lm"):
    """Inside shard_map over (fsdp, sp): sequence shard local, params shards
    gathered — embeddings up front (the lookup needs them), layer leaves one
    scan step at a time via the shared recipe's `layer_transform` hook, the
    lm_head lazily after the scan (ZeRO-3 execution model). Gradients flow
    back through all_gather's transpose (reduce-scatter), so grads come out
    sharded exactly like the params."""
    key_valid = attention_mask.astype(bool)
    ring_attn = _ring_attn_fn(key_valid, sp_axis, attn_impl,
                              input_ids.shape[1])

    lora_specs = specs.get("lora", {}).get("layers")

    def gather_layer(layer_local, lora_local):
        layer_full = _gather_by_spec(
            layer_local, specs["layers"], fsdp_axis, skip_leading_dim=True
        )
        lora_full = (
            _gather_by_spec(lora_local, lora_specs, fsdp_axis, skip_leading_dim=True)
            if lora_local is not None else None
        )
        return layer_full, lora_full

    embed_full = _gather_by_spec(
        params_local["embed_tokens"], specs["embed_tokens"], fsdp_axis
    )
    params_mixed = {**params_local, "embed_tokens": embed_full}
    x = _hidden_from_inputs(
        params_mixed, config, jnp.where(key_valid, input_ids, 0), attention_mask,
        position_ids, lora_scale, remat, attn_fn=ring_attn,
        layer_transform=gather_layer,
    )
    norm_full = _gather_by_spec(params_local["norm"], specs["norm"], fsdp_axis)
    if head == "score":
        # value/RM head: final-normed hidden @ score — position-local, no
        # cross-shard traffic (matches core.model.score_forward)
        from nanorlhf_tpu.core.model import rms_norm

        x = rms_norm(x, norm_full, config.rms_norm_eps)
        score = _gather_by_spec(
            params_local["score"], specs["score"], fsdp_axis
        )
        return x.astype(jnp.float32) @ score.astype(jnp.float32)
    # lm_head / final norm gathered only now (tied models reuse embed_full)
    head_tree = {"embed_tokens": embed_full, "norm": norm_full}
    if not config.tie_word_embeddings:
        head_tree["lm_head"] = _gather_by_spec(
            params_local["lm_head"], specs["lm_head"], fsdp_axis
        )
    return _logits(config, head_tree, x)


def sp_score_logprobs(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,   # [B, T] global, T divisible by sp axis
    pad_token_id: int,
    temperature: float,
    mesh: Mesh,
    sp_axis: str = "sp",
    fsdp_axis: str | None = None,
    lora_scale: float = 1.0,
    remat: bool = False,
    with_entropy: bool = False,
    entropy_from_position: int = 0,
    attn_impl: str = "xla",
) -> jnp.ndarray:
    """Per-position next-token logprobs [B, T] under sequence parallelism —
    the scoring primitive for beyond-one-device contexts (the RL logprob
    pass, `/root/reference/GRPO/grpo_trainer.py:534-556`, at ring scale).

    Entry t holds log p(token_{t+1} | tokens_{<=t}); the final position is 0
    (no next token). Labels cross shard boundaries, so each shard fetches its
    right neighbor's first token via ppermute. Callers slice
    `[:, ctx-1:T-1]` for response logprobs exactly as in the single-device
    path. `fsdp_axis` switches the underlying forward to the
    params-sharded-at-rest variant. `remat` checkpoints per-layer activations
    — pass the trainer's gradient_checkpointing when differentiating through
    this (scoring-only callers can leave it off).

    `attn_impl` routes the ring: "auto"/"pallas" engage the flash ring
    (`ring_attention_flash`) per `use_flash` resolution. Both rings are
    differentiable (the flash ring's backward re-runs the ring through the
    Pallas flash-bwd kernels with the global lse) — scoring and update
    passes should use the SAME impl so the ratio/KL estimates carry no
    kernel-mismatch offset.

    `with_entropy=True` additionally returns the unmasked-mean entropy of
    the temperature-scaled logits (the reference's `policy/entropy_avg_new`
    stat, `GRPO/grpo_trainer.py:679-687`): each shard's logits are
    full-vocab, so per-position entropy is local and the global mean is one
    psum over the sp axis — the global [B, T, V] logits never materialize.
    The mean spans global positions [entropy_from_position, T-1) — callers
    pass `context_length - 1` so the scope matches the dense path, whose
    logits cover only the response region (`padded_forward_logits`'s
    `response_context_length` slice); prompt positions have systematically
    lower entropy on a trained model and must not dilute the stat.

    Unaffected by `cfg.fused_logprob` (ops/fused_logprob.py, the dense
    paths' chunked linear-cross-entropy): the per-shard logits block here is
    already [B, T/sp, V]-local, reduced to per-token scalars inside the
    shard_map body before anything global assembles — sequence parallelism
    IS this path's logits-memory mitigation, scaling with the ring width.
    Row-chunking the local head would compose with it but only pays off once
    T/sp alone exceeds the fused chunk budget.
    """
    from nanorlhf_tpu.core.model import padding_inputs
    from nanorlhf_tpu.ops.masking import (
        entropy_from_logits,
        guard_temperature,
        logprobs_from_logits,
    )

    _, attention_mask, position_ids = padding_inputs(query_responses, pad_token_id)
    attention_mask = attention_mask.astype(jnp.int32)

    n_sp = mesh.shape[sp_axis]

    T_global = query_responses.shape[1]

    def local_score(logits_local, ids_local):
        # label for local position t = ids[t+1]; last local label comes from
        # the right neighbor's first token (left rotation around the ring)
        perm = [(i, (i - 1) % n_sp) for i in range(n_sp)]
        from_right = jax.lax.ppermute(ids_local[:, :1], sp_axis, perm)
        labels = jnp.concatenate([ids_local[:, 1:], from_right], axis=1)
        lp = logprobs_from_logits(logits_local, labels, temperature)
        if not with_entropy:
            return lp
        # response-region scope: global positions [from, T-1) — same span
        # the dense path's response_context_length slice covers
        t_local = logits_local.shape[1]
        gpos = jax.lax.axis_index(sp_axis) * t_local + jnp.arange(t_local)
        in_span = (gpos >= entropy_from_position) & (gpos < T_global - 1)
        ent_pos = jax.lax.stop_gradient(entropy_from_logits(
            logits_local.astype(jnp.float32) / guard_temperature(temperature)
        ))                                             # [B, T_local]
        s = jax.lax.psum((ent_pos * in_span[None, :]).sum(), sp_axis)
        c = jax.lax.psum(
            (in_span.sum() * ent_pos.shape[0]).astype(jnp.float32), sp_axis
        )
        return lp, s / jnp.maximum(c, 1.0)

    out_specs = (P(None, sp_axis), P()) if with_entropy else P(None, sp_axis)

    if fsdp_axis is not None:
        specs = _fsdp_specs(params, fsdp_axis)

        def fn(params_local, ids, mask, pos):
            logits = _sp_fsdp_forward_local(
                config, specs, sp_axis, fsdp_axis, lora_scale, remat,
                params_local, ids, mask, pos, attn_impl=attn_impl,
            )
            return local_score(logits, ids)

        out = shard_map(
            fn, mesh=mesh,
            in_specs=(specs, P(None, sp_axis), P(None, sp_axis), P(None, sp_axis)),
            out_specs=out_specs,
            check_vma=False,
        )(params, query_responses, attention_mask, position_ids)
    else:
        def fn(ids, mask, pos):
            logits = _sp_forward_local(
                params, config, ids, mask, pos,
                axis_name=sp_axis, lora_scale=lora_scale, remat=remat,
                attn_impl=attn_impl,
            )
            return local_score(logits, ids)

        out = shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, sp_axis), P(None, sp_axis), P(None, sp_axis)),
            out_specs=out_specs,
            check_vma=False,
        )(query_responses, attention_mask, position_ids)
    lp, ent = out if with_entropy else (out, None)
    # final global position has no next token
    lp = lp.at[:, -1].set(0.0)
    return (lp, ent) if with_entropy else lp


def sp_score_values(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,   # [B, T] global, T divisible by sp axis
    pad_token_id: int,
    mesh: Mesh,
    sp_axis: str = "sp",
    fsdp_axis: str | None = None,
    lora_scale: float = 1.0,
    remat: bool = False,
    attn_impl: str = "xla",
) -> jnp.ndarray:
    """Per-position value/RM scores [B, T, num_labels] under sequence
    parallelism — `core.model.score_forward` at ring scale (the PPO value
    pass, `PPO/ppo_trainer.py:630-634,732`, for beyond-one-device contexts).
    The score head is position-local, so unlike logprob scoring nothing
    crosses shard boundaries after the ring. Differentiable with either
    ring impl; the PPO update should score and differentiate with the same
    `attn_impl` as the value-scoring pass."""
    from nanorlhf_tpu.core.model import padding_inputs, rms_norm

    _, attention_mask, position_ids = padding_inputs(query_responses, pad_token_id)
    attention_mask = attention_mask.astype(jnp.int32)

    if fsdp_axis is not None:
        specs = _fsdp_specs(params, fsdp_axis)
        fn = partial(_sp_fsdp_forward_local, config, specs, sp_axis,
                     fsdp_axis, lora_scale, remat, attn_impl=attn_impl,
                     head="score")
        return shard_map(
            fn, mesh=mesh,
            in_specs=(specs, P(None, sp_axis), P(None, sp_axis), P(None, sp_axis)),
            out_specs=P(None, sp_axis, None),
            check_vma=False,
        )(params, query_responses, attention_mask, position_ids)

    def fn(ids, mask, pos):
        x = _sp_hidden_local(params, config, ids, mask, pos,
                             axis_name=sp_axis, lora_scale=lora_scale,
                             remat=remat, attn_impl=attn_impl)
        x = rms_norm(x, params["norm"], config.rms_norm_eps)
        return x.astype(jnp.float32) @ params["score"].astype(jnp.float32)

    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, sp_axis), P(None, sp_axis), P(None, sp_axis)),
        out_specs=P(None, sp_axis, None),
        check_vma=False,
    )(query_responses, attention_mask, position_ids)


def sp_fsdp_forward_logits(
    params: dict,
    config: ModelConfig,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
    position_ids: jnp.ndarray,
    mesh: Mesh,
    sp_axis: str = "sp",
    fsdp_axis: str = "fsdp",
    lora_scale: float = 1.0,
    remat: bool = False,
) -> jnp.ndarray:
    """Sequence-parallel forward with fsdp-sharded parameters (roadmap #7).

    Params enter through shard_map in_specs with the framework's fsdp
    placements — sharded at rest, all-gathered one layer at a time inside the
    scan — while the sequence dim shards over `sp_axis`. Peak param memory
    per device ≈ params/n_fsdp + one full layer + the full embedding table
    (and, for untied models, the lm_head while computing logits) — the
    embedding must be whole for the lookup and the head for the projection.
    """
    specs = _fsdp_specs(params, fsdp_axis)
    fn = shard_map(
        partial(_sp_fsdp_forward_local, config, specs, sp_axis, fsdp_axis,
                lora_scale, remat),
        mesh=mesh,
        in_specs=(specs, P(None, sp_axis), P(None, sp_axis), P(None, sp_axis)),
        out_specs=P(None, sp_axis, None),
        # logits are fsdp-replicated by construction (every member gathered
        # identical weights), which vma inference can't prove through
        # all_gather — the parity tests assert it instead
        check_vma=False,
    )
    return fn(params, input_ids, attention_mask, position_ids)
