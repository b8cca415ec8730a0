"""Plain reference: a Qwen2 forward pass in `jax.numpy`, float32.

RMSNorm, rotary embedding (rotate-half), biased grouped-query attention,
SwiGLU, tied or untied output head, optional LoRA delta. No kernel, no cache,
no batching trick, and nothing imported from `nanorlhf_tpu`: it reads the
parameter tree by its leaf names only (`embed_tokens [V, D]`, `layers.*`
stacked on a leading layer axis with kernels `[L, in, out]`, `norm`,
`lm_head [D, V]` when untied, `lora.layers.<proj>.{a, b}`).

Weights may arrive in bfloat16: each layer is cast to float32 as it is used
(exact), so the reference never holds a float32 copy of the model. Callers
wrap calls in `jax.default_matmul_precision("highest")`: on a TPU a float32
matmul otherwise runs in lower precision.

Departure from the published description: rows are LEFT-padded, so position
ids count real tokens only (`cumsum(mask) - 1`) and pad keys are masked; a
real row without pads is computed exactly as published.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _rope(x, positions, theta):
    """x [B, heads, T, hd]; HF rotate-half convention."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[:, None, :, None].astype(F32) * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _linear(h, p, lora, scale):
    y = h @ p["kernel"].astype(F32)
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    if lora is not None:
        y = y + ((h @ lora["a"].astype(F32)) @ lora["b"].astype(F32)) * scale
    return y


def hidden_states(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
                  mask=None):
    """Final-normed hidden states [B, T, D] for left-padded token ids. `mask`
    [B, T] says which positions are real; by default every id but `pad_id`
    (a model can emit the pad id itself: callers that know the lengths pass
    the mask)."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    allowed = causal[None, None] & mask[:, None, None, :]
    x = params["embed_tokens"].astype(F32)[jnp.where(mask, ids, 0)]
    lora_layers = params.get("lora", {}).get("layers", {})

    def layer(x, lp):
        p, lo = lp
        lin = lambda h, name: _linear(h, p[name], lo.get(name), lora_scale)  # noqa: E731
        h = _rms_norm(x, p["input_layernorm"], eps)
        q = lin(h, "q_proj").reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = lin(h, "k_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v = lin(h, "v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(hd))
        s = jnp.where(allowed, s, MASKED)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        x = x + lin(a.transpose(0, 2, 1, 3).reshape(B, T, H * hd), "o_proj")
        h = _rms_norm(x, p["post_attention_layernorm"], eps)
        mlp = jax.nn.silu(lin(h, "gate_proj")) * lin(h, "up_proj")
        return x + lin(mlp, "down_proj"), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], lora_layers))
    return _rms_norm(x, params["norm"], eps)


def logits(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
           last: int | None = None, mask=None):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    h = hidden_states(params, cfg, ids, pad_id, lora_scale, mask)
    if last is not None:
        h = h[:, -last:]
    if cfg["tie_word_embeddings"]:
        return h @ params["embed_tokens"].astype(F32).T
    return h @ params["lm_head"].astype(F32)


def response_logprobs(params, cfg: dict, query_responses, context: int,
                      pad_id: int, temperature: float,
                      lora_scale: float = 1.0):
    """log p(token_t | tokens_<t) at temperature, for t in the response:
    [B, T - context]. The logits at position t-1 predict token t."""
    n_resp = query_responses.shape[1] - context
    lg = logits(params, cfg, query_responses, pad_id, lora_scale,
                last=n_resp + 1)[:, :-1]
    logp = jax.nn.log_softmax(lg / temperature, axis=-1)
    labels = query_responses[:, context:]
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
