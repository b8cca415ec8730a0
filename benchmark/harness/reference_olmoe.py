"""Plain reference: an OLMoE forward pass in `jax.numpy`, float32.

The layer as published (HF `modeling_olmoe.py`), `x` the residual stream:

    h = rmsnorm(x);  q = rmsnorm(h W_q),  k = rmsnorm(h W_k)   (weights q_norm,
        k_norm over the WHOLE projection width, before the head split),
        v = h W_v;  rotate-half RoPE on q, k;  causal softmax attention;
        x += a W_o
    h = rmsnorm(x);  p = softmax(h W_r) over the experts;  (w, e) = top_k(p),
        renormalised over the chosen ones only if `norm_topk_prob`;
        x += sum_j w_j W_down[e_j]( silu(h W_gate[e_j]) * (h W_up[e_j]) )

No kernel, no cache, no sort and no grouped matmul: EVERY expert is computed
for EVERY token and the results are weighted by a [tokens, experts] matrix
that is zero off the top k, so nothing here shares a line or an idea with
`nanorlhf_tpu/ops/moe.py`. Nothing is imported from `nanorlhf_tpu` (RMSNorm,
rotate-half RoPE and the LoRA'd linear are harness/reference.py's): the tree
is read by its leaf names only (`embed_tokens [V, D]`; `layers.*` stacked on
a leading layer axis: `q_proj/k_proj/v_proj/o_proj.kernel [L, in, out]`,
`q_norm`, `k_norm`, `input_layernorm`, `post_attention_layernorm`,
`router.kernel [L, D, E]`, `experts.{gate,up,down}_proj.kernel [L, E, in,
out]`; `norm`; `lm_head [D, V]`; `lora.layers.<proj>.{a, b}` on the
attention projections).

Weights may arrive in bfloat16: each layer is cast to float32 as it is used
(exact), and the experts are visited one at a time (a scan), so the
reference never holds a float32 copy of the model nor a [tokens, experts,
width] array. Callers wrap calls in `jax.default_matmul_precision("highest")`:
on a TPU a float32 matmul otherwise runs in lower precision.

Departure from the published description, the same as harness/reference.py:
rows are LEFT-padded, so position ids count real tokens only
(`cumsum(mask) - 1`) and pad keys are masked; a real row without pads is
computed exactly as published. (HF computes the router's logits in the
model's dtype and the softmax in float32; here both are float32, which is
what "published" means for a float32 model.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, MASKED, _linear, _rms_norm, _rope


def _expert_mlp(h, p, top_k: int, norm_topk_prob: bool):
    """h [B, T, D] -> [B, T, D]: all experts for all tokens, one expert at a
    time, weighted by the dense [B, T, E] matrix of top-k router weights."""
    probs = jax.nn.softmax(h @ p["router"]["kernel"].astype(F32), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    dense_w = jnp.where(probs >= kth, probs, 0.0)          # zero off the top k
    if norm_topk_prob:
        dense_w = dense_w / jnp.sum(dense_w, axis=-1, keepdims=True)

    def one(acc, ew):
        gate, up, down, w = ew                             # w [B, T]
        out = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
        return acc + w[..., None] * out, None

    ex = p["experts"]
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
         ex["down_proj"]["kernel"], jnp.moveaxis(dense_w, -1, 0)))
    return acc


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
    top_k, renorm = cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"])
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
        q = _rms_norm(lin(h, "q_proj"), p["q_norm"], eps)
        k = _rms_norm(lin(h, "k_proj"), p["k_norm"], eps)
        q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v = lin(h, "v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(hd))
        s = jnp.where(allowed, s, MASKED)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        x = x + lin(a.transpose(0, 2, 1, 3).reshape(B, T, H * hd), "o_proj")
        h = _rms_norm(x, p["post_attention_layernorm"], eps)
        return x + _expert_mlp(h, p, top_k, renorm), None

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
