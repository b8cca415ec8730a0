"""Plain reference: a SmallThinker forward pass in `jax.numpy`, float32.

The layer as this repository reads the published description (the 21B-A3B
`config.json` and the model card's "SWA(4096); NoPE global; sparse ReGLU;
router placed before attention"), `x` the residual stream, layer `l` with
`w = sliding_window_layout[l]`, `r = rope_layout[l]`:

    h  = rmsnorm(x; input_layernorm)
    s  = h                                  the ROUTER's input
    q, k, v = h W_q, h W_k, h W_v           no biases; heads of head_dim
    if r: rotate-half RoPE on q, k          (a layer with r = 0 has none)
    a  = softmax(q k^T / sqrt(head_dim) + M) v
         M causal; if w, key j is visible to query i iff i - window < j <= i
    x += a W_o
    h2 = rmsnorm(x; post_attention_layernorm)
    z  = s W_r (E logits);  top-k of z;  p = softmax over those k
    x += sum_e p_e W_down[e]( relu(h2 W_gate[e]) * (h2 W_up[e]) )

No kernel, no cache, no sort, no grouped matmul, no scan over periods: ONE
scan over the layers, each with its own two entries of the layouts as data
(both masks are built once; a layer picks its own with `where`, and rotates
q and k or not with `where`), EVERY expert is computed for EVERY token and
weighted by a [tokens, experts] matrix that is zero off the top k. Nothing is imported from `nanorlhf_tpu`
(RMSNorm, rotate-half RoPE and the LoRA'd linear are harness/reference.py's);
the tree is read by leaf names only (`embed_tokens [V, D]`; `layers.*` stacked
on a leading layer axis: `q_proj/k_proj/v_proj/o_proj.kernel [L, in, out]`,
`input_layernorm`, `post_attention_layernorm`, `router.kernel [L, D, E]`,
`experts.{gate,up,down}_proj.kernel [L, E, in, out]`; `norm`; `lm_head
[D, V]`; `lora.layers.<proj>.{a, b}` on the attention projections).

Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics:
- rows are LEFT-padded, so position ids count real tokens only and pad keys
  are masked (harness/reference.py's departure); the window is over sequence
  indices, which for a row's real tokens are its positions plus a constant;
- attention goes in blocks of `QUERY_BLOCK` queries once a row is longer
  than that (a 14k-token row's [heads, T, T] scores are 22 GB), each block
  against all keys under its own rows of the mask: the same sums;
- the vocabulary projection goes in `HEAD_BLOCKS` column blocks of the head
  (a float32 copy of a 151,936 x 2,560 head is 1.6 GB beside a served model);
- `softmax over the top k of z` is computed as published models do it,
  softmax over all E then renormalised over the chosen k (`norm_topk_prob`):
  the same numbers.

`window=False` and `nope=False` are the two NEGATIVE CONTROLS of the cell's
comparison (a model without the window; a model that rotates every layer):
against either, a sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, MASKED, _linear, _rms_norm, _rope

QUERY_BLOCK = 256
HEAD_BLOCKS = 8


def _expert_mlp(h, s, p, top_k: int, renorm: bool):
    """h, s [B, T, D] -> [B, T, D]: the router reads `s`, the experts `h`;
    all experts for all tokens, one expert at a time."""
    probs = jax.nn.softmax(s @ p["router"]["kernel"].astype(F32), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    dense_w = jnp.where(probs >= kth, probs, 0.0)          # zero off the top k
    if renorm:
        dense_w = dense_w / jnp.sum(dense_w, axis=-1, keepdims=True)

    def one(acc, ew):
        gate, up, down, w = ew                             # w [B, T]
        out = (jax.nn.relu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
        return acc + w[..., None] * out, None

    ex = p["experts"]
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
         ex["down_proj"]["kernel"], jnp.moveaxis(dense_w, -1, 0)))
    return acc


def _attention(q, k, v, allowed):
    """q, k, v [B, H, T, hd]; allowed [B, 1, T, T] -> [B, H, T, hd], in
    blocks of queries where the row is long."""
    hd = q.shape[-1]

    def block(qb, mb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(mb, s, MASKED)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    T = q.shape[2]
    if T <= QUERY_BLOCK:
        return block(q, allowed)
    n = -(-T // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - T       # padded queries see nothing, cut off below
    qs = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ms = jnp.pad(allowed, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qs = jnp.moveaxis(qs.reshape(q.shape[:2] + (n, QUERY_BLOCK, hd)), 2, 0)
    ms = jnp.moveaxis(ms.reshape(ms.shape[:2] + (n, QUERY_BLOCK, T)), 2, 0)
    out = jax.lax.map(lambda a: block(*a), (qs, ms))       # [n, B, H, bq, hd]
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:2] + (n * QUERY_BLOCK, hd))[:, :, :T]


def hidden_states(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
                  mask=None, window: bool = True, nope: bool = True):
    """Final-normed hidden states [B, T, D] for left-padded token ids.
    `window=False` drops the sliding window, `nope=False` rotates every
    layer: the negative controls."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    top_k = cfg["moe_num_active_primary_experts"]
    renorm = bool(cfg["norm_topk_prob"])
    L, W = cfg["num_hidden_layers"], int(cfg["sliding_window_size"])
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    causal = (j <= i)[None, None] & mask[:, None, None, :]
    near = causal & (j > i - W)[None, None]
    x = params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
    lora_layers = params.get("lora", {}).get("layers", {})
    windowed = jnp.asarray([window and bool(w) for w in
                            cfg["sliding_window_layout"][:L]])
    rotated = jnp.asarray([(not nope) or bool(r) for r in
                           cfg["rope_layout"][:L]])

    def layer(x, lp):
        p, lo, is_window, is_rotated = lp
        lin = lambda h, name: _linear(h, p[name], lo.get(name), lora_scale)  # noqa: E731
        h = _rms_norm(x, p["input_layernorm"], eps)
        s = h
        q = lin(h, "q_proj").reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = lin(h, "k_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v = lin(h, "v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        q = jnp.where(is_rotated, _rope(q, positions, theta), q)
        k = jnp.where(is_rotated, _rope(k, positions, theta), k)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        a = _attention(q, k, v, jnp.where(is_window, near, causal))
        x = x + lin(a.transpose(0, 2, 1, 3).reshape(B, T, H * hd), "o_proj")
        h2 = _rms_norm(x, p["post_attention_layernorm"], eps)
        return x + _expert_mlp(h2, s, p, top_k, renorm), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], lora_layers, windowed,
                                   rotated))
    return _rms_norm(x, params["norm"], eps)


def logits(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
           last: int | None = None, mask=None, window: bool = True,
           nope: bool = True):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    h = hidden_states(params, cfg, ids, pad_id, lora_scale, mask, window, nope)
    if last is not None:
        h = h[:, -last:]
    head = (params["embed_tokens"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    V = head.shape[1]
    if V % HEAD_BLOCKS or V < 65536:
        return h @ head.astype(F32)
    cols = jnp.moveaxis(head.reshape(head.shape[0], HEAD_BLOCKS, -1), 1, 0)
    out = jax.lax.map(lambda w: h @ w.astype(F32), cols)   # [n, B, T, V / n]
    return jnp.moveaxis(out, 0, 2).reshape(h.shape[:2] + (V,))


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
