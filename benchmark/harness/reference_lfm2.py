"""Plain reference: an LFM2-MoE forward pass in `jax.numpy`, float32.

The layers as this repository reads the published description (the 24B-A2B
`config.json`, `model_type: lfm2_moe`, and the family's modelling code), `x`
the residual stream, layer `l` of kind `layer_types[l]`:

    h = rmsnorm(x; input_layernorm)               the operator's norm
    conv:       [b | c | u] = h W_in              three parts of D, in that order
                g_t = b_t * u_t
                y_t = c_t * sum_{j<K} w[j] * g_{t-K+1+j}    depthwise, causal, no bias;
                                                  g is 0 before the row's first real token
                x += y W_out
    attention:  q, k, v = h W_q, h W_k, h W_v     no biases; heads of D / H
                q = rmsnorm(q; q_norm) per head,  k = rmsnorm(k; k_norm) per head
                rotate-half RoPE on q, k;  a = softmax(q k^T / sqrt(hd) + causal) v
                x += a W_o
    h2 = rmsnorm(x; post_attention_layernorm)     the MLP's norm
    l < num_dense_layers:   x += W_down( silu(h2 W_gate) * (h2 W_up) )
    else:       s = sigmoid(h2 W_r)               E scores
                chosen = top k of (s + bias)      the bias selects only
                p = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
                x += sum_e p_e W_down[e]( silu(h2 W_gate[e]) * (h2 W_up[e]) )
    logits = rmsnorm(x; norm) E^T                 head tied to the embedding

No kernel, no cache, no state, no sort, no grouped matmul, no packed heads:
ONE scan over the layers, each with its kind as data (both operators are
computed and a layer keeps its own with `where`; the weights of the kind it
is not are another layer's, picked by an index that is data too), the
convolution as K shifted multiplies over the whole row, EVERY expert computed
for EVERY token and weighted by a [tokens, experts] matrix that is zero off
the chosen k. The dense layers come first, in a scan of their own (another
MLP, so another tree). Nothing is imported from `nanorlhf_tpu` (RMSNorm and
rotate-half RoPE are harness/reference.py's); the tree is read by leaf names
only: `embed_tokens [V, D]`, `norm`, and two stacks, `dense_layers` and
`layers`, each with `input_layernorm`, `post_attention_layernorm [n, D]`,
the MLP (`gate_proj/up_proj/down_proj.kernel [n, in, out]`, or
`router.{kernel [n, D, E], bias [n, E]}` and `experts.*.kernel [n, E, in,
out]`), `conv.{in_proj.kernel [nc, D, 3D], conv.kernel [nc, K, D] (oldest
tap first), out_proj.kernel [nc, D, D]}` over ITS conv layers and
`q_proj/k_proj/v_proj/o_proj.kernel`, `q_norm/k_norm [na, hd]` over ITS
attention layers.

Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics:
- rows are LEFT-padded, so position ids count real tokens only, pad keys are
  masked and a pad's `g` is 0 (what stands before a row's first token);
- attention goes in blocks of `QUERY_BLOCK` queries once a row is longer
  than that, each block against all keys under its own rows of the mask;
- the vocabulary projection goes in `HEAD_BLOCKS` column blocks.

The NEGATIVE CONTROLS of the cell's comparison are keywords: `bias=False`
(the selection without its bias) and `qk_norm=False` (no per-head norms).
Against either, a sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, MASKED, _rms_norm, _rope

QUERY_BLOCK = 256
HEAD_BLOCKS = 8
NORM_EPS = 1e-6         # under the chosen scores' sum


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def _expert_mlp(h, p, top_k: int, renorm: bool, scale: float, bias: bool):
    """h [B, T, D] -> [B, T, D]: all experts for all tokens, one at a time."""
    s = jax.nn.sigmoid(h @ p["router"]["kernel"].astype(F32))
    pick = s + p["router"]["bias"].astype(F32) if bias else s
    kth = jnp.sort(pick, axis=-1)[..., -top_k][..., None]
    dense_w = jnp.where(pick >= kth, s, 0.0)               # zero off the chosen
    if renorm:
        dense_w = dense_w / (jnp.sum(dense_w, axis=-1, keepdims=True) + NORM_EPS)
    dense_w = dense_w * scale

    def one(acc, ew):
        gate, up, down, w = ew                             # w [B, T]
        return acc + w[..., None] * _swiglu(h, gate, up, down), None

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


def _conv(h, mask, w_in, taps, w_out):
    """The gated short convolution over whole rows: K shifted multiplies."""
    b, c, u = jnp.split(h @ w_in.astype(F32), 3, axis=-1)
    g = jnp.where(mask[..., None], b * u, 0.0)
    K, T = taps.shape[0], h.shape[1]
    back = jnp.pad(g, ((0, 0), (K - 1, 0), (0, 0)))        # g_{t-K+1+j} at [t + j]
    mixed = sum(taps[j].astype(F32) * back[:, j:j + T] for j in range(K))
    return (c * mixed) @ w_out.astype(F32)


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None,
                  bias: bool = True, qk_norm: bool = True):
    """Final-normed hidden states [B, T, D] for left-padded token ids.
    `bias=False` selects by the scores alone, `qk_norm=False` drops the
    per-head norms: the negative controls."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps = cfg["norm_eps"]
    theta = float((cfg.get("rope_parameters") or {}).get(
        "rope_theta", cfg.get("rope_theta", 1e6)))
    top_k, renorm = cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"])
    scale = float(cfg.get("routed_scaling_factor", 1.0))
    bias = bias and bool(cfg.get("use_expert_bias"))
    L, dense = cfg["num_hidden_layers"], int(cfg.get("num_dense_layers") or 0)
    kinds = [t == "conv" for t in cfg["layer_types"][:L]]
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    causal = (j <= i)[None, None] & mask[:, None, None, :]
    x = params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)

    def stack(x, tree, is_conv, mlp):
        """One scan over `tree`'s layers; `is_conv` their kinds."""
        n = len(is_conv)
        # a layer's index among its kind's leaves; the other kind's index is
        # clamped to a layer that exists (its result is thrown away)
        at_conv = [sum(is_conv[:l]) for l in range(n)]
        at_attn = [l - c for l, c in enumerate(at_conv)]
        nc, na = sum(is_conv), n - sum(is_conv)
        shared = {k: v for k, v in tree.items()
                  if k in ("input_layernorm", "post_attention_layernorm",
                           "router", "experts", "gate_proj", "up_proj",
                           "down_proj")}
        conv = tree.get("conv")
        attn = {k: tree[k] for k in ("q_proj", "k_proj", "v_proj", "o_proj",
                                     "q_norm", "k_norm") if k in tree}

        def layer(x, lp):
            p, kind, ic, ia = lp
            h = _rms_norm(x, p["input_layernorm"], eps)
            op = jnp.zeros_like(x)
            if nc:
                cw = jax.tree.map(lambda a: a[ic], conv)
                op = _conv(h, mask, cw["in_proj"]["kernel"],
                           cw["conv"]["kernel"], cw["out_proj"]["kernel"])
            if na:
                aw = jax.tree.map(lambda a: a[ia], attn)
                lin = lambda name: h @ aw[name]["kernel"].astype(F32)  # noqa: E731
                q = lin("q_proj").reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                k = lin("k_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
                v = lin("v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
                if qk_norm:
                    q = _rms_norm(q, aw["q_norm"], eps)
                    k = _rms_norm(k, aw["k_norm"], eps)
                q, k = _rope(q, positions, theta), _rope(k, positions, theta)
                k = jnp.repeat(k, H // KV, axis=1)
                v = jnp.repeat(v, H // KV, axis=1)
                a = _attention(q, k, v, causal)
                a = a.transpose(0, 2, 1, 3).reshape(B, T, H * hd) \
                    @ aw["o_proj"]["kernel"].astype(F32)
                op = jnp.where(kind, op, a)
            x = x + op
            h2 = _rms_norm(x, p["post_attention_layernorm"], eps)
            return x + mlp(h2, p), None

        x, _ = jax.lax.scan(layer, x, (
            shared, jnp.asarray(is_conv),
            jnp.asarray([min(c, max(nc - 1, 0)) for c in at_conv]),
            jnp.asarray([min(a, max(na - 1, 0)) for a in at_attn])))
        return x

    if dense:
        x = stack(x, params["dense_layers"], kinds[:dense],
                  lambda h2, p: _swiglu(h2, p["gate_proj"]["kernel"],
                                        p["up_proj"]["kernel"],
                                        p["down_proj"]["kernel"]))
    x = stack(x, params["layers"], kinds[dense:],
              lambda h2, p: _expert_mlp(h2, p, top_k, renorm, scale, bias))
    return _rms_norm(x, params["norm"], eps)


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, bias: bool = True, qk_norm: bool = True):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    h = hidden_states(params, cfg, ids, pad_id, mask, bias, qk_norm)
    if last is not None:
        h = h[:, -last:]
    head = (params["embed_tokens"].T if cfg.get("tie_word_embeddings", True)
            else params["lm_head"])
    V = head.shape[1]
    if V % HEAD_BLOCKS or V < 65536:
        return h @ head.astype(F32)
    cols = jnp.moveaxis(head.reshape(head.shape[0], HEAD_BLOCKS, -1), 1, 0)
    out = jax.lax.map(lambda w: h @ w.astype(F32), cols)   # [n, B, T, V / n]
    return jnp.moveaxis(out, 0, 2).reshape(h.shape[:2] + (V,))


def response_logprobs(params, cfg: dict, query_responses, context: int,
                      pad_id: int, temperature: float):
    """log p(token_t | tokens_<t) at temperature, for t in the response:
    [B, T - context]. The logits at position t-1 predict token t."""
    n_resp = query_responses.shape[1] - context
    lg = logits(params, cfg, query_responses, pad_id, last=n_resp + 1)[:, :-1]
    logp = jax.nn.log_softmax(lg / temperature, axis=-1)
    labels = query_responses[:, context:]
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
