"""Plain reference: a Trinity (`model_type: afmoe`) forward pass in
`jax.numpy`, float32.

The layers as this repository reads the published description (the
Trinity-Large-Preview `config.json` and its card: "SWA(4096) gated; global
every 4th", "256 experts, top-4, 1 shared; sigmoid routing, SMEBU bias",
"depth-scaled sandwich norm"), `x` the residual stream, layer `l` of kind
`layer_types[l]`:

    x0 = E[ids] * sqrt(hidden_size)               mup_enabled (a)
    h  = rmsnorm(x; input_layernorm)
    q, k, v = h W_q, h W_k, h W_v;  g = h W_g     no biases; W_g as wide as W_q
    q = rmsnorm(q; q_norm) per head,  k = rmsnorm(k; k_norm) per head
    sliding_attention: rotate-half RoPE on q, k; key j visible to query i
                       iff i - sliding_window < j <= i
    full_attention:    NO rotary (b); causal
    a  = softmax(q k^T / sqrt(head_dim) + mask) v
    a  = a * sigmoid(g)                           per element, before W_o
    x += rmsnorm(a W_o; attn_branch_norm)         the branch is normed AGAIN
    h2 = rmsnorm(x; post_attention_layernorm)     (HF: pre_mlp_layernorm)
    l < num_dense_layers:  m = W_down( silu(h2 W_gate) * (h2 W_up) )
    else:   s = sigmoid(h2 W_r)                   E scores, float32
            chosen = top k of (s + bias)          the bias selects only
            p = s[chosen] / (sum s[chosen] + 1e-20) * route_scale
            m = shared(h2) + sum_e p_e W_down[e]( silu(h2 W_gate[e]) * (h2 W_up[e]) )
    x += rmsnorm(m; mlp_branch_norm)
    logits = rmsnorm(x; norm) W_head              untied

(a), (b) and the leaf names are ASSUMED from the family's modelling code
(no network here): the configuration file lists them under `assumed`.

**The chip's share**: the tree holds experts `[offset, offset + held)` of a
router of all E (`experts.*.kernel [n, held, in, out]`); the sum over `e`
runs over the held ones among the chosen k, what the absent ones would add is
left out (`held`, `offset`: arguments, by default the file's
`num_experts_held` / `num_experts_offset`, or every expert). The vocabulary
slice is the tree's own: `embed_tokens [V', D]` and `lm_head [D, V']`.

No kernel, no cache, no sort, no grouped matmul, no scan over periods: one
scan over each stack's layers (the dense layers have another MLP, so another
tree), each layer with its kind as data (both masks are built once; a layer
picks its own with `where`, and rotates q and k or not with `where`), EVERY
held expert computed for EVERY token and weighted by a [tokens, held] matrix
that is zero off the chosen k. Nothing is imported from `nanorlhf_tpu`
(RMSNorm and rotate-half RoPE are harness/reference.py's); the tree is read
by leaf names only: `embed_tokens`, `norm`, `lm_head`, and two stacks,
`dense_layers` and `layers`, each with `input_layernorm`,
`attn_branch_norm`, `post_attention_layernorm`, `mlp_branch_norm [n, D]`,
`q_proj/k_proj/v_proj/o_proj/g_proj.kernel [n, in, out]`, `q_norm/k_norm
[n, hd]` and the MLP (`gate_proj/up_proj/down_proj.kernel [n, in, out]`, or
`router.{kernel [n, D, E], bias [n, E]}`, `experts.*.kernel` and
`shared_expert.*.kernel [n, in, out]`).

Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics:
- rows are LEFT-padded, so position ids count real tokens only and pad keys
  are masked; the window is over sequence indices, which for a row's real
  tokens are its positions plus a constant;
- attention goes in blocks of `QUERY_BLOCK` queries once a row is longer
  than that, each block against all keys under its own rows of the mask;
- the vocabulary projection goes in `HEAD_BLOCKS` column blocks.

The NEGATIVE CONTROLS of the cell's comparison are keywords, each a model
without one mechanism: `gate=False`, `attn_norm=False` / `mlp_norm=False`
(either branch norm), `nope=False` (rotary on the global layers too),
`window=False`, `bias=False`, `embed_scale=False`. Against any of them a
sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, MASKED, _rms_norm, _rope

QUERY_BLOCK = 256
HEAD_BLOCKS = 8
NORM_EPS = 1e-20        # under the chosen scores' sum


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"].astype(F32))
            * (h @ p["up_proj"]["kernel"].astype(F32))) \
        @ p["down_proj"]["kernel"].astype(F32)


def _expert_mlp(h, p, top_k: int, renorm: bool, scale: float, bias: bool,
                offset: int):
    """h [B, T, D] -> [B, T, D]: the shared expert, and every HELD expert for
    every token, one at a time, weighted by the router's chosen scores."""
    s = jax.nn.sigmoid(h @ p["router"]["kernel"].astype(F32))
    pick = s + p["router"]["bias"].astype(F32) if bias else s
    kth = jnp.sort(pick, axis=-1)[..., -top_k][..., None]
    dense_w = jnp.where(pick >= kth, s, 0.0)               # zero off the chosen
    if renorm:
        dense_w = dense_w / (jnp.sum(dense_w, axis=-1, keepdims=True) + NORM_EPS)
    dense_w = dense_w * scale
    ex = p["experts"]
    held = ex["gate_proj"]["kernel"].shape[0]
    mine = jnp.moveaxis(dense_w[..., offset:offset + held], -1, 0)  # [held, B, T]

    def one(acc, ew):
        gate, up, down, w = ew
        out = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
        return acc + w[..., None] * out, None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
         ex["down_proj"]["kernel"], mine))
    if "shared_expert" in p:
        acc = acc + _swiglu(h, p["shared_expert"])
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


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None,
                  held: int | None = None, offset: int | None = None,
                  gate: bool = True, attn_norm: bool = True,
                  mlp_norm: bool = True, nope: bool = True,
                  window: bool = True, bias: bool = True,
                  embed_scale: bool = True):
    """Final-normed hidden states [B, T, D] for left-padded token ids. The
    boolean keywords are the negative controls (module docstring)."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    top_k, renorm = cfg["num_experts_per_tok"], bool(cfg["route_norm"])
    scale = float(cfg["route_scale"])
    L, W = cfg["num_hidden_layers"], int(cfg["sliding_window"])
    dense = int(cfg.get("num_dense_layers") or 0)
    offset = int(cfg.get("num_experts_offset") or 0) if offset is None else offset
    if held is not None and "layers" in params:
        have = params["layers"]["experts"]["gate_proj"]["kernel"].shape[1]
        if have != held:
            raise ValueError(f"the tree holds {have} experts a layer, not {held}")
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    causal = (j <= i)[None, None] & mask[:, None, None, :]
    near = causal & (j > i - W)[None, None]
    x = params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
    if embed_scale:
        x = x * jnp.sqrt(F32(D))
    sliding = [t == "sliding_attention" for t in cfg["layer_types"][:L]]
    windowed = jnp.asarray([window and s for s in sliding])
    rotated = jnp.asarray([(not nope) or s for s in sliding])

    def layer(x, lp, experts: bool):
        p, is_window, is_rotated = lp
        lin = lambda h, name: h @ p[name]["kernel"].astype(F32)  # noqa: E731
        heads = lambda a, n: a.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
        h = _rms_norm(x, p["input_layernorm"], eps)
        q = _rms_norm(heads(lin(h, "q_proj"), H), p["q_norm"], eps)
        k = _rms_norm(heads(lin(h, "k_proj"), KV), p["k_norm"], eps)
        v = heads(lin(h, "v_proj"), KV)
        q = jnp.where(is_rotated, _rope(q, positions, theta), q)
        k = jnp.where(is_rotated, _rope(k, positions, theta), k)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        a = _attention(q, k, v, jnp.where(is_window, near, causal))
        a = a.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
        if gate:
            a = a * jax.nn.sigmoid(lin(h, "g_proj"))
        a = lin(a, "o_proj")
        x = x + (_rms_norm(a, p["attn_branch_norm"], eps) if attn_norm else a)
        h2 = _rms_norm(x, p["post_attention_layernorm"], eps)
        m = (_expert_mlp(h2, p, top_k, renorm, scale, bias, offset)
             if experts else _swiglu(h2, p))
        return x + (_rms_norm(m, p["mlp_branch_norm"], eps)
                    if mlp_norm else m), None

    if dense:
        x, _ = jax.lax.scan(
            lambda x, lp: layer(x, lp, False), x,
            (params["dense_layers"], windowed[:dense], rotated[:dense]))
    if L > dense:
        x, _ = jax.lax.scan(
            lambda x, lp: layer(x, lp, True), x,
            (params["layers"], windowed[dense:], rotated[dense:]))
    return _rms_norm(x, params["norm"], eps)


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, **flags):
    """Next-token logits [B, T or last, V']; `last` keeps only the final
    `last` positions before the vocabulary projection. `flags`: the share
    (`held`, `offset`) and the negative controls of `hidden_states`."""
    h = hidden_states(params, cfg, ids, pad_id, mask, **flags)
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
