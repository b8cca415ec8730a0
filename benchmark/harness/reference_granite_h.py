"""Plain reference: a Granite 4.0-H forward pass in `jax.numpy`, float32.

The layers as this repository reads the published description (the
`granite-4.0-h-small` `config.json`, `model_type: granitemoehybrid`, and the
family's modelling code, `GraniteMoeHybrid` on Bamba's Mamba-2 mixer;
docs/GRANITE_H.md and the configuration's `assumed` have every point that is
no key of the config), `x` the residual stream, a layer a Mamba-2 mixer OR an
attention (`layer_types`), a mixture of experts with a shared expert after
every one:

    x0 = embed[ids] * embedding_multiplier
    h  = rmsnorm(x; input_layernorm)
    mamba:      [z | xBC | dt] = h W_in           widths I | I + 2 G N | H
                xBC = silu(conv_K(xBC) + b_conv)  depthwise, causal; the input
                                                  is 0 before a row's first token
                [xs | B | C] = xBC                I | G N | G N
                d_t = softplus(dt_t + dt_bias);  a_t = exp(d_t * (-exp(A_log)))
                S_t[h] = a_t[h] S_(t-1)[h] + d_t[h] * xs_t[h] (outer) B_t[g(h)]
                y_t[h] = S_t[h] C_t[g(h)] + D[h] * xs_t[h]
                y = rmsnorm(y * silu(z)) over each GROUP of I / G channels, * norm
                m = y W_out
    attention:  q, k, v = h W_q, h W_k, h W_v     no rotary, no bias
                m = (softmax(q k^T * attention_multiplier + causal) v) W_o
    x  = x + m * residual_multiplier
    h2 = rmsnorm(x; post_attention_layernorm)
    r  = h2 W_router;  top k of r;  w = softmax over THOSE k logits
    e  = sum_(i in top k, held here) w_i * (silu(h2 Wg_i) * (h2 Wu_i)) Wd_i
    s  = (silu(h2 Sg) * (h2 Su)) Sd               the shared expert
    x  = x + (e + s) * residual_multiplier
    logits = (rmsnorm(x; norm) embed^T) / logits_scaling

No kernel, no cache, no chunk, no carried state: the layers one after the
other in a Python loop (their kinds differ), the recurrence as ONE `lax.scan`
over the TOKENS of the whole row from a zero state, the convolution as K
shifted multiplies over the whole row, the experts as a dense sum: every HELD
expert computed for every token, one at a time, and weighted by a [tokens]
vector that is zero where the token did not choose it. Nothing is imported
from `nanorlhf_tpu`; the tree is read by leaf names only: `embed_tokens [V,
D]`, `norm`, and `layers` with `input_layernorm`, `post_attention_layernorm
[L, D]`, `router.kernel [L, D, E]`, `experts.{gate,up,down}_proj.kernel [L,
held, in, out]`, `shared_expert.{gate,up,down}_proj.kernel [L, in, out]`,
`{q,k,v,o}_proj.kernel [attention layers, in, out]` and `ssm.{in_proj.kernel
[mixer layers, D, 2 I + 2 G N], dt_proj.kernel [.., D, H] (W_in's last H
columns, a leaf of their own in this tree), conv.kernel [.., K, I + 2 G N]
(oldest tap first), conv.bias, A_log, D, dt_bias [.., H], norm [.., I],
out_proj.kernel [.., I, D]}`.

**The chip's share**: the tree holds experts `[offset, offset + held)` of a
router of all E; the sum over `i` runs over the held ones among the chosen k
and what the absent ones would add is left out (`held`, `offset`: arguments,
by default the file's `num_experts_held` / `num_experts_offset`,
or every expert). The shared expert is whole on every chip. The vocabulary
slice is a smaller vocabulary.

Weights may arrive in bfloat16: a layer's are cast to float32 as the layer
is reached (exact), behind an `optimization_barrier` with the stream, so
that one layer's float32 copies are live at a time and not all ten's (an
expert layer's held kernels are 1.36 GB in float32; the experts go one at a
time inside a scan for the same reason). Callers wrap calls in
`jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics: rows are LEFT-padded (pad
keys masked, a pad enters the convolution as 0 and has `d_t = 0`); attention
goes in blocks of queries once a row is long; `logits` takes its rows ONE AT
A TIME (`lax.map` over the batch).

The NEGATIVE CONTROLS are names in `without`: `"rope"` (rotate-half rotary
applied on the attention layers), `"attention_multiplier"` (the scale
1 / sqrt(head_dim) in its place), `"residual_multiplier_moe"` /
`"residual_multiplier_mixer"` (the multiplier dropped on that branch),
`"shared_expert"`, `"renormalise"` (the softmax over all E logits, its chosen
k weights not renormalised), `"gate_before_norm"` (the mixer's norm BEFORE
the gate), `"attention"` (the attention layers' branch zeroed),
`"embedding_multiplier"`, `"logits_scaling"`, and `"conv_bias"`, `"D"`,
`"dt_bias"`, `"norm"` (the mixer's leaf dropped: zeros, zeros, zeros, ones).
Against any of them a sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, _rms_norm, _rope

QUERY_BLOCK = 1024
MASKED = -2.0 ** 30


def share(cfg: dict, held=None, offset=None) -> tuple:
    """(held, offset) of the routed experts: the arguments, else the file's."""
    E = int(cfg["num_local_experts"])
    held = int(cfg.get("num_experts_held") or E) if held is None else held
    offset = (int(cfg.get("num_experts_offset") or 0)
              if offset is None else offset)
    return held, offset


def _attention(q, k, v, allowed, scale):
    """q, k, v [B, H, T, hd]; allowed [B, 1, T, T] -> [B, H, T, hd], in
    blocks of queries where the row is long."""
    hd = q.shape[-1]

    def block(qb, mb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) * scale
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
    return jnp.moveaxis(out, 0, 2).reshape(
        q.shape[:2] + (n * QUERY_BLOCK, hd))[:, :, :T]


def mixer(h, mask, w, cfg: dict, without=()):
    """The Mamba-2 mixer over whole rows: (`m` [B, T, D], the recurrence's
    state `S` [B, H, P, N] after the row's last token)."""
    B, T, _ = h.shape
    H, P, G, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"],
                     cfg["mamba_d_conv"])
    I = H * P
    leaf = lambda name, dropped: (jnp.full_like(w[name], dropped, F32)   # noqa: E731
                                  if name in without else w[name].astype(F32))
    p = h @ jnp.concatenate(
        [w["in_proj"]["kernel"], w["dt_proj"]["kernel"]], axis=-1).astype(F32)
    z, xbc, dt = jnp.split(p, (I, 2 * I + 2 * G * N), axis=-1)
    xbc = jnp.where(mask[..., None], xbc, 0.0)
    back = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))       # x_{t-K+1+j} at [t + j]
    taps = w["conv"]["kernel"].astype(F32)
    bias = (jnp.zeros_like(w["conv"]["bias"], F32) if "conv_bias" in without
            else w["conv"]["bias"].astype(F32))
    xbc = jax.nn.silu(sum(taps[j] * back[:, j:j + T] for j in range(K)) + bias)
    xs, Bm, Cm = jnp.split(xbc, (I, I + G * N), axis=-1)
    Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
    d = jnp.where(mask[..., None],
                  jax.nn.softplus(dt + leaf("dt_bias", 0.0)), 0.0)  # [B, T, H]
    a = jnp.exp(d * -jnp.exp(w["A_log"].astype(F32)))
    # a group's B and C for each of its H / G heads
    heads = lambda m: jnp.repeat(m, H // G, axis=1)                     # noqa: E731

    def token(S, t):    # (xs and y travel as [B, I]: whole 128-lane rows)
        a_t, d_t, x_t, B_t, C_t = t
        x_t = x_t.reshape(B, H, P)
        S = (a_t[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * heads(B_t)[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, heads(C_t)).reshape(B, I)

    front = lambda v: jnp.moveaxis(v, 1, 0)                            # noqa: E731
    S, y = jax.lax.scan(token, jnp.zeros((B, H, P, N), F32),
                        (front(a), front(d), front(xs), front(Bm), front(Cm)))
    y = jnp.moveaxis(y, 0, 1) + (leaf("D", 0.0)[:, None]
                                 * xs.reshape(B, T, H, P)).reshape(B, T, I)

    def group_norm(y):
        y = y.reshape(B, T, G, I // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg["rms_norm_eps"])
        return y.reshape(B, T, I) * leaf("norm", 1.0)

    if "gate_before_norm" in without:       # the control: norm, THEN gate
        y = group_norm(y) * jax.nn.silu(z)
    else:
        y = group_norm(y * jax.nn.silu(z))
    return y @ w["out_proj"]["kernel"].astype(F32), S


def _swiglu(h, p):
    gate = h @ p["gate_proj"]["kernel"].astype(F32)
    up = h @ p["up_proj"]["kernel"].astype(F32)
    return (jax.nn.silu(gate) * up) @ p["down_proj"]["kernel"].astype(F32)


def moe_layer(h2, p, cfg: dict, held=None, offset=None, without=(),
              layer=None):
    """(the routed experts' part, the shared expert's) of one layer's
    mixture on the normed state `h2` [B, T, D]: `p` holds `router`,
    `experts` (`[held, in, out]` kernels) and `shared_expert` of that layer;
    with `layer` (a traced index) `experts` is every layer's `[L, held, in,
    out]` and an expert's kernels are cut out of it where it lies, one at a
    time. The routed part sums the HELD experts among each token's chosen k."""
    held, offset = share(cfg, held, offset)
    have = p["experts"]["gate_proj"]["kernel"].shape[-3]
    if have != held:
        raise ValueError(f"the tree holds {have} experts a layer, not {held}")
    k = int(cfg["num_experts_per_tok"])
    r = h2 @ p["router"]["kernel"].astype(F32)              # [B, T, E]
    top, chosen = jax.lax.top_k(r, k)
    if "renormalise" in without:    # the control: softmax over all E
        w = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), chosen, axis=-1)
    else:
        w = jax.nn.softmax(top, axis=-1)
    # dense weights [B, T, E], zero where a token did not choose the expert
    dense_w = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1], dtype=F32)
                      * w[..., None], axis=-2)
    mine = jnp.moveaxis(dense_w[..., offset:offset + held], -1, 0)

    def one(acc, ew):
        e, weight = ew
        at = (e,) if layer is None else (layer, e)
        expert = jax.tree.map(lambda a: jax.lax.dynamic_slice(
            a, at + (0, 0), (1,) * len(at) + a.shape[-2:]).reshape(
                a.shape[-2:]), p["experts"])
        return acc + weight[..., None] * _swiglu(h2, expert), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h2),
                             (jnp.arange(held), mine))
    shared = (jnp.zeros_like(h2) if "shared_expert" in without
              else _swiglu(h2, p["shared_expert"]))
    return routed, shared


def _layers(params, cfg: dict, ids, pad_id: int, mask=None, without=(),
            held=None, offset=None):
    """(final-normed hidden states [B, T, D], every MIXER layer's recurrent
    state after the last token [mixer layers, B, H, P, N]) for left-padded
    token ids."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps = cfg["rms_norm_eps"]
    one = lambda key, default: (1.0 if key in without                  # noqa: E731
                                else float(cfg.get(key, default)))
    res = float(cfg.get("residual_multiplier", 1.0))
    res_mixer = 1.0 if "residual_multiplier_mixer" in without else res
    res_moe = 1.0 if "residual_multiplier_moe" in without else res
    scale = (1.0 / hd ** 0.5 if "attention_multiplier" in without
             else float(cfg["attention_multiplier"]))
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    causal = (j <= i)[None, None] & mask[:, None, None, :]
    x = (params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
         * one("embedding_multiplier", 1.0))
    tree = params["layers"]
    at = lambda stack, n: jax.tree.map(                                 # noqa: E731
        lambda a: jax.lax.dynamic_index_in_dim(a, n, keepdims=False), stack)
    every = {k: v for k, v in tree.items()
             if k in ("input_layernorm", "post_attention_layernorm", "router",
                      "shared_expert")}
    attn = {k: tree[k] for k in ("q_proj", "k_proj", "v_proj", "o_proj")}
    states, n_mixer, n_attn = [], 0, 0
    for n, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        # (this layer's weights are cut out of the stacks, and cast, once
        # the stream has reached it: its place is known no earlier)
        x, n, here = jax.lax.optimization_barrier(
            (x, jnp.int32(n), jnp.int32(n_mixer if kind == "mamba" else n_attn)))
        p = {**at(every, n), "experts": tree["experts"]}
        own = at(tree["ssm"], here) if kind == "mamba" else at(attn, here)
        h = _rms_norm(x, p["input_layernorm"], eps)
        if kind == "mamba":
            n_mixer += 1
            m, S = mixer(h, mask, own, cfg, without)
            states.append(S)
            x = x + m * res_mixer
        else:
            n_attn += 1
            lin = lambda name: h @ own[name]["kernel"].astype(F32)     # noqa: E731
            q = lin("q_proj").reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            k = lin("k_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
            v = lin("v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
            if "rope" in without:   # the control: the published model has none
                theta = float(cfg.get("rope_theta", 1e4))
                q, k = _rope(q, positions, theta), _rope(k, positions, theta)
            a = _attention(q, jnp.repeat(k, H // KV, axis=1),
                           jnp.repeat(v, H // KV, axis=1), causal, scale)
            m = (a.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
                 @ own["o_proj"]["kernel"].astype(F32))
            x = x + (0.0 if "attention" in without else m * res)
        h2 = _rms_norm(x, p["post_attention_layernorm"], eps)
        routed, shared = moe_layer(h2, p, cfg, held, offset, without, n)
        x = x + (routed + shared) * res_moe
    return _rms_norm(x, params["norm"], eps), jnp.stack(states)


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None, without=(),
                  held=None, offset=None):
    """Final-normed hidden states [B, T, D] for left-padded token ids;
    `without`: the negative controls (module docstring)."""
    return _layers(params, cfg, ids, pad_id, mask, without, held, offset)[0]


def final_states(params, cfg: dict, ids, pad_id: int, mask=None):
    """The recurrent state a MIXER layer after the rows' last token, [mixer
    layers, B, H, P, N] in float32: what a cache that has taken `ids` in, in
    however many pieces and steps, should hold for the row."""
    return _layers(params, cfg, ids, pad_id, mask)[1]


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, without=(), held=None, offset=None):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    if ids.shape[0] > 1:        # a row at a time (module docstring)
        mask = (ids != pad_id) if mask is None else mask
        return jax.lax.map(lambda row: logits(
            params, cfg, row[0][None], pad_id, last, row[1][None], without,
            held, offset)[0], (ids, mask))
    h = hidden_states(params, cfg, ids, pad_id, mask, without, held, offset)
    if last is not None:
        h = h[:, -last:]
    divisor = (1.0 if "logits_scaling" in without
               else float(cfg.get("logits_scaling", 1.0)))
    head = (params["embed_tokens"].T if cfg.get("tie_word_embeddings", True)
            else params["lm_head"])
    return (h @ head.astype(F32)) / divisor
