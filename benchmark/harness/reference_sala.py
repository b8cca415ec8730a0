"""Plain reference: a MiniCPM-SALA forward pass in `jax.numpy`, float32.

The layers as this repository reads the published description
(`openbmb/MiniCPM-SALA` `config.json`, `model_type: minicpm_sala`; the two
mixers by the papers the config names them after; docs/SALA.md has every
assumed point), `x` the residual stream, `mixer_types[l]` the layer's mixer:

    x0 = embed[ids] * scale_emb
    r  = scale_depth / sqrt(published depth)
    h  = rmsnorm(x; input_layernorm)
    "lightning-attn":  q, k, v = h W_q, h W_k, h W_v      H heads of hd
        q, k: rmsnorm over each head (q_norm, k_norm [hd]); rotate-half RoPE
        S_t[h] = lam[h] S_(t-1)[h] + k_t[h] (outer) v_t[h]
        o_t[h] = q_t[h] S_t[h] / sqrt(hd)
        lam[h] = exp(-2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)),
                 l the PUBLISHED layer index, L the published depth
        o = rmsnorm over each head's hd channels, * o_norm [H hd]
        o = o * sigmoid(h W_z);   x = x + r * (o W_o)
    "minicpm4":  q, k, v = h W_q, h W_k, h W_v      H / KV heads of hd, no RoPE
        q, k: rmsnorm over each head (q_norm, k_norm [hd])
        a row of n < dense_len real tokens: a = softmax(q k^T / sqrt(hd) + causal) v
        else, for the query at position t, KV head g:
          kc_j = mean(k[stride j : stride j + kernel_size])    whole windows
          p_h  = softmax over {j : stride j + kernel_size - 1 <= t} of q_h . kc_j / sqrt(hd)
          P_j  = sum of p_h over the query heads of g
          score[b] = max(P_j : per b - 1 <= j <= per b + per - 1),  per = block / stride
          chosen = the first init_blocks, the window / block blocks that end
                   at t's own, the best of the rest by score: topk in all
          a = softmax over the causal slots of the chosen blocks (q_h k^T / sqrt(hd)) v
        a = a * sigmoid(h W_g);   x = x + r * (a W_o)
    h2 = rmsnorm(x; post_attention_layernorm)
    x  = x + r * ((silu(h2 W_gate) * (h2 W_up)) W_down)
    logits = (rmsnorm(x; norm) / (hidden / dim_model_base)) W_head

No kernel, no cache, no chunk, no carried state: the lightning recurrence is
ONE `lax.scan` over the TOKENS of the row from a zero state; the sparse
layer recomputes compressed keys, scores, pooling and top-k for every query
from the row's full K. Nothing is imported from `nanorlhf_tpu` (RMSNorm and
rotate-half RoPE are harness/reference.py's); the tree is read by leaf names
only: `embed_tokens [V, D]`, `norm`, `lm_head [D, V]`, and `layers` with
`input_layernorm`, `post_attention_layernorm`, `{gate,up,down}_proj.kernel`
over every layer, `{q,k,v,o,g}_proj.kernel`, `q_norm`, `k_norm` over the
SPARSE layers in order, and `lightning.{q,k,v,z,o}_proj.kernel`,
`lightning.{q,k,o}_norm` over the lightning layers in order.

Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics:
- a row comes LEFT-padded and is turned so that its first real token is at
  index 0 (`jnp.roll`), computed there with the pads behind it masked, and
  turned back; the rows of a call, which do not see one another, go side
  by side (`jax.vmap` of the one row's function: a token of the recurrence
  is then one step for every row, where rows one at a time made the
  comparison's set-up a scan step a row a token; the CALLER bounds the
  slots of a call);
- the sparse layer goes in blocks of `QUERY_BLOCK` queries, the MLP in
  blocks of `TOKEN_BLOCK` tokens and the lightning recurrence `HEAD_GROUP`
  heads at a time (two scans over the tokens a layer: at the cell's long
  row of 21 k tokens the float32 q, k, v and o of sixteen heads are 0.7 GB,
  the program's temporaries 2.8 GB of the 5 the served engine leaves free),
  each query, token and head as written;
- the vocabulary projection goes in `HEAD_BLOCKS` column blocks.

The NEGATIVE CONTROLS are names in `without`: `"selection"` (the chosen
blocks replaced by the newest `topk`), `"group_sum"` (each query head
selects for itself: the first head of a group speaks for it), `"dense_len"`
(every row selects), `"decay"` (lam = 1), `"scale_depth"` (r = 1),
`"scale_emb"`, `"logit_scale"`, `"lightning"` / `"sparse"` (the mixer's
branch zeroed), `"gate"` (no output gates), `"o_norm"`. Against any of them
a sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, _rms_norm, _rope

HEAD_BLOCKS = 8
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
HEAD_GROUP = 16
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def sizes(cfg: dict) -> dict:
    """The sparse layer's sizes, `sparse_config`'s under short names."""
    s = cfg["sparse_config"]
    return {"stride": int(s["kernel_stride"]), "ksize": int(s["kernel_size"]),
            "block": int(s["block_size"]), "topk": int(s["topk"]),
            "init": int(s["init_blocks"]), "window": int(s["window_size"]),
            "dense_len": int(s["dense_len"])}


def log_decays(cfg: dict, without=()):
    """`log lam` [lightning layers, H], by the published layer index."""
    H = int(cfg["lightning_nh"])
    depth = int(cfg.get("published_layers") or cfg["num_hidden_layers"])
    first = int(cfg.get("first_published_layer") or 0)
    slopes = 2.0 ** (-8.0 * (jnp.arange(H, dtype=F32) + 1) / H)
    rows = [-slopes * (1.0 - (first + l) / max(depth - 1, 1) + 1e-5)
            for l, m in enumerate(cfg["mixer_types"]) if MIXERS[m] == "lightning"]
    out = jnp.stack(rows)
    return jnp.zeros_like(out) if "decay" in without else out


def _blocked(fn, size: int, *arrays):
    """`fn(*blocks)` over the arrays' common leading axis in blocks of
    `size` (the last padded)."""
    n = arrays[0].shape[0]
    parts = -(-n // size)
    if parts == 1:
        return fn(*arrays)
    cut = lambda x: jnp.pad(x, ((0, parts * size - n),) + ((0, 0),) * (x.ndim - 1)  # noqa: E731
                            ).reshape((parts, size) + x.shape[1:])
    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in arrays))
    return out.reshape((parts * size,) + out.shape[2:])[:n]


def _lightning(h, n, w, cfg, log_lam, without):
    """One row's lightning mixer, `h` [T, D] with the real tokens first:
    `(the branch before W_o [T, H hd], S [H, hd(v), hd(k)] after token n -
    1)`. The heads, which do not see one another, go `HEAD_GROUP` at a
    time."""
    T = h.shape[0]
    H, hd = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    hg = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    positions = jnp.arange(T)[None]
    real = jnp.arange(T) < n
    # a group's columns of a kernel [D, H hd] -> [H / hg, D, hg hd]
    cols = lambda name: jnp.moveaxis(w[name]["kernel"].reshape(      # noqa: E731
        -1, H // hg, hg * hd), 1, 0)

    def group(part):
        Wq, Wk, Wv, lam = part
        heads = lambda W: (h @ W.astype(F32)).reshape(                # noqa: E731
            T, hg, hd).transpose(1, 0, 2)[None]
        q = _rope(_rms_norm(heads(Wq), w["q_norm"], eps), positions, theta)[0]
        k = _rope(_rms_norm(heads(Wk), w["k_norm"], eps), positions, theta)[0]
        v = heads(Wv)[0]                                        # [hg, T, hd]

        def token(S, t):
            q_t, k_t, v_t, real_t = t
            new = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
            S = jnp.where(real_t, new, S)               # S [hg, hd(k), hd(v)]
            return S, jnp.einsum("hk,hkv->hv", q_t, S) / hd ** 0.5

        front = lambda a: jnp.moveaxis(a, 1, 0)                      # noqa: E731
        return jax.lax.scan(token, jnp.zeros((hg, hd, hd), F32),
                            (front(q), front(k), front(v), real))

    S, o = jax.lax.map(group, (cols("q_proj"), cols("k_proj"), cols("v_proj"),
                               jnp.exp(log_lam).reshape(H // hg, hg)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H, hd)         # [groups, T, hg, hd]
    S = S.reshape(H, hd, hd)
    if "o_norm" not in without:
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
             ).reshape(T, H * hd) * w["o_norm"].astype(F32)
    o = o.reshape(T, H * hd)
    if "gate" not in without:
        o = o * jax.nn.sigmoid(h @ w["z_proj"]["kernel"].astype(F32))
    return o, S.transpose(0, 2, 1)


def _select(q, t, k, cfg: dict, without=()):
    """The blocks the queries choose: `q` [H, Tq, hd] at positions `t` [Tq],
    `k` [KV, T, hd] the row's keys by position. Returns `(chosen [KV, Tq,
    NB] bool, score [KV, Tq, NB])`; every query's own causal limit is
    applied by the caller."""
    z = sizes(cfg)
    stride, ksize, block = z["stride"], z["ksize"], z["block"]
    per = block // stride
    KV, T, hd = k.shape
    H, Tq, _ = q.shape
    G = H // KV
    Nc = max((T - ksize) // stride + 1, 1)
    NB = -(-T // block)
    at = stride * jnp.arange(Nc)[:, None] + jnp.arange(ksize)[None, :]
    kc = jnp.mean(k[:, jnp.minimum(at, T - 1)], axis=2)             # [KV, Nc, hd]
    s = jnp.einsum("kgqh,kch->kgqc", q.reshape(KV, G, Tq, hd), kc) / hd ** 0.5
    ended = (stride * jnp.arange(Nc) + ksize - 1)[None, :] <= t[:, None]
    p = jax.nn.softmax(jnp.where(ended[None, None], s, -jnp.inf), axis=-1)
    p = jnp.where(ended[None, None], p, 0.0)        # (no window yet: nothing)
    mass = p[:, 0] * G if "group_sum" in without else jnp.sum(p, axis=1)
    j = per * jnp.arange(NB)[:, None] + jnp.arange(-1, per)[None, :]  # [NB, per+1]
    pooled = jnp.where((j >= 0) & (j < Nc), mass[..., jnp.clip(j, 0, Nc - 1)], 0.0)
    score = jnp.max(pooled, axis=-1)                                # [KV, Tq, NB]
    b = jnp.arange(NB)[None, :]
    own = (t // block)[:, None]
    seen = b <= own
    if "selection" in without:      # the newest `topk` blocks, whatever scores
        return jnp.broadcast_to(seen & (b > own - z["topk"]),
                                (KV, Tq, NB)), score
    forced = seen & ((b < z["init"]) | (b > own - z["window"] // block))
    ranked = jnp.where(forced, jnp.inf, jnp.where(seen, score, -jnp.inf))
    kth = min(z["topk"], NB)
    vals, idx = jax.lax.top_k(ranked, kth)
    hit = (idx[..., None] == jnp.arange(NB)) & (vals > -jnp.inf)[..., None]
    return jnp.any(hit, axis=-2), score


def _sparse(h, n, w, cfg, without, decoded: int = 0):
    """One row's sparse layer, `h` [T, D] with the real tokens first: the
    branch before W_o [T, H hd]. The row's last `decoded` real tokens were
    decode steps, each a call of its own that holds the row up to itself;
    the tokens before them were ONE call (a prompt)."""
    T = h.shape[0]
    H, KV = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    eps, z = cfg["rms_norm_eps"], sizes(cfg)
    lin = lambda name: h @ w[name]["kernel"].astype(F32)            # noqa: E731
    q = _rms_norm(lin("q_proj").reshape(T, H, hd), w["q_norm"], eps).transpose(1, 0, 2)
    k = _rms_norm(lin("k_proj").reshape(T, KV, hd), w["k_norm"], eps).transpose(1, 0, 2)
    v = lin("v_proj").reshape(T, KV, hd).transpose(1, 0, 2)
    pos = jnp.arange(T)
    keys = jnp.where(pos < n - decoded, n - decoded, pos + 1)   # of t's call
    selects = (keys >= 0 if "dense_len" in without
               else keys >= z["dense_len"])

    def some(qb, tb):                                   # [bq, H, hd], [bq]
        qb = qb.transpose(1, 0, 2)
        chosen, _ = _select(qb, tb, k, cfg, without)
        allowed = jnp.repeat(chosen, z["block"], axis=-1)[..., :T]  # [KV, bq, T]
        allowed = jnp.where(selects[tb][None, :, None], allowed, True)
        allowed = allowed & (pos[None, None, :] <= tb[None, :, None])
        s = jnp.einsum("kgqh,kth->kgqt", qb.reshape(KV, H // KV, -1, hd), k) / hd ** 0.5
        a = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,kth->kgqh", a, v).reshape(H, -1, hd).transpose(1, 0, 2)

    out = _blocked(some, QUERY_BLOCK, q.transpose(1, 0, 2), pos)
    out = out.reshape(T, H * hd)
    if "gate" not in without:
        out = out * jax.nn.sigmoid(lin("g_proj"))
    return out


def _row(params, cfg: dict, ids, mask, without=(), decoded: int = 0):
    """One row [T] (left-padded): `(final-normed hidden [T, D] in the row's
    own order, the lightning layers' states [Ll, H, hd, hd])`."""
    T = ids.shape[0]
    D = int(cfg["hidden_size"])
    eps = cfg["rms_norm_eps"]
    depth = int(cfg.get("published_layers") or cfg["num_hidden_layers"])
    r = 1.0 if "scale_depth" in without else float(cfg["scale_depth"]) / depth ** 0.5
    n = jnp.sum(mask)
    start = T - n                       # left-padded: the first real token
    ids = jnp.roll(jnp.where(mask, ids, 0), -start)
    x = params["embed_tokens"][ids].astype(F32)
    if "scale_emb" not in without:
        x = x * float(cfg["scale_emb"])
    L = params["layers"]
    lam = log_decays(cfg, without)
    at = {"sparse": 0, "lightning": 0}
    states = []
    for l, mixer in enumerate(cfg["mixer_types"]):
        kind = MIXERS[mixer]
        i = at[kind]
        at[kind] += 1
        # (a layer's weights are cast to float32 once the stream has reached
        # it, not all layers' at the start: 0.8 GB a layer at these widths)
        x, L = jax.lax.optimization_barrier((x, L))
        h = _rms_norm(x, L["input_layernorm"][l], eps)
        if kind == "lightning":
            w = jax.tree.map(lambda a: a[i], L["lightning"])
            o, S = _lightning(h, n, w, cfg, lam[i], without)
            states.append(S)
        else:
            w = {name: jax.tree.map(lambda a: a[i], L[name]) for name in (
                "q_proj", "k_proj", "v_proj", "o_proj", "g_proj", "q_norm",
                "k_norm")}
            o = _sparse(h, n, w, cfg, without, decoded)
        if kind not in without:
            x = x + r * (o @ w["o_proj"]["kernel"].astype(F32))
        h2 = _rms_norm(x, L["post_attention_layernorm"][l], eps)
        gate, up, down = (L[name]["kernel"][l] for name in (
            "gate_proj", "up_proj", "down_proj"))
        ff = _blocked(lambda t: (jax.nn.silu(t @ gate.astype(F32))
                                 * (t @ up.astype(F32))) @ down.astype(F32),
                      TOKEN_BLOCK, h2)
        x = x + r * ff
    x = _rms_norm(x, params["norm"], eps)
    return jnp.roll(x, start, axis=0), jnp.stack(states)


def _rows(params, cfg: dict, ids, pad_id: int, mask, without, decoded: int):
    """`_row` for every row of `ids` [B, T], side by side."""
    mask = (ids != pad_id) if mask is None else mask
    return jax.vmap(lambda row, real: _row(params, cfg, row, real, without,
                                           decoded))(ids, mask)


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None, without=(),
                  decoded: int = 0):
    """Final-normed hidden states [B, T, D] for left-padded token ids;
    `decoded`: every row's last so many tokens were decode steps
    (`_sparse`)."""
    return _rows(params, cfg, ids, pad_id, mask, without, decoded)[0]


def final_states(params, cfg: dict, ids, pad_id: int, mask=None,
                 decoded: int = 0):
    """The lightning layers' state after the rows' last token, `[Ll, B, H,
    hd(v), hd(k)]` float32 (the system's layout): what a cache that has
    taken `ids` in, in however many pieces and steps, should hold."""
    return jnp.moveaxis(_rows(params, cfg, ids, pad_id, mask, (), decoded)[1],
                        0, 1)


def first_layer_selection(params, cfg: dict, ids, pad_id: int, last: int):
    """The FIRST layer's selection for the row's last `last` queries (the
    first layer of the configuration is a sparse one and reads the
    embedding alone, so its q and k are every implementation's): `(q [H,
    last, hd], k [KV, n, hd], t [last], chosen [KV, last, NB], score [KV,
    last, NB])` for ONE left-padded row [1, T] without pads inside."""
    if MIXERS[cfg["mixer_types"][0]] != "sparse":
        raise ValueError("the first layer is not a sparse one")
    row = ids[0]
    mask = row != pad_id
    T = row.shape[0]
    n = jnp.sum(mask)
    x = params["embed_tokens"][jnp.roll(jnp.where(mask, row, 0), n - T)].astype(F32)
    x = x * float(cfg["scale_emb"])
    L = params["layers"]
    H, KV = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, L["input_layernorm"][0], eps)
    q = _rms_norm((h @ L["q_proj"]["kernel"][0].astype(F32)).reshape(T, H, hd),
                  L["q_norm"][0], eps).transpose(1, 0, 2)
    k = _rms_norm((h @ L["k_proj"]["kernel"][0].astype(F32)).reshape(T, KV, hd),
                  L["k_norm"][0], eps).transpose(1, 0, 2)
    t = n - last + jnp.arange(last)
    qs = jax.lax.dynamic_slice_in_dim(q, n - last, last, axis=1)
    chosen, score = _select(qs, t, k, cfg)
    return qs, k, t, chosen, score


def _head(params, cfg: dict, h, last, without):
    if last is not None:
        h = h[:, -last:]
    if "logit_scale" not in without:
        h = h / (float(cfg["hidden_size"]) / float(cfg["dim_model_base"]))
    head = (params["embed_tokens"].T if cfg.get("tie_word_embeddings")
            else params["lm_head"])
    V = head.shape[1]
    if V % HEAD_BLOCKS or V < 65536:
        return h @ head.astype(F32)
    width = V // HEAD_BLOCKS
    out = jax.lax.map(lambda at: h @ jax.lax.dynamic_slice_in_dim(
        head, at * width, width, axis=1).astype(F32), jnp.arange(HEAD_BLOCKS))
    return jnp.moveaxis(out, 0, 2).reshape(h.shape[:2] + (V,))


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, without=(), decoded: int = 0):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection; `decoded` as
    `hidden_states`'."""
    h = hidden_states(params, cfg, ids, pad_id, mask, without, decoded)
    return _head(params, cfg, h, last, without)


def logits_and_states(params, cfg: dict, ids, pad_id: int,
                      last: int | None = None, mask=None, without=(),
                      decoded: int = 0):
    """`logits` and, from the same pass, `final_states` by row, `[B, Ll, H,
    hd(v), hd(k)]`."""
    h, states = _rows(params, cfg, ids, pad_id, mask, without, decoded)
    return _head(params, cfg, h, last, without), states
