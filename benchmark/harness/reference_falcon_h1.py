"""Plain reference: a Falcon-H1 forward pass in `jax.numpy`, float32.

The layer as this repository reads the published description (the
34B-Instruct `config.json`, `model_type: falcon_h1`, and the family's
modelling code; docs/SSM.md has every assumed point), `x` the residual
stream, EVERY layer the same, every multiplier a key of the config:

    x0 = embed[ids] * embedding_multiplier
    h  = rmsnorm(x; input_layernorm)
    attention:  q = (h*attention_in_multiplier) W_q
                k = (h*attention_in_multiplier) W_k * key_multiplier
                v = (h*attention_in_multiplier) W_v     no biases, no q/k norm
                rotate-half RoPE; a = softmax(q k^T / sqrt(hd) + causal) v
                A = (a W_o) * attention_out_multiplier
    mixer:      p = ((h*ssm_in_multiplier) W_in) * mup
                [z | xs | B | C | dt] = p    widths I | I | G N | G N | H;
                    mup: ssm_multipliers[0..4] over those five parts
                [xs | B | C] = silu(conv_K([xs | B | C]) + b_conv)   depthwise, causal;
                    the input is 0 before the row's first real token
                d_t = softplus(dt_t + dt_bias);  a_t = exp(d_t * (-exp(A_log)))
                S_t[h] = a_t[h] S_(t-1)[h] + d_t[h] * xs_t[h] (outer) B_t[g(h)]
                y_t[h] = S_t[h] C_t[g(h)] + D[h] * xs_t[h]
                y = y * silu(z);  y = rmsnorm over each GROUP of I / G channels, * norm
                M = (y W_out) * ssm_out_multiplier
    x  = x + A + M                    one residual, both read the same h
    h2 = rmsnorm(x; post_attention_layernorm)          (HF: pre_ff_layernorm)
    x  = x + ((silu((h2 W_g) * mlp_multipliers[0]) * (h2 W_u)) W_d) * mlp_multipliers[1]
    logits = (rmsnorm(x; norm) W_head) * lm_head_multiplier

No kernel, no cache, no chunk, no carried state: ONE scan over the layers,
and inside it the recurrence as ONE `lax.scan` over the TOKENS of the whole
row from a zero state, the convolution as K shifted multiplies over the whole
row. Nothing is imported from `nanorlhf_tpu` (RMSNorm and rotate-half RoPE
are harness/reference.py's, the attention in blocks of queries
harness/reference_lfm2.py's); the tree is read by leaf names only:
`embed_tokens [V, D]`, `norm`, `lm_head [D, V]`, and `layers` with
`input_layernorm`, `post_attention_layernorm [L, D]`,
`{q,k,v,o,gate,up,down}_proj.kernel [L, in, out]` and `ssm.{in_proj.kernel
[L, D, 2 I + 2 G N], dt_proj.kernel [L, D, H] (W_in's last H columns, a leaf
of their own in this tree), conv.kernel [L, K, I + 2 G N] (oldest tap first),
conv.bias, A_log, D, dt_bias [L, H], norm [L, I], out_proj.kernel [L, I, D]}`.

Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room and none for numerics:
- rows are LEFT-padded, so position ids count real tokens only, pad keys are
  masked, a pad enters the convolution as 0 and has `d_t = 0` (what stands
  before a row's first token: nothing);
- attention goes in blocks of queries once a row is long;
- `logits` takes its rows ONE AT A TIME (`lax.map` over the batch: a row
  sees no other row), so that a part of many rows holds one row's float32
  activations beside a served model (eight rows of 576 at once wanted 3.3
  GB of temporaries, a row 0.6; compiled for a described v5e, PR 49);
- the vocabulary projection goes in `HEAD_BLOCKS` column blocks, each cut
  out of the stored head where it lies (the head is 2.67 GB in bfloat16).

The NEGATIVE CONTROLS are names in `without`: `"mixer"` / `"attention"` (the
branch zeroed), `"mup"` (the five-part vector left out), any single
multiplier by its config key (`mlp_multipliers` as `"mlp_gate_multiplier"` /
`"mlp_down_multiplier"`), and `"conv_bias"`, `"D"`, `"dt_bias"`, `"norm"`
(the mixer's leaf dropped: zeros, zeros, zeros, ones). Against any of them
a sound system must read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, _rms_norm, _rope
from harness.reference_lfm2 import _attention

HEAD_BLOCKS = 16


def multipliers(cfg: dict, without=()) -> dict:
    """The config's fourteen numbers by the names `without` takes."""
    gate, down = cfg.get("mlp_multipliers") or (1.0, 1.0)
    m = {key: float(cfg.get(key, 1.0)) for key in (
        "embedding_multiplier", "attention_in_multiplier", "key_multiplier",
        "attention_out_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
        "lm_head_multiplier")}
    m.update(mlp_gate_multiplier=float(gate), mlp_down_multiplier=float(down))
    m = {key: 1.0 if key in without else value for key, value in m.items()}
    m["mup"] = tuple(1.0 if "mup" in without else float(part)
                     for part in cfg.get("ssm_multipliers") or (1.0,) * 5)
    return m


def _mixer(h, mask, w, cfg: dict, m: dict, without):
    """The state-space mixer over whole rows: (`M` before `ssm_out`, the
    recurrence's state `S` [B, H, P, N] after the row's last token)."""
    B, T, _ = h.shape
    H, P, G, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"],
                     cfg["mamba_d_conv"])
    I = H * P
    leaf = lambda name, dropped: (jnp.full_like(w[name], dropped, F32)   # noqa: E731
                                  if name in without else w[name].astype(F32))
    p = (h * m["ssm_in_multiplier"]) @ jnp.concatenate(
        [w["in_proj"]["kernel"], w["dt_proj"]["kernel"]], axis=-1).astype(F32)
    p = p * jnp.concatenate([jnp.full((width,), part, F32) for width, part in
                             zip((I, I, G * N, G * N, H), m["mup"])])
    z, xbc, dt = jnp.split(p, (I, 2 * I + 2 * G * N), axis=-1)
    xbc = jnp.where(mask[..., None], xbc, 0.0)
    back = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))       # x_{t-K+1+j} at [t + j]
    taps = w["conv"]["kernel"].astype(F32)
    bias = (jnp.zeros_like(w["conv"]["bias"], F32) if "conv_bias" in without
            else w["conv"]["bias"].astype(F32))
    xbc = jax.nn.silu(sum(taps[j] * back[:, j:j + T] for j in range(K)) + bias)
    xs, Bm, Cm = jnp.split(xbc, (I, I + G * N), axis=-1)
    xs = xs.reshape(B, T, H, P)
    # a group's B and C for each of its H / G heads
    heads = lambda a: jnp.repeat(a.reshape(B, T, G, N), H // G, axis=2)  # noqa: E731
    Bm, Cm = heads(Bm), heads(Cm)
    d = jnp.where(mask[..., None],
                  jax.nn.softplus(dt + leaf("dt_bias", 0.0)), 0.0)  # [B, T, H]
    a = jnp.exp(d * -jnp.exp(w["A_log"].astype(F32)))

    def token(S, t):
        a_t, d_t, x_t, B_t, C_t = t
        S = (a_t[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    front = lambda v: jnp.moveaxis(v, 1, 0)                            # noqa: E731
    S, y = jax.lax.scan(token, jnp.zeros((B, H, P, N), F32),
                        (front(a), front(d), front(xs), front(Bm), front(Cm)))
    y = jnp.moveaxis(y, 0, 1) + leaf("D", 0.0)[:, None] * xs
    y = y.reshape(B, T, I) * jax.nn.silu(z)
    y = y.reshape(B, T, G, I // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return (y.reshape(B, T, I) * leaf("norm", 1.0)) \
        @ w["out_proj"]["kernel"].astype(F32), S


def _layers(params, cfg: dict, ids, pad_id: int, mask=None, without=()):
    """(final-normed hidden states [B, T, D], every layer's recurrent state
    after the last token [L, B, H, P, N]) for left-padded token ids."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    m = multipliers(cfg, without)
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    causal = (j <= i)[None, None] & mask[:, None, None, :]
    x = (params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
         * m["embedding_multiplier"])

    def layer(x, p):
        h = _rms_norm(x, p["input_layernorm"], eps)
        ha = h * m["attention_in_multiplier"]
        lin = lambda name: ha @ p[name]["kernel"].astype(F32)          # noqa: E731
        q = lin("q_proj").reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = (lin("k_proj") * m["key_multiplier"]).reshape(
            B, T, KV, hd).transpose(0, 2, 1, 3)
        v = lin("v_proj").reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        a = _attention(q, jnp.repeat(k, H // KV, axis=1),
                       jnp.repeat(v, H // KV, axis=1), causal)
        A = (a.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
             @ p["o_proj"]["kernel"].astype(F32)) * m["attention_out_multiplier"]
        M, S = _mixer(h, mask, p["ssm"], cfg, m, without)
        M = M * m["ssm_out_multiplier"]
        x = (x + (0.0 if "attention" in without else A)
             + (0.0 if "mixer" in without else M))
        h2 = _rms_norm(x, p["post_attention_layernorm"], eps)
        gate = (h2 @ p["gate_proj"]["kernel"].astype(F32)) * m["mlp_gate_multiplier"]
        ff = (jax.nn.silu(gate) * (h2 @ p["up_proj"]["kernel"].astype(F32))) \
            @ p["down_proj"]["kernel"].astype(F32)
        return x + ff * m["mlp_down_multiplier"], S

    x, states = jax.lax.scan(layer, x, params["layers"])
    return _rms_norm(x, params["norm"], eps), states


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None, without=()):
    """Final-normed hidden states [B, T, D] for left-padded token ids;
    `without`: the negative controls (module docstring)."""
    return _layers(params, cfg, ids, pad_id, mask, without)[0]


def final_states(params, cfg: dict, ids, pad_id: int, mask=None):
    """The recurrent state a layer after the rows' last token, [L, B, H, P,
    N] in float32: what a cache that has taken `ids` in, in however many
    pieces and steps, should hold for the row."""
    return _layers(params, cfg, ids, pad_id, mask)[1]


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, without=()):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    if ids.shape[0] > 1:        # a row at a time (module docstring)
        mask = (ids != pad_id) if mask is None else mask
        return jax.lax.map(lambda row: logits(
            params, cfg, row[0][None], pad_id, last, row[1][None], without)[0],
            (ids, mask))
    h = hidden_states(params, cfg, ids, pad_id, mask, without)
    if last is not None:
        h = h[:, -last:]
    scale = multipliers(cfg, without)["lm_head_multiplier"]
    head = (params["embed_tokens"].T if cfg.get("tie_word_embeddings")
            else params["lm_head"])
    V = head.shape[1]
    if V % HEAD_BLOCKS or V < 65536:
        return (h @ head.astype(F32)) * scale
    width = V // HEAD_BLOCKS
    out = jax.lax.map(lambda at: h @ jax.lax.dynamic_slice_in_dim(
        head, at * width, width, axis=1).astype(F32), jnp.arange(HEAD_BLOCKS))
    return jnp.moveaxis(out, 0, 2).reshape(h.shape[:2] + (V,)) * scale
