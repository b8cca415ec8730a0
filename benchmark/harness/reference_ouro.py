"""Plain reference: an Ouro (`model_type: ouro`, a looped language model,
arXiv:2510.25741) forward pass in `jax.numpy`, float32.

The model as this repository reads the published description (the
`config.json` of ByteDance/Ouro-2.6B for every size; the paper and the
published `modeling_ouro.py` for what the file has no key for, each listed
in the configuration file under `assumed`), `x` the residual stream:

    h_0 = E[ids]
    for t = 1 .. total_ut_steps:                  the SAME L layers each pass
        x = h_{t-1}
        for l = 1 .. L:
            a = Attn_l(rmsnorm(x; input_layernorm))        no biases
            x = x + rmsnorm(a; input_layernorm_2)          norms the BRANCH
            m = SwiGLU_l(rmsnorm(x; post_attention_layernorm))
            x = x + rmsnorm(m; post_attention_layernorm_2) norms the BRANCH
        h_t = rmsnorm(x; norm)                    the SAME final norm, INSIDE
        lam_t = sigmoid(w_g . h_t + b_g)          the exit gate, with bias
    logits = h_T W_head                           no further norm; untied

    Attn: causal MHA, rotate-half RoPE on q and k over the whole head
    (theta 1e6), scores / sqrt(head_dim);  SwiGLU(h) = W_down(silu(W_gate h)
    * (W_up h)).

    p(t) = lam_t prod_{j<t} (1 - lam_j) for t < T,  p(T) = prod_{j<T} (1 -
    lam_j); a token exits at the first t with sum_{i<=t} p(i) >= q
    (`early_exit_threshold`; the published q = 1 is never reached before T).

Pass `t` of layer `l` attends to the keys and values that pass `t` of layer
`l` computed for the earlier tokens, and to nothing of another pass: with no
cache that is simply the full causal forward of each pass over the whole
row, which is what stands here.

No kernel, no cache, no batching trick, nothing imported from
`nanorlhf_tpu` (RMSNorm and RoPE are harness/reference.py's, the causal
attention in blocks of queries harness/reference_trinity.py's); the tree is
read by leaf names only: `embed_tokens [V, D]`, `norm [D]`, `lm_head [D,
V]`, `early_exit_gate.{kernel [D, 1], bias [1]}`, and `layers.*` stacked on
a leading layer axis: `input_layernorm`, `attn_branch_norm` (HF
`input_layernorm_2`), `post_attention_layernorm`, `mlp_branch_norm` (HF
`post_attention_layernorm_2`) `[L, D]`, `q_proj/k_proj/v_proj/o_proj/
gate_proj/up_proj/down_proj.kernel [L, in, out]`, and optionally
`lora.layers.<proj>.{a, b}` (one adapter a layer, used by every pass).

Weights may arrive in bfloat16: a layer's are cast to float32 as the layer
is computed (exact), one layer at a time inside a scan, so the reference
holds no float32 copy of the model and fits beside the served engine at the
published sizes. Callers wrap calls in
`jax.default_matmul_precision("highest")`.

Departures from the published description, each for room and none for
numerics:
- rows are LEFT-padded, so position ids count real tokens only and pad keys
  are masked; a real row without pads is computed exactly as published;
- attention goes in blocks of 256 queries once a row is longer than that,
  each block against all keys under its own rows of the mask.

The NEGATIVE CONTROLS of the cell's comparison are keywords, each another
model: `passes=n` (another number of passes), `pass_norm=False` (the final
norm once, after the last pass only), `attn_norm=False` / `mlp_norm=False`
(either branch norm left out). Against any of them a sound system must read
as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import F32, _linear, _rms_norm, _rope
from harness.reference_trinity import _attention


def passes_hidden(params, cfg: dict, ids, pad_id: int, mask=None,
                  lora_scale: float = 1.0, passes: int | None = None,
                  pass_norm: bool = True, attn_norm: bool = True,
                  mlp_norm: bool = True):
    """Every pass's normed state, `h_1 .. h_T` stacked `[T, B, S, D]`, for
    left-padded token ids. The keywords after `lora_scale` are the negative
    controls (module docstring)."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n_pass = int(cfg["total_ut_steps"]) if passes is None else int(passes)
    mask = (ids != pad_id) if mask is None else mask
    positions = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    B, T = ids.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    allowed = causal[None, None] & mask[:, None, None, :]
    x = params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
    lora_layers = params.get("lora", {}).get("layers", {})

    def layer(x, lp):
        p, lo = lp
        # (a layer's bf16 weights are cast here, inside the scan's body: no
        # float32 copy of the stack is ever made)
        p = jax.lax.optimization_barrier(p)
        lin = lambda h, name: _linear(h, p[name], lo.get(name), lora_scale)  # noqa: E731
        heads = lambda a, n: a.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
        h = _rms_norm(x, p["input_layernorm"], eps)
        q = _rope(heads(lin(h, "q_proj"), H), positions, theta)
        k = _rope(heads(lin(h, "k_proj"), KV), positions, theta)
        v = heads(lin(h, "v_proj"), KV)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        a = _attention(q, k, v, allowed)
        a = lin(a.transpose(0, 2, 1, 3).reshape(B, T, H * hd), "o_proj")
        x = x + (_rms_norm(a, p["attn_branch_norm"], eps) if attn_norm else a)
        h = _rms_norm(x, p["post_attention_layernorm"], eps)
        m = lin(jax.nn.silu(lin(h, "gate_proj")) * lin(h, "up_proj"),
                "down_proj")
        return x + (_rms_norm(m, p["mlp_branch_norm"], eps)
                    if mlp_norm else m), None

    states = []
    for t in range(n_pass):     # the SAME layers, and the SAME final norm
        x, _ = jax.lax.scan(layer, x, (params["layers"], lora_layers))
        if pass_norm or t == n_pass - 1:
            x = _rms_norm(x, params["norm"], eps)
        states.append(x)
    return jnp.stack(states)


def hidden_states(params, cfg: dict, ids, pad_id: int, mask=None, **flags):
    """The last pass's normed state [B, S, D]: what the head takes."""
    return passes_hidden(params, cfg, ids, pad_id, mask, **flags)[-1]


def logits(params, cfg: dict, ids, pad_id: int, last: int | None = None,
           mask=None, **flags):
    """Next-token logits [B, S or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection. `flags`:
    `lora_scale` and the negative controls of `passes_hidden`."""
    h = hidden_states(params, cfg, ids, pad_id, mask, **flags)
    if last is not None:
        h = h[:, -last:]
    head = (params["embed_tokens"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return h @ head.astype(F32)


def exit_distribution(params, cfg: dict, ids, pad_id: int, mask=None,
                      **flags):
    """`(p [B, S, T], exit_pass [B, S])`: the gate's distribution over the
    passes and the pass (from 1) at which each token exits under the
    file's `early_exit_threshold`."""
    hs = passes_hidden(params, cfg, ids, pad_id, mask, **flags)
    gate = params["early_exit_gate"]
    lam = jax.nn.sigmoid(
        (hs @ gate["kernel"].astype(F32))[..., 0] + gate["bias"].astype(F32)[0])
    n_pass = lam.shape[0]
    p, left = [], jnp.ones_like(lam[0])
    for t in range(n_pass - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p = jnp.stack(p + [left], axis=-1)
    q = float(cfg.get("early_exit_threshold", 1.0))
    cum = jnp.cumsum(p, axis=-1)
    exit_pass = jnp.full(p.shape[:-1], n_pass, jnp.int32)
    for t in range(n_pass - 1, 0, -1):
        exit_pass = jnp.where(cum[..., t - 1] >= q, t, exit_pass)
    return p, exit_pass
