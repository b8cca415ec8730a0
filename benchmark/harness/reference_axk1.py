"""Plain reference: an A.X-K1 (DeepSeek-V3 key set) forward pass in
`jax.numpy`, float32, expanded attention only.

The layer as published (`config.json` of skt/A.X-K1; the equations are
DeepSeek-V3's `modeling_deepseek.py`, whose key set this is letter for
letter), `x` the residual stream, every norm an RMSNorm:

    h = norm(x);  c_q = norm(h W_qa);  [q_nope | q_r] = c_q W_qb  per head
        [c_kv | k_r] = h W_kva;  c_kv = norm(c_kv);  RoPE on q_r and on k_r
        (one k_r for all heads);  [k_nope | v] = c_kv W_kvb  per head
        P = softmax_causal(([q_nope | q_r] . [k_nope | k_r]) s);  x += (P v) W_o
        s = (qk_nope + qk_rope)^-0.5 mscale(factor, mscale_all_dim)^2
    layer < first_k_dense_replace:
        x += W_down( silu(h W_gate) * (h W_up) ),  h = norm(x)
    later layers:
        sc = sigmoid(h W_r) over ALL n_routed_experts;  top num_experts_per_tok
        of them (`topk_method: "none"`: no groups, no correction bias);
        w = routed_scaling_factor sc / sum of the chosen sc;
        x += sum_{j held here} w_j expert_j(h) + shared_expert(h)

No kernel, no cache, no absorbed form, no sort and no grouped matmul: every
HELD expert is computed for every token and weighted by a [tokens, experts]
matrix that is zero off the top k. Nothing is imported from `nanorlhf_tpu`:
the tree is read by its leaf names only (`embed_tokens [V, D]`;
`dense_layers.*` and `layers.*` stacked on a leading layer axis:
`{q_a,q_b,kv_a,kv_b,o}_proj.kernel [L, in, out]`, `q_a_layernorm`,
`kv_a_layernorm`, `input_layernorm`, `post_attention_layernorm`; the dense
stack's `{gate,up,down}_proj.kernel`; the expert stack's `router.kernel
[L, D, E]`, `experts.{gate,up,down}_proj.kernel [L, held, in, out]`,
`shared_expert.{gate,up,down}_proj.kernel`; `norm`; `lm_head [D, V]`;
`lora.{dense_layers,layers}.<proj>.{a, b}` on the attention projections).

Weights may arrive in bfloat16 and are cast to float32 as they are used
(exact). To fit beside a served model (weights + page pool fill 13 of 16 GB)
nothing large is ever whole in float32 or copied out of its stack: rows go
one at a time, attention in blocks of `QUERY_BLOCK` queries, the experts one
at a time, each read from the stack of every layer's experts where it lies,
and the dense layer's 18,432 columns in `MLP_BLOCKS` blocks (a sum over
column blocks is the same sum). Callers wrap calls in
`jax.default_matmul_precision("highest")`.

Departures from the published description:
- rows are LEFT-padded, so position ids count real tokens only
  (`cumsum(mask) - 1`) and pad keys are masked, as harness/reference.py;
- **the chip's share**: the configuration holds experts `[offset, offset +
  held)` of `n_routed_experts` (`n_routed_experts_held`,
  `n_routed_experts_offset`; absent keys: all). The router scores and picks
  over all of them; what the absent experts would add is left out, here as
  in the program, and the partial sum goes on to the next layer;
- RoPE in the rotate-half layout (`[x0..x_{d/2-1} | x_{d/2}..]` pairs), where
  the published code stores pairs interleaved and de-interleaves before
  rotating: a fixed permutation of `W_qb`'s and `W_kva`'s rotary columns,
  which leaves every q.k unchanged and random weights indifferent
  (`core/params.py` applies it when it loads a checkpoint);
- the router's logits in float32 (published: the model's dtype for the
  matmul, float32 for the sigmoid), which is what "published" means for a
  float32 model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.reference import F32, MASKED, _linear, _rms_norm

QUERY_BLOCK = 128
MLP_BLOCKS = 4


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(cfg: dict):
    """Rotary frequencies [qk_rope_head_dim / 2] and the cos/sin multiplier,
    YaRN as DeepSeek-V3 writes it (`DeepseekV3YarnRotaryEmbedding`)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return extra, 1.0
    factor, original = sc["factor"], sc["original_max_position_embeddings"]

    def dim_of(rotations):      # the dimension that turns this often
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    freq = extra / factor * (1.0 - keep) + extra * keep
    mult = _yarn_mscale(factor, sc.get("mscale", 1)) \
        / _yarn_mscale(factor, sc.get("mscale_all_dim", 0))
    return freq, mult


def _rope(x, positions, freq, mult):
    """x [heads, T, d], positions [T]; rotate-half."""
    ang = positions[:, None].astype(F32) * freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(ang) * mult) + rotated * (jnp.sin(ang) * mult)


def _attention(q, k, v, allowed, scale):
    """q, k [H, T, d], v [H, T, dv], allowed [T, T] -> [H, T, dv], a block of
    queries at a time."""
    H, T, _ = q.shape
    n = -(-T // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - T
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(H, n, QUERY_BLOCK, -1)
    ab = jnp.pad(allowed, ((0, pad), (0, 0))).reshape(n, QUERY_BLOCK, T)

    def block(args):
        qi, ai = args
        s = jnp.einsum("hqd,hkd->hqk", qi, k) * scale
        p = jax.nn.softmax(jnp.where(ai[None], s, MASKED), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    out = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), ab))    # [n, H, bq, dv]
    return jnp.moveaxis(out, 0, 1).reshape(H, n * QUERY_BLOCK, -1)[:, :T]


def _swiglu(h, gate, up, down, blocks: int = 1):
    """W_down(silu(h W_gate) * (h W_up)), the hidden columns in `blocks`
    equal blocks whose contributions add up."""
    F = gate.shape[1]
    if blocks == 1 or F % blocks:
        return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
    width = F // blocks

    def one(i, acc):
        cols = lambda w, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * width, width, axis).astype(F32)
        return acc + (jax.nn.silu(h @ cols(gate, 1)) * (h @ cols(up, 1))) \
            @ cols(down, 0)

    return jax.lax.fori_loop(0, blocks, one, jnp.zeros_like(h))


def router_weights(h, router, cfg: dict):
    """The dense [T, n_routed_experts] matrix of routing weights: the chosen
    experts' scaled scores, zero off the top k."""
    k = cfg["num_experts_per_tok"]
    logits = h @ router.astype(F32)
    if cfg.get("scoring_func", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(scores, axis=-1)[..., -k][..., None]
    w = jnp.where(scores >= kth, scores, 0.0)
    if cfg.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.get("routed_scaling_factor", 1.0)


def _expert_mlp(h, p, cfg: dict, experts=None, layer=0):
    """h [T, D]: the held routed experts' weighted sum plus the shared
    expert. `experts` is the stack of EVERY expert layer's held experts
    (`[L, held, ...]` kernels), read one expert at a time at `layer`, so no
    layer's 12 experts are ever set side by side; by default `p["experts"]`,
    one layer's."""
    E = cfg["n_routed_experts"]
    held = cfg.get("n_routed_experts_held") or E
    offset = cfg.get("n_routed_experts_offset") or 0
    w = router_weights(h, p["router"]["kernel"], cfg)[:, offset:offset + held]
    if experts is None:
        experts = jax.tree.map(lambda a: a[None], p["experts"])
    kernel = lambda name, e: experts[name]["kernel"][layer, e]  # noqa: E731

    def one(e, acc):
        return acc + w[:, e][:, None] * _swiglu(
            h, kernel("gate_proj", e), kernel("up_proj", e),
            kernel("down_proj", e))

    acc = jax.lax.fori_loop(0, held, one, jnp.zeros_like(h))
    if "shared_expert" in p:
        sh = p["shared_expert"]
        acc = acc + _swiglu(h, sh["gate_proj"]["kernel"],
                            sh["up_proj"]["kernel"], sh["down_proj"]["kernel"])
    return acc


def _row_hidden(params, cfg: dict, ids, mask, lora_scale, last=None):
    """One row: ids, mask [T] -> final-normed hidden states [T or last, D]."""
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    T = ids.shape[0]
    positions = jnp.cumsum(mask) - mask.astype(jnp.int32)
    allowed = jnp.tril(jnp.ones((T, T), bool)) & mask[None, :]
    freq, mult = _inv_freq(cfg)
    scale = (dn + dr) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc:
        scale *= _yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)) ** 2
    x = params["embed_tokens"][jnp.where(mask, ids, 0)].astype(F32)
    lora = params.get("lora", {})

    def layer(x, lp):
        p, lo, index = lp
        lin = lambda h, name: _linear(h, p[name], lo.get(name), lora_scale)  # noqa: E731
        h = _rms_norm(x, p["input_layernorm"], eps)
        c_q = _rms_norm(lin(h, "q_a_proj"), p["q_a_layernorm"], eps)
        q = lin(c_q, "q_b_proj").reshape(T, H, dn + dr).transpose(1, 0, 2)
        kv_a = lin(h, "kv_a_proj")
        c_kv = _rms_norm(kv_a[:, :r], p["kv_a_layernorm"], eps)
        k_r = _rope(kv_a[None, :, r:], positions, freq, mult)       # [1, T, dr]
        kv = lin(c_kv, "kv_b_proj").reshape(T, H, dn + dv).transpose(1, 0, 2)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], positions, freq, mult)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (H, T, dr))], axis=-1)
        a = _attention(q, k, kv[..., dn:], allowed, scale)
        x = x + lin(a.transpose(1, 0, 2).reshape(T, H * dv), "o_proj")
        h = _rms_norm(x, p["post_attention_layernorm"], eps)
        if "router" in p:
            return x + _expert_mlp(h, p, cfg, experts, index), None
        return x + _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                           p["down_proj"]["kernel"], MLP_BLOCKS), None

    for name in ("dense_layers", "layers"):
        if name in params:
            tree = dict(params[name])
            experts = tree.pop("experts", None)   # read in place, by expert
            n = tree["input_layernorm"].shape[0]
            x, _ = jax.lax.scan(
                layer, x, (tree, lora.get(name, {}), jnp.arange(n)))
    if last is not None:
        x = x[-last:]
    return _rms_norm(x, params["norm"], eps)


def hidden_states(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
                  mask=None, last: int | None = None):
    """Final-normed hidden states [B, T or last, D] for left-padded token
    ids, one row at a time. `mask` [B, T] says which positions are real; by
    default every id but `pad_id`."""
    mask = (ids != pad_id) if mask is None else mask
    return jax.lax.map(
        lambda im: _row_hidden(params, cfg, im[0], im[1], lora_scale, last),
        (ids, mask))


def logits(params, cfg: dict, ids, pad_id: int, lora_scale: float = 1.0,
           last: int | None = None, mask=None):
    """Next-token logits [B, T or last, V]; `last` keeps only the final
    `last` positions before the vocabulary projection."""
    h = hidden_states(params, cfg, ids, pad_id, lora_scale, mask, last)
    if cfg.get("tie_word_embeddings"):
        return h @ params["embed_tokens"].astype(F32).T
    return h @ params["lm_head"].astype(F32)


def response_logprobs(params, cfg: dict, query_responses, context: int,
                      pad_id: int, temperature: float,
                      lora_scale: float = 1.0):
    """log p(token_t | tokens_<t) at temperature, for t in the response:
    [B, T - context]."""
    n_resp = query_responses.shape[1] - context
    lg = logits(params, cfg, query_responses, pad_id, lora_scale,
                last=n_resp + 1)[:, :-1]
    logp = jax.nn.log_softmax(lg / temperature, axis=-1)
    labels = query_responses[:, context:]
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
