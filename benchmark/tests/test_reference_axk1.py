"""reference_axk1.py against a forward written out by hand in numpy float64
for a single head and a single held expert (so the reference does not only
agree with the program it is there to check), against
`core.model.padded_forward_logits` at a tiny size with a LoRA delta and left
pads, and against itself in the nearest lower precision."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference_axk1

CFG = {"model_type": "axk1", "attention_bias": False, "hidden_act": "silu",
       "topk_method": "none", "moe_layer_freq": 1, "vocab_size": 300,
       "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "first_k_dense_replace": 1,
       "moe_intermediate_size": 32, "n_routed_experts": 16,
       "n_routed_experts_held": 4, "n_routed_experts_offset": 8,
       "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
       "scoring_func": "sigmoid", "routed_scaling_factor": 2.5,
       "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 64},
       "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False}


def build(cfg, seed=0):
    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

    mcfg = ModelConfig.from_hf_config(cfg)
    key = jax.random.PRNGKey(seed)
    params = init_params(mcfg, key, jnp.float32)
    lora = init_lora_params(mcfg, LoraConfig(r=4, alpha=8), key, jnp.float32)
    for stack in lora.values():                 # B starts at zero: fill it
        for i, name in enumerate(sorted(stack)):
            b = stack[name]["b"]
            stack[name]["b"] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), b.shape, b.dtype)
    params["lora"] = lora
    ids = np.array(jax.random.randint(key, (3, 20), 3, 300))
    ids[0, :7] = 0
    ids[1, :1] = 0
    return mcfg, params, jnp.asarray(ids)


def test_reference_matches_the_program_forward():
    from nanorlhf_tpu.core.model import padded_forward_logits

    mcfg, params, ids = build(CFG)
    with jax.default_matmul_precision("highest"):
        want = padded_forward_logits(params, mcfg, ids, 0, lora_scale=2.0)
        got = reference_axk1.logits(params, CFG, ids, 0, lora_scale=2.0)
    real = np.asarray(ids != 0)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=1e-4 * scale)
    lp = reference_axk1.response_logprobs(params, CFG, ids, 12, 0, 0.9, 2.0)
    ref = jax.nn.log_softmax(want[:, 11:-1] / 0.9, axis=-1)
    ref = jnp.take_along_axis(ref, ids[:, 12:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ref), atol=2e-4)


def _norm(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def test_a_single_head_single_expert_layer_by_hand():
    """One expert layer, one head, one held expert of two, one per token,
    token by token in numpy float64 from the published equations: the
    attention with its YaRN frequencies and scale, the sigmoid router whose
    one chosen weight renormalises to the scaling factor, the held expert
    only where it is the chosen one, and the shared expert always."""
    cfg = dict(CFG, num_hidden_layers=1, first_k_dense_replace=0,
               num_attention_heads=1, num_key_value_heads=1,
               n_routed_experts=2, n_routed_experts_held=1,
               n_routed_experts_offset=1, num_experts_per_tok=1)
    _, params, _ = build(cfg, seed=3)
    params.pop("lora")
    T = 9
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, T), 3, 300))
    got = np.asarray(reference_axk1.hidden_states(
        params, cfg, jnp.asarray(ids), 0))[0]

    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), params["layers"])
    k = lambda name: p[name]["kernel"]          # noqa: E731
    dn, dr, r = 16, 8, 32
    x = np.asarray(params["embed_tokens"], np.float64)[ids[0]]
    # YaRN by hand: dim 8, base 1e4, original 64, factor 4
    base, dim = 1e4, 8
    corr = lambda rot: dim * math.log(64 / (rot * 2 * math.pi)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    freq = np.array([base ** (-2 * i / dim) for i in range(dim // 2)])
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freq = freq / 4 * ramp + freq * (1 - ramp)
    m = 0.1 * math.log(4) + 1.0
    scale = (dn + dr) ** -0.5 * m * m

    def rope(v, pos):
        ang = np.concatenate([pos * freq, pos * freq])
        rot = np.concatenate([-v[dr // 2:], v[:dr // 2]])
        return v * np.cos(ang) + rot * np.sin(ang)

    h = _norm(x, p["input_layernorm"])
    q = _norm(h @ k("q_a_proj"), p["q_a_layernorm"]) @ k("q_b_proj")
    kv_a = h @ k("kv_a_proj")
    c_kv = _norm(kv_a[:, :r], p["kv_a_layernorm"])
    kv = c_kv @ k("kv_b_proj")
    attn = np.zeros((T, 16))
    for t in range(T):
        qt = np.concatenate([q[t, :dn], rope(q[t, dn:], t)])
        s = np.array([qt @ np.concatenate([kv[j, :dn], rope(kv_a[j, r:], j)])
                      for j in range(t + 1)]) * scale
        w = np.exp(s - s.max())
        attn[t] = (w / w.sum()) @ kv[:t + 1, dn:]
    x = x + attn @ k("o_proj")
    h = _norm(x, p["post_attention_layernorm"])
    swiglu = lambda v, e: (_silu(v @ e["gate_proj"]["kernel"])          # noqa: E731
                           * (v @ e["up_proj"]["kernel"])) @ e["down_proj"]["kernel"]
    held = jax.tree.map(lambda a: a[0], p["experts"])       # expert 1 of 0, 1
    out = np.zeros_like(x)
    chosen_here = 0
    for t in range(T):
        score = 1 / (1 + np.exp(-(h[t] @ k("router"))))
        if score.argmax() == 1:         # top-1 is the held expert: w = 2.5
            out[t] += 2.5 * swiglu(h[t], held)
            chosen_here += 1
        out[t] += swiglu(h[t], p["shared_expert"])
    assert 0 < chosen_here < T          # both branches are exercised
    x = x + out
    want = _norm(x, np.asarray(params["norm"], np.float64))
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_a_lower_precision_does_not_pass_as_the_reference():
    _, params, ids = build(CFG)
    want = np.asarray(reference_axk1.logits(params, CFG, ids, 0, 2.0))
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = np.asarray(reference_axk1.logits(low, CFG, ids, 0, 2.0))
    real = np.asarray(ids != 0)
    assert np.abs(got - want)[real].max() > 10 * 1e-4 * np.abs(want).max()


def test_blocks_are_the_unblocked_computation():
    """Rows, query blocks and the dense MLP's column blocks are there for
    memory: any block size gives the same numbers."""
    _, params, ids = build(CFG)
    want = np.asarray(reference_axk1.logits(params, CFG, ids, 0, 2.0))
    old = reference_axk1.QUERY_BLOCK, reference_axk1.MLP_BLOCKS
    try:
        reference_axk1.QUERY_BLOCK, reference_axk1.MLP_BLOCKS = 7, 1
        got = np.asarray(reference_axk1.logits(params, CFG, ids, 0, 2.0))
    finally:
        reference_axk1.QUERY_BLOCK, reference_axk1.MLP_BLOCKS = old
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
