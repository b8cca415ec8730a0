"""reference_olmoe.py against `core.model.padded_forward_logits` at a tiny
size in float32, with a non-zero LoRA delta on the attention projections,
left pads, and the router's weights renormalised or not; and against a
forward written out token by token and expert by expert, so that the
reference does not only agree with the program it is there to check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference_olmoe

CFG = {"model_type": "olmoe", "vocab_size": 300, "hidden_size": 64,
       "intermediate_size": 32, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
       "num_experts_per_tok": 2, "norm_topk_prob": False, "clip_qkv": None,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False}


def build(cfg):
    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

    mcfg = ModelConfig.from_hf_config(cfg)
    key = jax.random.PRNGKey(0)
    params = init_params(mcfg, key, jnp.float32)
    lora = init_lora_params(mcfg, LoraConfig(r=4, alpha=8), key, jnp.float32)
    assert sorted(lora["layers"]) == ["k_proj", "o_proj", "q_proj", "v_proj"]
    for i, name in enumerate(lora["layers"]):    # B starts at zero: fill it
        b = lora["layers"][name]["b"]
        lora["layers"][name]["b"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, i), b.shape, b.dtype)
    params["lora"] = lora
    ids = np.array(jax.random.randint(key, (3, 20), 3, 300))
    ids[0, :7] = 0
    ids[1, :1] = 0
    return mcfg, params, jnp.asarray(ids)


@pytest.mark.parametrize("renorm", [False, True])
def test_reference_matches_the_program_forward(renorm):
    from nanorlhf_tpu.core.model import padded_forward_logits

    cfg = dict(CFG, norm_topk_prob=renorm)
    mcfg, params, ids = build(cfg)
    with jax.default_matmul_precision("highest"):
        want = padded_forward_logits(params, mcfg, ids, 0, lora_scale=2.0)
        got = reference_olmoe.logits(params, cfg, ids, 0, lora_scale=2.0)
    real = np.asarray(ids != 0)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=1e-4 * scale)
    # the response-logprob convention: logits at t-1 predict token t
    lp = reference_olmoe.response_logprobs(params, cfg, ids, 12, 0, 0.9, 2.0)
    ref = jax.nn.log_softmax(want[:, 11:-1] / 0.9, axis=-1)
    ref = jnp.take_along_axis(ref, ids[:, 12:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("renorm", [False, True])
def test_expert_mlp_is_the_published_sum(renorm):
    """One layer's MLP, token by token in numpy: softmax over the experts,
    the top two, and the weighted sum of those two experts' SwiGLU."""
    _, params, _ = build(CFG)
    p = jax.tree.map(lambda x: np.asarray(x[1], np.float64), params["layers"])
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 5, 64)), np.float64)
    want = np.zeros_like(h)
    for b in range(2):
        for t in range(5):
            x = h[b, t]
            logits = x @ p["router"]["kernel"]
            prob = np.exp(logits - logits.max())
            prob /= prob.sum()
            top = np.argsort(-prob)[:2]
            w = prob[top] / (prob[top].sum() if renorm else 1.0)
            for e, we in zip(top, w):
                g = x @ p["experts"]["gate_proj"]["kernel"][e]
                u = x @ p["experts"]["up_proj"]["kernel"][e]
                want[b, t] += we * ((g / (1 + np.exp(-g)) * u)
                                    @ p["experts"]["down_proj"]["kernel"][e])
    layer = jax.tree.map(lambda x: x[1], params["layers"])
    got = reference_olmoe._expert_mlp(jnp.asarray(h, jnp.float32), layer, 2, renorm)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4 * np.abs(want).max())


def test_a_lower_precision_does_not_pass_as_the_reference():
    """The tolerance the tier-1 tests hold the program to (1e-4 of the
    logits' scale) refuses the same reference computed in bfloat16, the
    nearest precision below float32."""
    cfg = dict(CFG)
    _, params, ids = build(cfg)
    want = np.asarray(reference_olmoe.logits(params, cfg, ids, 0, 2.0))
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = np.asarray(reference_olmoe.logits(low, cfg, ids, 0, 2.0))
    real = np.asarray(ids != 0)
    assert np.abs(got - want)[real].max() > 10 * 1e-4 * np.abs(want).max()
