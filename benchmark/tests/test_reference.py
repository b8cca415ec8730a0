"""reference.py against `core.model.padded_forward_logits` at a tiny size in
float32, tied and untied, with a non-zero LoRA delta and left pads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_the_program_forward(tied):
    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params
    from nanorlhf_tpu.core.model import padded_forward_logits

    cfg = {"vocab_size": 300, "hidden_size": 64, "intermediate_size": 96,
           "num_hidden_layers": 3, "num_attention_heads": 4,
           "num_key_value_heads": 2, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-6, "tie_word_embeddings": tied,
           "model_type": "qwen2"}
    mcfg = ModelConfig.from_hf_config(cfg)
    key = jax.random.PRNGKey(0)
    params = init_params(mcfg, key, jnp.float32)
    # biases and LoRA B start at zero: fill them so they count
    lora = init_lora_params(mcfg, LoraConfig(r=4, alpha=8), key, jnp.float32)
    fill = lambda x, k: 0.1 * jax.random.normal(k, x.shape, x.dtype)  # noqa: E731
    for i, name in enumerate(lora["layers"]):
        lora["layers"][name]["b"] = fill(lora["layers"][name]["b"],
                                         jax.random.fold_in(key, i))
    for i, name in enumerate(("q_proj", "k_proj", "v_proj")):
        params["layers"][name]["bias"] = fill(params["layers"][name]["bias"],
                                              jax.random.fold_in(key, 100 + i))
    params["lora"] = lora
    ids = np.array(jax.random.randint(key, (3, 20), 3, 300))
    ids[0, :7] = 0
    ids[1, :1] = 0
    ids = jnp.asarray(ids)
    with jax.default_matmul_precision("highest"):
        want = padded_forward_logits(params, mcfg, ids, 0, lora_scale=2.0)
        got = reference.logits(params, cfg, ids, 0, lora_scale=2.0)
    real = np.asarray(ids != 0)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-4, rtol=2e-4)
    # the response-logprob convention: logits at t-1 predict token t
    lp = reference.response_logprobs(params, cfg, ids, 12, 0, 0.9, 2.0)
    ref = jax.nn.log_softmax(want[:, 11:-1] / 0.9, axis=-1)
    ref = jnp.take_along_axis(ref, ids[:, 12:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ref), atol=2e-4)
