"""OLMoE's sparse-expert layer through the normal path, against the plain
float32 reference (`benchmark/harness/reference_olmoe.py`: every expert for
every token, masked by the top-k weights; nothing shared with ops/moe.py).

Tiny sizes on the CPU: 2 layers, hidden 64, 4 heads (MHA), 8 experts, 2 per
token, expert width 32, float32 on both sides.

Tolerance: both sides are float32 and differ in summation order only, so
logits and adapter gradients are held to 1e-4 of the reference's largest
magnitude. A bf16 expert matmul (2^-8 relative), a dropped token, a missing
expert, a renormalised weight or a wrong sort order is orders of magnitude
outside that. Logits are compared, never sampled tokens.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import (ModelConfig, decode_step, init_kv_cache,
                               init_params, padded_forward_logits, prefill)
from nanorlhf_tpu.core.lora import (LoraConfig, init_lora_params, lora_targets,
                                    merge_lora, trainable_mask)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from harness import reference_olmoe  # noqa: E402

PAD, V, TOL = 0, 128, 1e-4


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(ModelConfig.olmoe_tiny(vocab_size=V), **kw)


def as_file(cfg: ModelConfig) -> dict:
    """The configuration as the reference reads it: config.json keys."""
    return dict(hidden_size=cfg.hidden_size,
                num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_key_value_heads,
                rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob,
                tie_word_embeddings=cfg.tie_word_embeddings)


def weights(cfg, lora: bool = False, seed: int = 0):
    params = init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    if lora:
        lc = LoraConfig(r=4, alpha=8)
        ad = init_lora_params(cfg, lc, jax.random.PRNGKey(seed + 1), jnp.float32)
        # B is zero at birth: give it values, or the adapter tests nothing
        ad = jax.tree_util.tree_map_with_path(
            lambda path, x: x if path[-1].key == "a" else 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(path) + x.shape[-1]), x.shape, x.dtype), ad)
        params = {**params, "lora": ad}
    return params


def tokens(rows=3, T=14, pads=True, seed=0):
    ids = np.random.RandomState(seed).randint(1, V, (rows, T))
    if pads:
        ids[0, :5] = PAD
        ids[1, :2] = PAD
    return jnp.asarray(ids)


def close(got, want, mask=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want)
    if mask is not None:
        err = err[np.asarray(mask)]
    assert err.max() <= TOL * np.abs(want).max(), (err.max(), np.abs(want).max())


# (a) uncached forward ------------------------------------------------------

@pytest.mark.parametrize("pads", [False, True], ids=["nopads", "pads"])
@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
def test_forward_matches_reference(lora, pads):
    cfg = tiny()
    params, ids = weights(cfg, lora), tokens(pads=pads)
    scale = 2.0 if lora else 1.0
    got = padded_forward_logits(params, cfg, ids, PAD, lora_scale=scale)
    want = reference_olmoe.logits(params, as_file(cfg), ids, PAD, scale)
    close(got, want, mask=ids != PAD)


# (b) prefill + decode through the contiguous cache -------------------------

def test_contiguous_cache_matches_reference_at_every_position():
    cfg = tiny()
    params, ids = weights(cfg, lora=True), tokens(T=16)
    want = reference_olmoe.logits(params, as_file(cfg), ids, PAD, 2.0)
    P, T = 8, ids.shape[1]
    mask = ids != PAD
    caches = init_kv_cache(cfg, ids.shape[0], T, jnp.float32)
    logits, caches = prefill(params, cfg, ids[:, :P], mask[:, :P], caches,
                             lora_scale=2.0)
    close(logits, want[:, P - 1])
    key_mask = jnp.zeros((ids.shape[0], T), bool).at[:, :P].set(mask[:, :P])
    n_real = mask[:, :P].sum(axis=1)
    for t in range(P, T):
        key_mask = key_mask.at[:, t].set(True)
        logits, caches = decode_step(params, cfg, ids[:, t], n_real + (t - P),
                                     t, key_mask, caches, lora_scale=2.0)
        close(logits, want[:, t])


# (c) the paged DecodeSession ------------------------------------------------

def test_paged_session_matches_reference_at_every_position():
    """Greedy generation through the paged `DecodeSession` (pages of 4, two
    tokens a beat), then the reference's full forward over prompt +
    generated tokens: at every generated position the session's token is
    the reference's argmax (to tolerance: a tie may fall either way) and the
    logprob the session captured for it is the reference's log-softmax."""
    from nanorlhf_tpu.sampler.paged.session import DecodeSession

    cfg, n_new, P = tiny(), 6, 8
    params = weights(cfg)
    prompts = tokens(rows=3, T=P)
    sess = DecodeSession(
        params, cfg, rows=3, prompt_len=P, max_tokens=n_new, page_size=4,
        eos_token_id=V + 5, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        greedy=True, capture_logprobs=True, sync_every=2)
    sess.bootstrap(prompts, prompts != PAD)
    for _ in range(n_new):
        done, _ = sess.step()
        if done.all():
            break
    assert done.all()
    out, captured = np.asarray(sess.state[1]), np.asarray(sess.state[2])
    full = jnp.concatenate([prompts, jnp.asarray(out)], axis=1)
    real = jnp.concatenate([prompts != PAD, jnp.ones_like(out, bool)], axis=1)
    want = np.asarray(reference_olmoe.logits(
        params, as_file(cfg), full, PAD, mask=real))[:, P - 1: -1]
    chosen = np.take_along_axis(want, out[..., None], axis=-1)[..., 0]
    assert (chosen >= want.max(axis=-1) - TOL * np.abs(want).max()).all()
    logp = chosen - np.log(np.exp(want - want.max(-1, keepdims=True)).sum(-1)) \
        - want.max(-1)
    close(captured, logp)


# (d) gradients of the GRPO loss w.r.t. the adapter --------------------------

def _grpo_loss(logits_fn, params, ids, ctx, adv, old):
    lg = logits_fn(params)[:, ctx - 1: -1]
    lp = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                             ids[:, ctx:, None], axis=-1)[..., 0]
    ratio = jnp.exp(lp - old)
    pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 0.8, 1.2))
    kl = jnp.exp(old - lp) - (old - lp) - 1.0
    return jnp.mean(pg + 0.05 * kl)


def test_adapter_gradients_match_reference():
    cfg = tiny()
    params, ids, ctx = weights(cfg, lora=True), tokens(T=14), 6
    adv = jnp.asarray([[1.0], [-0.5], [0.25]])
    old = -3.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (3, 14 - ctx))
    base = {k: v for k, v in params.items() if k != "lora"}

    def system(lora):
        return _grpo_loss(lambda p: padded_forward_logits(p, cfg, ids, PAD, 2.0),
                          {**base, "lora": lora}, ids, ctx, adv, old)

    def plain(lora):
        return _grpo_loss(
            lambda p: reference_olmoe.logits(p, as_file(cfg), ids, PAD, 2.0),
            {**base, "lora": lora}, ids, ctx, adv, old)

    got, want = jax.grad(system)(params["lora"]), jax.grad(plain)(params["lora"])
    assert set(got["layers"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0
        close(g, w)


# (e) norm_topk_prob both ways ------------------------------------------------

@pytest.mark.parametrize("renorm", [False, True], ids=["as_published", "renormalised"])
def test_norm_topk_prob(renorm):
    cfg = tiny(norm_topk_prob=renorm)
    params, ids = weights(cfg), tokens()
    got = padded_forward_logits(params, cfg, ids, PAD)
    close(got, reference_olmoe.logits(params, as_file(cfg), ids, PAD), ids != PAD)
    other = reference_olmoe.logits(
        params, as_file(tiny(norm_topk_prob=not renorm)), ids, PAD)
    assert np.abs(np.asarray(got) - np.asarray(other)).max() > 100 * TOL


# (f) num_experts == 0 is today's dense layer, bit for bit ---------------------

def test_dense_layer_is_bit_identical_to_the_parents():
    """The dense logits of the parent commit, recorded from it
    (tests/data/dense_logits_pr26.npy: `qwen2_tiny(128)`, seed 0, float32,
    this test's tokens): same weights from the same keys, same program."""
    cfg = ModelConfig.qwen2_tiny(vocab_size=V)
    assert cfg.num_experts == 0 and not cfg.qk_norm
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert set(params["layers"]) == {
        "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
        "post_attention_layernorm", "gate_proj", "up_proj", "down_proj"}
    got = np.asarray(padded_forward_logits(params, cfg, tokens(), PAD))
    want = np.load(os.path.join(REPO, "tests", "data", "dense_logits_pr26.npy"))
    assert np.array_equal(got, want)


# (g) a skewed router: huge and empty groups -----------------------------------

def test_skewed_router_still_matches():
    """The op alone on all-positive inputs, so a router column of one sign
    decides for every token: expert 0 is in every token's top 2 (a group of
    N rows), experts 5-7 in none (empty groups), and the ragged matmul, the
    sort and the combine still give the reference's `_expert_mlp`."""
    from nanorlhf_tpu.ops.moe import moe_mlp, router_stats

    cfg = tiny()
    p = jax.tree.map(lambda x: x[0], weights(cfg)["layers"])
    D, E = cfg.hidden_size, cfg.num_experts
    router = np.asarray(p["router"]["kernel"]).copy()
    router[:, 0], router[:, 5:] = 0.5, -0.5
    p["router"]["kernel"] = jnp.asarray(router)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (4, 24, D))) + 0.1
    ex = p["experts"]
    got, aux = moe_mlp(h, p["router"]["kernel"], ex["gate_proj"]["kernel"],
                       ex["up_proj"]["kernel"], ex["down_proj"]["kernel"], 2, False)
    stats = router_stats(jax.tree.map(lambda x: x[None], aux),
                         jnp.ones((4, 24), bool), E)
    load = np.asarray(stats["load"]).sum(axis=(0, 1))
    assert load[0] == 4 * 24 and (load[5:] == 0).all() and load.sum() == 2 * 4 * 24
    assert int(stats["dropped"]) == 0
    close(got, reference_olmoe._expert_mlp(h, p, 2, False))


# (h) HF state dict: load -> export round trip ---------------------------------

def test_hf_state_dict_round_trip(tmp_path):
    from nanorlhf_tpu.core.params import (export_hf_checkpoint,
                                          hf_state_dict_from_params,
                                          load_hf_checkpoint,
                                          params_from_hf_state_dict)

    cfg = tiny()
    params = weights(cfg)
    sd = hf_state_dict_from_params(cfg, params)
    L, E = cfg.num_hidden_layers, cfg.num_experts
    assert sd["model.layers.1.mlp.experts.7.down_proj.weight"].shape == (64, 32)
    assert sd["model.layers.0.mlp.gate.weight"].shape == (E, 64)
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (64,)
    assert len(sd) == 3 + L * (2 + 2 + 4 + 1 + 3 * E)
    assert not any("mlp.gate_proj" in k for k in sd)
    back = params_from_hf_state_dict(cfg, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    out = export_hf_checkpoint(cfg, params, str(tmp_path / "ckpt"), dtype="float32")
    with open(os.path.join(out, "config.json")) as f:
        written = json.load(f)
    assert written["model_type"] == "olmoe"
    assert written["architectures"] == ["OlmoeForCausalLM"]
    loaded_cfg, loaded = load_hf_checkpoint(out, jnp.float32)
    assert loaded_cfg.num_experts == E and loaded_cfg.qk_norm
    assert loaded_cfg.num_experts_per_tok == 2 and not loaded_cfg.norm_topk_prob
    ids = tokens()
    assert np.array_equal(
        np.asarray(padded_forward_logits(loaded, loaded_cfg, ids, PAD)),
        np.asarray(padded_forward_logits(params, cfg, ids, PAD)))


# (i) the CPU virtual mesh ------------------------------------------------------

def test_sharded_logits_match_single_device():
    from jax.sharding import NamedSharding

    from nanorlhf_tpu.parallel import MeshConfig, make_mesh, param_sharding_rules

    cfg = tiny()
    params, ids = weights(cfg, lora=True), tokens(rows=4)
    want = padded_forward_logits(params, cfg, ids, PAD, 2.0)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                     devices=jax.devices()[:4])
    rules = param_sharding_rules(params)
    assert tuple(rules["layers"]["experts"]["gate_proj"]["kernel"]) == (
        None, "tensor", "fsdp", None)
    assert not any(rules["layers"]["router"]["kernel"])
    sharded = jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params, rules)
    got = jax.jit(lambda p, x: padded_forward_logits(p, cfg, x, PAD, 2.0))(
        sharded, ids)
    close(got, want)


# (j) one RLTrainer update, with the counters in its row -------------------------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    out = tmp_path_factory.mktemp("moe_trainer")
    tok = ToyTokenizer(vocab_size=256)
    cfg = ModelConfig.olmoe_tiny(vocab_size=256)
    rl = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(out), response_length=8,
        temperature=1.0, sample_n=2, total_episodes=8,
        per_device_train_batch_size=1, gradient_accumulation_steps=2,
        num_mini_batches=2, num_ppo_epochs=1, learning_rate=1e-3,
        kl_coef=0.05, use_lora=True, lora_r=4, lora_alpha=8,
        gradient_checkpointing=True, mesh=MeshConfig(2, 2, 2),
        save_steps=10 ** 9, report_to="jsonl")
    data = load_prompt_dataset("synthetic:64", tok, max_prompt_len=12)

    def reward(texts, eos):
        return np.asarray([len(set(t.split())) / 20.0 for t in texts], np.float32)

    trainer = RLTrainer(rl, cfg, tok,
                        init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
                        data, reward)
    before = jax.tree.map(np.asarray, trainer.params)
    trainer.train(num_updates=1)
    with open(os.path.join(str(out), "metrics.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if "episode" in r]
    return trainer, before, rows


def test_trainer_update_runs_and_moves_only_what_trains(trained):
    trainer, before, rows = trained
    assert len(rows) == 1 and np.isfinite(rows[0]["loss/policy_avg_new"])
    after = jax.tree.map(np.asarray, trainer.params)
    assert set(after["lora"]["layers"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b), before, after)
    assert moved["lora"]["layers"]["q_proj"]["b"] and moved["lm_head"]
    assert not any(jax.tree.leaves(moved["layers"]))   # router, experts, norms


@pytest.mark.parametrize("counter,lo,hi", [
    ("moe/load_max_over_mean", 1.0, 8.0),
    ("moe/router_entropy", 0.5, np.log(8) + 1e-6),
    ("moe/dropped_tokens", 0.0, 0.0),
])
def test_trainer_row_has_the_moe_counters(trained, counter, lo, hi):
    row = trained[2][0]
    assert lo <= row[counter] <= hi, (counter, row[counter])


# what the model decides, and what does not compose ------------------------------

def test_lora_targets_follow_the_model():
    lc = LoraConfig(r=4, alpha=8)
    assert lora_targets(tiny(), lc) == ("q_proj", "k_proj", "v_proj", "o_proj")
    assert len(lora_targets(ModelConfig.qwen2_tiny(), lc)) == 7
    params = weights(tiny(), lora=True)
    mask = trainable_mask(params, lc)
    assert not any(jax.tree.leaves(mask["layers"])) and mask["lm_head"]
    merged = merge_lora(params, 2.0)
    ids = tokens()
    close(padded_forward_logits(merged, tiny(), ids, PAD),
          padded_forward_logits(params, tiny(), ids, PAD, 2.0))


def test_int8_rollout_weights_raise_with_experts(tmp_path):
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    tok = ToyTokenizer(vocab_size=256)
    cfg = ModelConfig.olmoe_tiny(vocab_size=256)
    rl = RLConfig(algo=AlgoName.GRPO, output_dir=str(tmp_path), sample_n=2,
                  response_length=4, use_lora=True, lora_r=4,
                  rollout_quant="int8", mesh=MeshConfig(2, 2, 2))
    with pytest.raises(ValueError, match="sparse-expert"):
        RLTrainer(rl, cfg, tok,
                  init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
                  load_prompt_dataset("synthetic:16", tok, max_prompt_len=8),
                  lambda texts, eos: np.zeros(len(texts), np.float32))


@pytest.mark.parametrize("hf,match", [
    ({"model_type": "qwen2_moe", "num_experts": 60, "num_experts_per_tok": 4,
      "shared_expert_intermediate_size": 5632}, "expert keys"),
    ({"model_type": "mixtral", "num_local_experts": 8,
      "num_experts_per_tok": 2}, "expert keys"),
    ({"model_type": "olmoe", "num_experts": 64, "num_experts_per_tok": 8,
      "clip_qkv": 8.0}, "clip_qkv"),
], ids=["qwen2_moe", "mixtral", "clip_qkv"])
def test_from_hf_config_refuses_what_the_decoder_lacks(hf, match):
    base = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 32,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 4}
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**base, **hf})


def test_from_hf_config_reads_the_published_file():
    with open(os.path.join(REPO, "benchmark", "configs", "olmoe-1b-7b.json")) as f:
        published = json.load(f)
    got = ModelConfig.from_hf_config(published)
    want = dataclasses.replace(
        ModelConfig.olmoe_1b_7b(),
        num_hidden_layers=published["num_hidden_layers"])
    assert got == want


# speculative verify needs nothing: the MLP is per token -------------------------

def test_decode_verify_equals_a_chain_of_decode_steps():
    from nanorlhf_tpu.core.model import decode_verify

    cfg = tiny()
    params, ids = weights(cfg), tokens(T=12)
    P, k, T = 8, 3, 12
    mask = ids != PAD
    caches = init_kv_cache(cfg, ids.shape[0], T, jnp.float32)
    _, caches = prefill(params, cfg, ids[:, :P], mask[:, :P], caches)
    key_mask = jnp.zeros((ids.shape[0], T), bool).at[:, :P].set(mask[:, :P])
    n_real = mask[:, :P].sum(axis=1)
    positions = n_real[:, None] + jnp.arange(k + 1)[None]
    fill = jnp.full((ids.shape[0],), P, jnp.int32)
    got, _ = decode_verify(params, cfg, ids[:, P:P + k + 1], positions, fill,
                           key_mask, caches)
    want = reference_olmoe.logits(params, as_file(cfg), ids, PAD)
    close(got, want[:, P:P + k + 1])


# the Pallas grouped matmul, interpreted: the same layer, the same reference -------

def test_expert_kernel_matches_reference_forward_cache_and_gradients():
    """`attention_impl="pallas"` takes megablox's grouped matmul (interpret
    mode here) over the whole expert stack in place, in the uncached forward
    too; logits, a cached decode step and the adapter's gradients still are
    the reference's."""
    from nanorlhf_tpu.core.model import use_expert_kernel

    cfg = tiny(attention_impl="pallas")
    assert use_expert_kernel(cfg) and not use_expert_kernel(tiny())
    assert not use_expert_kernel(tiny(attention_impl="xla"))
    params, ids = weights(cfg, lora=True), tokens(T=12)
    want = reference_olmoe.logits(params, as_file(cfg), ids, PAD, 2.0)
    close(padded_forward_logits(params, cfg, ids, PAD, 2.0), want, ids != PAD)
    P, T, mask = 8, 12, ids != PAD
    caches = init_kv_cache(cfg, ids.shape[0], T, jnp.float32)
    logits, caches = prefill(params, cfg, ids[:, :P], mask[:, :P], caches, 2.0)
    close(logits, want[:, P - 1])
    key_mask = jnp.zeros((ids.shape[0], T), bool).at[:, :P + 1].set(
        jnp.concatenate([mask[:, :P], jnp.ones((ids.shape[0], 1), bool)], 1))
    logits, _ = decode_step(params, cfg, ids[:, P], mask[:, :P].sum(axis=1), P,
                            key_mask, caches, 2.0)
    close(logits, want[:, P])
    base = {k: v for k, v in params.items() if k != "lora"}
    loss = lambda fn: lambda lora: jnp.sum(  # noqa: E731
        jnp.tanh(fn({**base, "lora": lora}))[:, -4:] ** 2)
    got = jax.grad(loss(lambda p: padded_forward_logits(p, cfg, ids, PAD, 2.0)))(
        params["lora"])
    ref = jax.grad(loss(lambda p: reference_olmoe.logits(
        p, as_file(cfg), ids, PAD, 2.0)))(params["lora"])
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        close(g, w)
