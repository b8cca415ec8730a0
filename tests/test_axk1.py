"""A.X-K1 (MLA + a leading dense layer + shared-plus-routed sigmoid experts)
through the normal path, against the plain float32 reference
(`benchmark/harness/reference_axk1.py`: expanded attention only, every held
expert for every token; nothing shared with core/mla.py or ops/moe.py).

Tiny sizes on the CPU (`ModelConfig.axk1_tiny`): 1 dense + 2 expert layers,
hidden 64, 4 heads (nope 16 | rope 8, v 16), q rank 24, kv rank 32, 16 routed
experts, 4 per token, one shared; YaRN x4 over 64 positions; float32 on both
sides.

Tolerance: both sides are float32 and differ in summation order and in the
FORM of attention only (the cached single-token step is the absorbed form,
the reference the expanded one: equal in exact arithmetic), so logits are
held to 1e-4 of the reference's largest magnitude. A bf16 matmul anywhere
(2^-8 relative: `test_a_bf16_computation_fails_the_tolerance`), a wrong
softmax scale or YaRN ramp, a missing shared expert, an unscaled or
un-renormalised router weight, a dense layer too many or too few, a latent
written to the wrong page or an absent expert computed is orders of magnitude
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
from nanorlhf_tpu.core.lora import (MLA_TARGETS, LoraConfig, init_lora_params,
                                    lora_targets, merge_lora, trainable_mask)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from harness import reference_axk1  # noqa: E402

PAD, V, TOL = 0, 128, 1e-4


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(ModelConfig.axk1_tiny(vocab_size=V), **kw)


def as_file(cfg: ModelConfig) -> dict:
    """The configuration as the reference reads it: config.json keys."""
    f, o, bf, bs, m, ma = cfg.yarn
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=dict(type="yarn", factor=f, beta_fast=bf, beta_slow=bs,
                          original_max_position_embeddings=o, mscale=m,
                          mscale_all_dim=ma),
        n_routed_experts=cfg.num_experts,
        n_routed_experts_held=cfg.experts_held,
        n_routed_experts_offset=cfg.experts_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, scoring_func=cfg.scoring_func,
        routed_scaling_factor=cfg.routed_scaling_factor,
        tie_word_embeddings=cfg.tie_word_embeddings)


def weights(cfg, lora: bool = False, seed: int = 0, dtype=jnp.float32):
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype)
    if lora:
        lc = LoraConfig(r=4, alpha=8)
        ad = init_lora_params(cfg, lc, jax.random.PRNGKey(seed + 1), dtype)
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


def close(got, want, mask=None, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want)
    if mask is not None:
        err = err[np.asarray(mask)]
    assert err.max() <= tol * np.abs(want).max(), (err.max(), np.abs(want).max())


SHARE = dict(experts_held=4, experts_offset=8)     # one chip of four


# (a) uncached forward ------------------------------------------------------

@pytest.mark.parametrize("share", [{}, SHARE], ids=["whole", "share"])
@pytest.mark.parametrize("pads", [False, True], ids=["nopads", "pads"])
@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
def test_forward_matches_reference(lora, pads, share):
    cfg = tiny(**share)
    params, ids = weights(cfg, lora), tokens(pads=pads)
    scale = 2.0 if lora else 1.0
    got = padded_forward_logits(params, cfg, ids, PAD, lora_scale=scale)
    want = reference_axk1.logits(params, as_file(cfg), ids, PAD, scale)
    close(got, want, mask=ids != PAD)


def test_a_bf16_computation_fails_the_tolerance():
    """The tolerance is tight enough to catch the nearest lower precision:
    the same weights and tokens, computed in bfloat16."""
    cfg = tiny(**SHARE)
    params, ids = weights(cfg), tokens()
    want = np.asarray(reference_axk1.logits(params, as_file(cfg), ids, PAD))
    low = padded_forward_logits(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), params), cfg, ids, PAD)
    err = np.abs(np.asarray(low, np.float32) - want)[np.asarray(ids != PAD)]
    assert err.max() > 20 * TOL * np.abs(want).max()


def test_blocked_queries_equal_one_block(monkeypatch):
    """The expanded form in blocks of queries (as a long chunk or scoring
    row takes it) is the unblocked computation."""
    from nanorlhf_tpu.core import mla

    cfg = tiny()
    params, ids = weights(cfg), tokens(T=21)
    want = padded_forward_logits(params, cfg, ids, PAD)
    monkeypatch.setattr(mla, "_SCORE_BYTES", 3 * 4 * 21 * 8 * 4)   # 8 queries
    assert mla._query_block(3, 4, 21, 21) == 8
    close(padded_forward_logits(params, cfg, ids, PAD), want, mask=ids != PAD)


# (b) prefill + decode through the contiguous cache: absorbed == expanded ----

@pytest.mark.parametrize("share", [{}, SHARE], ids=["whole", "share"])
def test_contiguous_cache_matches_reference_at_every_position(share):
    """Prefill (expanded) then single-token steps (ABSORBED, against the
    latent cache) equal the reference's expanded full forward."""
    cfg = tiny(**share)
    params, ids = weights(cfg, lora=True), tokens(T=16)
    want = reference_axk1.logits(params, as_file(cfg), ids, PAD, 2.0)
    P, T = 8, ids.shape[1]
    mask = ids != PAD
    caches = init_kv_cache(cfg, ids.shape[0], T, jnp.float32)
    assert len(caches) == 1 and caches[0].shape == (3, 3, 1, T, 40)
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


def test_decode_verify_equals_the_reference():
    """T > 1 against the cache (speculative verify, a prefill chunk): the
    expanded form over the cached latents."""
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
    want = reference_axk1.logits(params, as_file(cfg), ids, PAD)
    close(got, want[:, P:P + k + 1])


# (c) the paged session: bootstrap, chunked prefill, radix hit, COW ----------

@pytest.mark.parametrize("block_pages", [8, 1], ids=["one_block", "blocks"])
def test_paged_session_matches_reference_at_every_position(block_pages,
                                                           monkeypatch):
    """(`blocks`: a key block of the paged read is one page of 4 slots, so a
    row's read walks several blocks with the online softmax between them.)"""
    from nanorlhf_tpu.core import mla
    from nanorlhf_tpu.sampler.paged.session import DecodeSession

    monkeypatch.setattr(mla, "_BLOCK_PAGES", block_pages)

    cfg, n_new, P = tiny(**SHARE), 6, 8
    params = weights(cfg)
    prompts = tokens(rows=3, T=P)
    sess = DecodeSession(
        params, cfg, rows=3, prompt_len=P, max_tokens=n_new, page_size=4,
        eos_token_id=V + 5, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        greedy=True, capture_logprobs=True, sync_every=2)
    assert sess.latent_cache == 1
    assert sess.kv_bytes_per_token == 3 * 40 * 4       # layers x width x f32
    assert [c.shape for c in sess.state[3]] == [(3, 12, 1, 4, 32),
                                                (3, 12, 1, 1, 32)]
    sess.bootstrap(prompts, prompts != PAD)
    for _ in range(n_new):
        done, _ = sess.step()
        if done.all():
            break
    assert done.all()
    out, captured = np.asarray(sess.state[1]), np.asarray(sess.state[2])
    full = jnp.concatenate([prompts, jnp.asarray(out)], axis=1)
    real = jnp.concatenate([prompts != PAD, jnp.ones_like(out, bool)], axis=1)
    want = np.asarray(reference_axk1.logits(
        params, as_file(cfg), full, PAD, mask=real))[:, P - 1: -1]
    chosen = np.take_along_axis(want, out[..., None], axis=-1)[..., 0]
    assert (chosen >= want.max(axis=-1) - TOL * np.abs(want).max()).all()
    logp = chosen - np.log(np.exp(want - want.max(-1, keepdims=True)).sum(-1)) \
        - want.max(-1)
    close(captured, logp)


@pytest.mark.parametrize("block_pages", [8, 2], ids=["one_block", "blocks"])
def test_served_tokens_follow_the_reference_through_chunks_and_a_radix_hit(
        block_pages, monkeypatch):
    """`ServingEngine` on the latent pool: a cold prompt long enough for two
    KV-only prefill chunks and a final suffix, then two prompts that share a
    prefix ending inside a page (a radix hit with a copy-on-write page).
    Every served greedy token is the reference's argmax given the served
    context (to tolerance)."""
    from nanorlhf_tpu.core import mla
    from nanorlhf_tpu.serving.engine import ServingEngine

    monkeypatch.setattr(mla, "_BLOCK_PAGES", block_pages)
    cfg = tiny(**SHARE)
    params = weights(cfg)
    rng = np.random.RandomState(3)
    shared = rng.randint(2, V, 11).tolist()     # 10 pads + 11: ends in a page
    prompts = [rng.randint(2, V, 22).tolist(),
               shared + rng.randint(2, V, 3).tolist(),
               shared + rng.randint(2, V, 3).tolist()]
    n_new = 5
    with ServingEngine(params, cfg, eos_token_id=V + 5, pad_token_id=PAD,
                       page_size=4, prompt_len=24, max_new_tokens=8, rows=2,
                       prefill_chunk=8, sync_every=2) as eng:
        served = []
        for p in prompts:
            req, shed = eng.submit(p, greedy=True, max_tokens=n_new)
            assert shed is None
            served.append(list(eng.stream(req)))
        m = eng.metrics()
    assert m["serving/latent_cache"] == 1
    assert m["serving/kv_bytes_per_token"] == 3 * 40 * 4
    assert m["serving/prefix_hit_tokens"] >= len(shared) - 1
    assert m["serving/cow_splits"] >= 1
    assert eng.session.chunked_admissions >= 1
    for p, s in zip(prompts, served):
        assert len(s) == n_new
        ids = jnp.asarray([p + s])
        want = np.asarray(reference_axk1.logits(
            params, as_file(cfg), ids, PAD, last=n_new + 1))[0, :-1]
        chosen = want[np.arange(n_new), s]
        assert (chosen >= want.max(axis=-1) - TOL * np.abs(want).max()).all()


# (d) the chip's share -------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all four shares give (4 experts each of 16),
    plus the shared expert counted once, are the uncut layer; and the uncut
    layer is the reference's."""
    from nanorlhf_tpu.core.model import _mlp
    from nanorlhf_tpu.ops.moe import router_stats

    whole = tiny()
    params = weights(whole)
    p = jax.tree.map(lambda x: x[1], params["layers"])     # one expert layer
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64), jnp.float32)
    uncut, aux = _mlp(whole, h, p, None, 1.0)
    assert "absent" not in aux and int(aux["dropped"]) == 0
    shared = _mlp(whole, h, {k: v for k, v in p.items()
                             if k in ("shared_expert",)} | {
        "gate_proj": p["shared_expert"]["gate_proj"],
        "up_proj": p["shared_expert"]["up_proj"],
        "down_proj": p["shared_expert"]["down_proj"]}, None, 1.0)[0]
    routed = jnp.zeros_like(uncut)
    absent = here = 0
    for chip in range(4):
        cfg = tiny(experts_held=4, experts_offset=4 * chip)
        share = dict(p, experts=jax.tree.map(
            lambda x: x[4 * chip: 4 * chip + 4], p["experts"]))
        part, aux = _mlp(cfg, h, share, None, 1.0)
        routed = routed + (part - shared)
        assert int(aux["dropped"]) == 0
        absent += int(aux["absent"])
        stats = router_stats(jax.tree.map(lambda x: x[None], aux),
                             jnp.ones((2, 9), bool), 16)
        here += int(stats["load"][:, :, 4 * chip: 4 * chip + 4].sum())
    close(routed + shared, uncut)
    assert here == 2 * 9 * 4 and absent == 3 * here   # each assignment once
    close(uncut, reference_axk1._expert_mlp(
        h.reshape(-1, 64), p, as_file(whole)).reshape(h.shape))


def test_the_dense_layer_is_there_exactly_once():
    cfg = tiny()
    params = weights(cfg)
    assert params["dense_layers"]["gate_proj"]["kernel"].shape == (1, 64, 96)
    assert "router" not in params["dense_layers"]
    assert params["layers"]["router"]["kernel"].shape == (2, 64, 16)
    assert "gate_proj" not in params["layers"]
    ids = tokens()
    want = padded_forward_logits(params, cfg, ids, PAD)
    # zeroing the dense MLP's output changes the logits; the expert layers
    # hold no dense MLP to zero
    off = jax.tree.map(lambda x: x, params)
    off["dense_layers"] = dict(params["dense_layers"], down_proj={
        "kernel": jnp.zeros_like(params["dense_layers"]["down_proj"]["kernel"])})
    assert np.abs(np.asarray(padded_forward_logits(off, cfg, ids, PAD) - want)
                  ).max() > 1e-3
    no_dense = dataclasses.replace(cfg, first_k_dense_replace=0)
    assert "dense_layers" not in init_params(no_dense, jax.random.PRNGKey(0))


# (e) configuration -----------------------------------------------------------

@pytest.mark.parametrize("hf,match", [
    ({"model_type": "deepseek_v3", "n_routed_experts": 256,
      "n_shared_experts": 1, "num_experts_per_tok": 8}, "expert keys"),
    ({"model_type": "qwen2_moe", "num_experts": 60, "num_experts_per_tok": 4,
      "shared_expert_intermediate_size": 5632}, "expert keys"),
    ({"model_type": "axk1", "topk_method": "noaux_tc"}, "topk_method"),
    ({"model_type": "axk1", "topk_method": "none",
      "rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"model_type": "axk1", "topk_method": "none", "n_routed_experts": 16,
      "n_routed_experts_held": 4, "n_routed_experts_offset": 14}, "router"),
], ids=["deepseek_v3", "qwen2_moe", "topk_method", "rope_scaling", "offset"])
def test_from_hf_config_refuses_what_the_decoder_lacks(hf, match):
    base = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 32,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 4, "hidden_act": "silu"}
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**base, **hf})


def test_from_hf_config_reads_the_benchmark_file():
    with open(os.path.join(REPO, "benchmark", "configs", "axk1-ep16.json")) as f:
        published = json.load(f)
    got = ModelConfig.from_hf_config(published)
    want = dataclasses.replace(
        ModelConfig.axk1(), num_hidden_layers=published["num_hidden_layers"],
        vocab_size=published["vocab_size"], experts_held=12, experts_offset=0)
    assert got == want
    assert got.num_experts == 192 and got.num_dense_layers == 1
    assert got.latent_width == 576


def test_softmax_scale_and_yarn_are_the_published_numbers():
    from nanorlhf_tpu.core import mla

    cfg = ModelConfig.axk1()
    m = 0.1 * np.log(32.0) + 1.0
    assert abs(m - 1.3466) < 1e-4
    assert abs(mla.softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    cos, sin = mla.rope_tables(cfg, jnp.asarray([[0, 1, 5000]]))
    assert cos.shape == (1, 3, 64)
    assert float(jnp.abs(cos[0, 0] - 1.0).max()) == 0.0    # multiplier is 1
    # the fastest pair keeps theta's frequency, the slowest is slowed x32
    ang = np.arctan2(np.asarray(sin[0, 1]), np.asarray(cos[0, 1]))
    assert abs(ang[0] - 1.0) < 1e-6
    assert abs(ang[31] - 10_000.0 ** (-62 / 64) / 32) < 1e-9


# (f) HF names, adapters, specs, what raises ----------------------------------

def test_hf_state_dict_round_trip(tmp_path):
    from nanorlhf_tpu.core.params import (export_hf_checkpoint,
                                          hf_state_dict_from_params,
                                          load_hf_checkpoint,
                                          params_from_hf_state_dict)

    cfg = tiny(**SHARE)
    params = weights(cfg)
    sd = hf_state_dict_from_params(cfg, params)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (96, 64)
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 64)
    assert sd["model.layers.2.mlp.experts.11.down_proj.weight"].shape == (64, 32)
    assert "model.layers.1.mlp.experts.7.down_proj.weight" not in sd
    assert sd["model.layers.1.mlp.shared_experts.up_proj.weight"].shape == (32, 64)
    assert sd["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"].shape == (40, 64)
    assert sd["model.layers.0.self_attn.q_a_layernorm.weight"].shape == (24,)
    assert not any("layers.0.mlp.experts" in k or "layers.0.mlp.gate." in k
                   for k in sd)
    # rotary columns leave interleaved: x0 y0 x1 y1 <- x0 x1 .. y0 y1 ..
    ours = np.asarray(params["layers"]["kv_a_proj"]["kernel"][0])[:, 32:]
    theirs = np.asarray(sd["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"]).T[:, 32:]
    assert np.array_equal(theirs[:, 0::2], ours[:, :4])
    assert np.array_equal(theirs[:, 1::2], ours[:, 4:])
    back = params_from_hf_state_dict(cfg, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    out = export_hf_checkpoint(cfg, params, str(tmp_path / "ckpt"), dtype="float32")
    with open(os.path.join(out, "config.json")) as f:
        written = json.load(f)
    assert written["model_type"] == "axk1" and "head_dim" not in written
    loaded_cfg, loaded = load_hf_checkpoint(out, jnp.float32)
    assert loaded_cfg == dataclasses.replace(cfg, max_position_embeddings=1024)
    ids = tokens()
    assert np.array_equal(
        np.asarray(padded_forward_logits(loaded, loaded_cfg, ids, PAD)),
        np.asarray(padded_forward_logits(params, cfg, ids, PAD)))


def test_lora_targets_follow_the_model():
    lc = LoraConfig(r=4, alpha=8)
    assert lora_targets(tiny(), lc) == MLA_TARGETS
    params = weights(tiny(), lora=True)
    assert set(params["lora"]) == {"dense_layers", "layers"}
    assert set(params["lora"]["layers"]) == set(MLA_TARGETS)
    mask = trainable_mask(params, lc)
    assert not any(jax.tree.leaves(mask["layers"]))
    assert not any(jax.tree.leaves(mask["dense_layers"])) and mask["lm_head"]
    merged = merge_lora(params, 2.0)
    ids = tokens()
    close(padded_forward_logits(merged, tiny(), ids, PAD),
          padded_forward_logits(params, tiny(), ids, PAD, 2.0))


def test_sharded_logits_match_single_device():
    from jax.sharding import NamedSharding

    from nanorlhf_tpu.parallel import MeshConfig, make_mesh, param_sharding_rules

    cfg = tiny(**SHARE)
    params, ids = weights(cfg, lora=True), tokens(rows=4)
    want = padded_forward_logits(params, cfg, ids, PAD, 2.0)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                     devices=jax.devices()[:4])
    rules = param_sharding_rules(params)
    assert tuple(rules["layers"]["kv_b_proj"]["kernel"]) == (None, None, "tensor")
    assert tuple(rules["dense_layers"]["q_a_proj"]["kernel"]) == (None, "fsdp", None)
    assert tuple(rules["dense_layers"]["gate_proj"]["kernel"]) == (
        None, "fsdp", "tensor")
    assert tuple(rules["layers"]["shared_expert"]["down_proj"]["kernel"]) == (
        None, "tensor", "fsdp")
    assert tuple(rules["layers"]["experts"]["up_proj"]["kernel"]) == (
        None, "tensor", "fsdp", None)
    sharded = jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params, rules)
    got = jax.jit(lambda p, x: padded_forward_logits(p, cfg, x, PAD, 2.0))(
        sharded, ids)
    close(got, want)


def test_int8_kv_cache_raises_on_a_latent_cache():
    cfg = tiny(kv_cache_quant="int8")
    with pytest.raises(ValueError, match="latent"):
        init_kv_cache(cfg, 2, 8)


def test_ring_attention_raises():
    from nanorlhf_tpu.core.model import _hidden_from_inputs

    cfg, ids = tiny(), tokens()
    with pytest.raises(NotImplementedError, match="sequence-parallel"):
        _hidden_from_inputs(weights(cfg), cfg, ids, ids != PAD,
                            jnp.zeros_like(ids), 1.0, False,
                            attn_fn=lambda q, k, v: q)


# (g) generate() and one RLTrainer update --------------------------------------

def test_generate_runs_the_contiguous_rollout():
    from nanorlhf_tpu.sampler import SamplingParams, generate

    cfg = tiny(**SHARE)
    params, ids = weights(cfg), tokens(rows=2, T=8)
    out = generate(params, cfg, ids, ids != PAD, jax.random.PRNGKey(0),
                   SamplingParams(n=1, max_tokens=5, greedy=True),
                   eos_token_id=V + 5, pad_token_id=PAD)
    out = np.asarray(out)
    full = jnp.concatenate([ids, jnp.asarray(out)], axis=1)
    real = jnp.concatenate([ids != PAD, jnp.ones_like(out, bool)], axis=1)
    want = np.asarray(reference_axk1.logits(
        params, as_file(cfg), full, PAD, mask=real))[:, 7:-1]
    chosen = np.take_along_axis(want, out[..., None], axis=-1)[..., 0]
    assert (chosen >= want.max(axis=-1) - TOL * np.abs(want).max()).all()


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    out = tmp_path_factory.mktemp("axk1_trainer")
    tok = ToyTokenizer(vocab_size=256)
    cfg = ModelConfig.axk1_tiny(vocab_size=256, experts_held=4, experts_offset=4)
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
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b), before, after)
    assert moved["lora"]["layers"]["kv_b_proj"]["b"] and moved["lm_head"]
    assert moved["lora"]["dense_layers"]["q_a_proj"]["b"]
    assert not any(jax.tree.leaves(moved["layers"]))        # router, experts
    assert not any(jax.tree.leaves(moved["dense_layers"]))


@pytest.mark.parametrize("counter,lo,hi", [
    ("moe/dropped_tokens", 0.0, 0.0),
    ("moe/held_experts", 4.0, 4.0),
    ("moe/absent_assignments", 1.0, np.inf),
    ("moe/routed_here_frac", 0.05, 0.6),
    ("moe/router_entropy", 0.5, np.log(16) + 1e-6),
])
def test_trainer_row_has_the_moe_counters(trained, counter, lo, hi):
    row = trained[2][0]
    assert lo <= row[counter] <= hi, (counter, row[counter])


@pytest.mark.parametrize("option,match", [
    (dict(rollout_quant="int8"), "sparse-expert"),
    (dict(kv_cache_quant="int8"), "latent"),
], ids=["rollout_quant", "kv_cache_quant"])
def test_what_the_training_path_cannot_do_raises(tmp_path, option, match):
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    tok = ToyTokenizer(vocab_size=256)
    cfg = ModelConfig.axk1_tiny(vocab_size=256)
    rl = RLConfig(algo=AlgoName.GRPO, output_dir=str(tmp_path), sample_n=2,
                  response_length=4, use_lora=True, lora_r=4,
                  mesh=MeshConfig(2, 2, 2), **option)
    with pytest.raises(ValueError, match=match):
        RLTrainer(rl, cfg, tok,
                  init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
                  load_prompt_dataset("synthetic:16", tok, max_prompt_len=8),
                  lambda texts, eos: np.zeros(len(texts), np.float32))


def test_a_share_in_token_blocks_is_the_same_layer(monkeypatch):
    """Past `_SHARE_TOKEN_BLOCK` tokens a chip's share goes in blocks (the
    dispatch buffers of a long scoring row): same logits, same counters."""
    from nanorlhf_tpu.core import model

    cfg = tiny(**SHARE)
    params, ids = weights(cfg), tokens(rows=3, T=14)
    want, stats = padded_forward_logits(params, cfg, ids, PAD, router_stats=True)
    monkeypatch.setattr(model, "_SHARE_TOKEN_BLOCK", 16)    # 42 tokens: 3 x 14
    got, blocked = padded_forward_logits(params, cfg, ids, PAD, router_stats=True)
    close(got, want, mask=ids != PAD)
    assert int(blocked["absent"]) == int(stats["absent"])
    assert int(blocked["dropped"]) == 0
    assert np.array_equal(np.asarray(blocked["load"]), np.asarray(stats["load"]))
    monkeypatch.setattr(model, "_SHARE_TOKEN_BLOCK", 20)    # 3 x 14 again: no pad
    monkeypatch.setattr(model, "_SHARE_TOKEN_BLOCK", 32)    # 2 x 21: no pad
    close(padded_forward_logits(params, cfg, ids, PAD), want, mask=ids != PAD)
    monkeypatch.setattr(model, "_SHARE_TOKEN_BLOCK", 25)    # 2 x 21
    ids5 = tokens(rows=3, T=15)                             # 45 = 2 x 23 - 1
    w5, s5 = padded_forward_logits(params, cfg, ids5, PAD, router_stats=True)
    monkeypatch.setattr(model, "_SHARE_TOKEN_BLOCK", 10 ** 6)
    u5, t5 = padded_forward_logits(params, cfg, ids5, PAD, router_stats=True)
    close(w5, u5, mask=ids5 != PAD)
    assert int(s5["absent"]) == int(t5["absent"])


def test_a_share_dispatches_the_live_rows_only():
    """A decode step runs every resident row; a chip's share computes its
    experts for the rows someone listens to. Those rows' output is what it
    was; the others get the shared expert alone, and their assignments count
    as absent. `reached` counts the held experts (8-11) that got a row."""
    from nanorlhf_tpu.core.model import _mlp

    cfg = tiny(**SHARE)
    p = jax.tree.map(lambda x: x[0], weights(cfg)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(7), (6, 1, 64), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, False])
    want, aux_all = _mlp(cfg, h, p, None, 1.0)
    got, aux = _mlp(cfg, h, p, None, 1.0, live=live)
    close(got[live], want[live])
    shared = _mlp(tiny(), h, {"gate_proj": p["shared_expert"]["gate_proj"],
                              "up_proj": p["shared_expert"]["up_proj"],
                              "down_proj": p["shared_expert"]["down_proj"]},
                  None, 1.0)[0]
    close(got[~live], shared[~live])
    assert int(aux["absent"]) >= int(aux_all["absent"]) and int(aux["dropped"]) == 0
    chosen, counted = np.asarray(aux["experts"]), np.asarray(live)
    here = (chosen >= 8) & (chosen < 12)
    assert int(aux["absent"]) == 6 * 4 - int(here[counted].sum())
    assert int(aux["reached"]) == len(set(chosen[counted][here[counted]])) > 0
    assert int(aux_all["reached"]) == len(set(chosen[here])) >= int(aux["reached"])


def test_the_engine_counts_the_held_experts_its_live_row_reached():
    """`serving/held_experts_hit` over `serving/decode_steps`: one request in
    an engine of two rows, so every step has one live row and one nobody
    listens to. A lone token reaches as many held experts as it has
    assignments on them, so the count is the held part of the router's load
    (`padded_forward_logits`' stats) over the tokens the steps were fed: the
    served ones but the last. The other row adds nothing."""
    from nanorlhf_tpu.core.model import padded_forward_logits
    from nanorlhf_tpu.serving.engine import ServingEngine

    cfg = tiny(**SHARE)
    params = weights(cfg)
    prompt, n_new = np.random.RandomState(9).randint(2, V, 9).tolist(), 6
    with ServingEngine(params, cfg, eos_token_id=V + 5, pad_token_id=PAD,
                       page_size=4, prompt_len=12, max_new_tokens=8, rows=2,
                       sync_every=2) as eng:
        req, shed = eng.submit(prompt, greedy=True, max_tokens=n_new)
        assert shed is None
        served = list(eng.stream(req))
        m = eng.metrics()
    assert len(served) == n_new and m["serving/decode_steps"] == n_new - 1

    def held_load(ids):
        _, stats = padded_forward_logits(params, cfg, jnp.asarray([ids]), PAD,
                                         response_context_length=1,
                                         router_stats=True)
        return float(np.asarray(stats["load"])[0, :, 8:12].sum())

    fed = held_load(prompt + served[:-1]) - held_load(prompt)
    assert m["serving/held_experts_hit"] == fed and fed > 0
