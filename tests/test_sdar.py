"""SDAR-MoE (docs/BLOCKDIFF.md): Qwen3-MoE's layer under a BLOCK-causal
mask, generated by diffusion over blocks of four, held to
`benchmark/harness/reference_sdar.py` on seeded float32 weights.

Tolerances, with their reasons. Both sides are float32 with matmuls at
"highest": they differ by the order of their sums (2e-5 of logits of
std ~1 is forty times what this file reads, 5e-7). That is tight enough
that bf16 where float32 is stated (1e-2), a causal mask, a shift, a missing
per-head norm, unrenormalised routing or another block length reads as wrong
by orders of magnitude: the negative controls below show each. A served token
must be the reference's argmax at its position IN THE FORWARD THAT UNMASKED
IT, within `TOL` of the top logit (a tie), and the positions a forward
unmasked must be the ones the schedule picks from the reference's
confidences: a missing commit, a stale provisional K/V or a wrong position
changes both.
"""

import dataclasses
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import reference_sdar as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.sampler import blockdiff  # noqa: E402
from nanorlhf_tpu.sampler.paged.session import DecodeSession  # noqa: E402
from nanorlhf_tpu.serving.engine import ServingEngine  # noqa: E402
from nanorlhf_tpu.serving.radix import RadixCache  # noqa: E402

EOS, PAD, B, V = 1, 0, 4, 128
TOL = 2e-5
CFG = ModelConfig.sdar_tiny(vocab_size=V)
# the reference reads a configuration FILE's keys
HF = {"model_type": "sdar_moe", "hidden_size": 64, "intermediate_size": 96,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
      "norm_topk_prob": True, "tie_word_embeddings": False, "vocab_size": V,
      "max_position_embeddings": 1024, "attention_bias": False,
      "block_length": B, "mask_token_id": V - 1}
# the catalog row's `config` (model-configs guide, SDAR-30B-A3B-Chat)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
ENGINE = dict(eos_token_id=EOS, pad_token_id=PAD, page_size=8, prompt_len=24,
              max_new_tokens=12, rows=3, sync_every=2, prefill_chunk=8,
              headroom=0.0)


def lay_weights(cfg=CFG, seed=0):
    """`init_params` with what a missing mechanism must show laid over it:
    norm weights that are not ones (the per-head ones too), a router whose
    top-2 weights are far from equal, and the head's EOS and pad columns
    zeroed (no row ends early, no served token is the pad id)."""
    params = init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    layers = params["layers"]
    for name in ("input_layernorm", "post_attention_layernorm", "q_norm",
                 "k_norm"):
        layers[name] = jnp.exp(0.4 * jax.random.normal(
            next(keys), layers[name].shape))
    layers["router"]["kernel"] = layers["router"]["kernel"] * 3.0
    params["embed_tokens"] = params["embed_tokens"] * 40.0
    params["lm_head"] = (params["lm_head"] * 20.0).at[:, jnp.asarray(
        [EOS, PAD])].set(0.0)
    return params


@pytest.fixture(scope="module")
def params():
    return lay_weights()


def prompt_of(n, seed):
    return np.random.default_rng([seed, n]).integers(3, V - 8, n).tolist()


# ------------------------------------------------------------------ config

def test_from_hf_config_builds_the_catalog_row():
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert cfg == dataclasses.replace(
        ModelConfig.sdar_30b_a3b(), rope_theta=1e6)
    assert cfg.block_generation and cfg.block_length == 4
    assert cfg.mask_token_id == 151669 and cfg.qk_norm_per_head
    assert cfg.expert_width == 768 and cfg.attention_pattern is None
    stated = ModelConfig.from_hf_config(
        {**CATALOG, "block_length": 8, "mask_token_id": 7})
    assert (stated.block_length, stated.mask_token_id) == (8, 7)


@pytest.mark.parametrize("key,value,match", [
    ("use_sliding_window", True, "sliding window"),
    ("sliding_window", 4096, "sliding window"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("mlp_only_layers", [0, 1], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("block_length", 0, "block_length"),
    ("mask_token_id", 151936, "mask_token_id"),
])
def test_from_hf_config_raises_by_name(key, value, match):
    with pytest.raises(ValueError, match=match) as e:
        ModelConfig.from_hf_config({**CATALOG, key: value})
    assert "sdar_moe" in str(e.value)


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    sd = hf_state_dict_from_params(CFG, params)
    assert sd["model.layers.1.mlp.experts.7.gate_proj.weight"].shape == (32, 64)
    assert sd["model.layers.1.mlp.experts.0.down_proj.weight"].shape == (64, 32)
    assert sd["model.layers.0.mlp.gate.weight"].shape == (8, 64)
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (16,)
    assert sd["model.layers.1.self_attn.k_norm.weight"].shape == (16,)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (64, 64)
    assert "model.layers.0.self_attn.q_proj.bias" not in sd
    assert sd["lm_head.weight"].shape == (V, 64)
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_exported_config_reads_back(tmp_path, params):
    from nanorlhf_tpu.core.params import export_hf_checkpoint

    out = export_hf_checkpoint(CFG, params, str(tmp_path), dtype="float32")
    with open(os.path.join(out, "config.json")) as f:
        written = json.load(f)
    assert written["model_type"] == "sdar_moe" and written["block_length"] == B
    assert ModelConfig.from_hf_config(written) == dataclasses.replace(
        CFG, expert_token_block=4096)


# ------------------------------------------------- the forward against M

def ours_logits(params, cfg, ids):
    return np.asarray(M.padded_forward_logits(
        params, cfg, jnp.asarray(ids)[None], PAD)[0])


@pytest.mark.parametrize("layers", [1, 2])
@pytest.mark.parametrize("length", [5, 13, 16])
def test_the_forward_is_the_references_under_the_block_mask(layers, length):
    cfg = dataclasses.replace(CFG, num_hidden_layers=layers)
    params = lay_weights(cfg, seed=layers)
    ids = prompt_of(length, 3)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, HF, jnp.asarray(ids), length))
        got = ours_logits(params, cfg, ids)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


def test_a_left_padded_row_keeps_its_blocks_at_absolute_positions(params):
    ids = prompt_of(10, 4)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, HF, jnp.asarray(ids), 10))
        got = np.asarray(M.padded_forward_logits(
            params, CFG, jnp.asarray([[PAD] * 3 + ids]), PAD)[0, 3:])
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


CONTROLS = {"causal": {"causal": True}, "shift": {"shift": True},
            "block_1": {"block_length": 1}, "block_8": {"block_length": 8},
            "no_head_norm": {"qk_norm": False}, "no_renorm": {"renorm": False}}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_forward_is_not_a_control_model(params, control):
    ids = prompt_of(14, 5)
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.forward(params, HF, jnp.asarray(ids), 14,
                                       **CONTROLS[control]))
        got = ours_logits(params, CFG, ids)
    assert np.abs(got - other).max() > 1e3 * TOL * np.abs(other).max()


def test_bfloat16_where_float32_is_stated_reads_as_wrong(params):
    ids = prompt_of(14, 5)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, HF, jnp.asarray(ids), 14))
    got = ours_logits(half, CFG, ids).astype(np.float32)
    assert np.abs(got - want).max() > 100 * TOL * np.abs(want).max()


def paged_prefill_then_blocks(params, cfg, ids, chunk):
    """The prompt's whole blocks in pieces of `chunk` through `decode_verify`
    over a paged cache, as the session's admission runs them; returns the
    logits of every piece, joined."""
    n, page = len(ids), 8
    nb = -(-(n + B) // page)
    caches = M.init_paged_kv_cache(cfg, nb, page, jnp.float32)
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    out = []
    for s in range(0, n, chunk):
        piece = ids[s:s + chunk]
        km = (jnp.arange(nb * page) < s)[None]
        lg, caches = M.decode_verify(
            params, cfg, jnp.asarray([piece]),
            s + jnp.arange(len(piece))[None], jnp.asarray([s]), km, caches,
            page_table=table, page_size=page)
        out.append(np.asarray(lg[0]))
    return np.concatenate(out)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_a_chunked_prefill_is_block_causal(params, chunk):
    ids = prompt_of(16, 6)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, HF, jnp.asarray(ids), 16))
        got = paged_prefill_then_blocks(params, CFG, ids, chunk)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


def test_prefill_is_block_causal(params):
    ids = prompt_of(12, 7)
    caches = M.init_kv_cache(CFG, 1, 16, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, HF, jnp.asarray(ids), 12))[-1]
        got, _ = M.prefill(params, CFG, jnp.asarray([ids]),
                           jnp.ones((1, 12), bool), caches)
    assert np.abs(np.asarray(got[0]) - want).max() < TOL * np.abs(want).max()


@pytest.mark.parametrize("impl,paged,form", [
    ("auto", False, "view"), ("auto", True, "view"),
    ("pallas", True, "paged_block"), ("pallas", False, "view")])
def test_the_block_reads_row_of_attention_form(impl, paged, form):
    cfg = dataclasses.replace(CFG, attention_impl=impl)
    assert M.attention_form(cfg, B, cached=True, paged=paged, decode=True,
                            cache_len=64) == form
    assert M.attention_form(cfg, 16, cached=False) == "query_blocks"
    assert M.attention_form(cfg, 16, cached=True, paged=True, verify=True,
                            cache_len=64) == "paged_walk"
    assert M.attention_form(cfg, 16, cached=True, cache_len=64) == "view"


def test_the_kernels_block_forward_is_the_plain_ones():
    """`attention_impl="pallas"` (interpreted here) at heads of 128 lanes and
    pages of whole tiles: the block's read rides the in-place paged decode
    kernel's group axis and its write goes a slot at a time through the
    live-row kernel; logits and the live rows' pages are the plain form's
    (the gathered view, the row scatter), and a row nobody listens to
    writes nothing."""
    cfg = dataclasses.replace(CFG, head_dim=128, num_attention_heads=2,
                              num_key_value_heads=1, num_hidden_layers=1)
    params = lay_weights(cfg, seed=3)
    page, nb, rows = 8, 4, 3
    caches = M.init_paged_kv_cache(cfg, rows * nb, page, jnp.float32)
    table = jnp.arange(rows * nb, dtype=jnp.int32).reshape(rows, nb)
    fill = jnp.asarray([8, 5, 12])              # one block straddles pages
    key_mask = jnp.arange(nb * page)[None, :] < fill[:, None]
    key_mask = key_mask & (jnp.arange(nb * page)[None, :] >= jnp.asarray(
        [0, 1, 4])[:, None])
    # a committed prefix to read: the same random K/V in both pools
    caches = tuple(jax.random.normal(jax.random.PRNGKey(i), c.shape)
                   for i, c in enumerate(caches))
    tokens = jnp.asarray([[5, 6, V - 1, V - 1], [9, V - 1, 7, V - 1],
                          [V - 1] * 4])
    live = jnp.asarray([True, True, False])
    args = (tokens, fill[:, None] + jnp.arange(B)[None], fill, key_mask)
    out = {}
    for impl in ("xla", "pallas"):
        c = dataclasses.replace(cfg, attention_impl=impl)
        logits, new, hit = jax.jit(lambda p, kv, c=c: M.block_forward(
            p, c, *args, kv, page_table=table, page_size=page, live=live,
            count_experts=True))(params, caches)
        out[impl] = (np.asarray(logits), [np.asarray(a) for a in new], hit)
    assert M.attention_form(dataclasses.replace(cfg, attention_impl="pallas"),
                            B, cached=True, paged=True, decode=True,
                            cache_len=32) == "paged_block"
    np.testing.assert_allclose(out["pallas"][0][:2], out["xla"][0][:2],
                               rtol=2e-5, atol=2e-5)
    assert int(out["pallas"][2]) == int(out["xla"][2]) > 0
    for got, want, before in zip(out["pallas"][1], out["xla"][1], caches):
        before = np.asarray(before)
        # the live rows' pages bit for bit; the third row's untouched
        np.testing.assert_array_equal(got[:, :2 * nb], want[:, :2 * nb])
        np.testing.assert_array_equal(got[:, 2 * nb:], before[:, 2 * nb:])
        assert (want[:, :2 * nb] != before[:, :2 * nb]).any()


# ------------------------------------------------- sampling and the choice

@pytest.mark.parametrize("steps,want", [
    (1, [4]), (2, [2, 2]), (3, [2, 1, 1]), (4, [1, 1, 1, 1])])
def test_the_schedule_spreads_the_block_over_the_steps(steps, want):
    got = [int(blockdiff.transfer_count(s, steps, B)) for s in range(steps)]
    assert got == want and sum(got) == B


def expected_choice(conf, masked, n, strategy, threshold=0.9):
    """The rule of docs/BLOCKDIFF.md in numpy: which positions a forward
    unmasks, from the confidences at hand."""
    idx = [i for i in range(len(conf)) if masked[i]]
    if strategy == "sequential":
        order = idx
    else:
        order = sorted(idx, key=lambda i: (-conf[i], i))
    chosen = set(order[:n])
    if strategy == "low_confidence_dynamic":
        chosen |= {i for i in idx if conf[i] > threshold}
    return sorted(chosen)


@pytest.mark.parametrize("strategy", blockdiff.REMASKING)
@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_choice_follows_the_rule(strategy, n):
    rng = np.random.default_rng([n, len(strategy)])
    conf = rng.random((6, B)).astype(np.float32)
    conf[0, :] = 0.5            # ties go to the lower position
    conf[1, 1:] = 0.95          # over the threshold
    masked = rng.random((6, B)) < 0.7
    masked[0] = True
    masked[1] = True
    got = np.asarray(blockdiff.choose_unmask(
        jnp.asarray(conf), jnp.asarray(masked), jnp.full((6,), n, jnp.int32),
        jnp.full((6,), blockdiff.REMASKING.index(strategy), jnp.int32)))
    for r in range(6):
        assert np.flatnonzero(got[r]).tolist() == expected_choice(
            conf[r], masked[r], n, strategy), (r, conf[r], masked[r])


def test_confidence_is_the_drawn_tokens_probability():
    logits = jax.random.normal(jax.random.PRNGKey(3), (3, B, V)) * 3.0
    greedy = jnp.asarray([True, False, False])
    per = lambda a: jnp.repeat(jnp.asarray(a), B)   # noqa: E731
    tok, conf = (a.reshape(3, B) for a in blockdiff.sample_positions(
        jax.random.PRNGKey(4), logits.reshape(3 * B, V),
        per([1.0, 0.7, 1.0]), per([1.0, 0.9, 1.0]), per(greedy), top_k=V,
        approx_top_k=False))
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(tok[0]), p[0].argmax(-1))
    np.testing.assert_allclose(np.asarray(conf[0]), p[0].max(-1), rtol=1e-5)
    # top_p 1, temperature 1, every candidate kept: the plain softmax's
    np.testing.assert_allclose(
        np.asarray(conf[2]),
        np.take_along_axis(p[2], np.asarray(tok[2])[:, None], 1)[:, 0],
        rtol=1e-5)
    # a nucleus: renormalised over what was kept, so never smaller
    scaled = np.asarray(jax.nn.softmax(logits[1] / 0.7, axis=-1))
    assert np.all(np.asarray(conf[1]) >= np.take_along_axis(
        scaled, np.asarray(tok[1])[:, None], 1)[:, 0] - 1e-6)


# --------------------------------------- generation against the replay

# name: (prompt length, denoising steps, remasking, max_tokens)
CASES = {
    "tail0_static4": (8, 4, "low_confidence_static", 12),
    "tail1_static2": (9, 2, "low_confidence_static", 10),
    "tail2_static1": (10, 1, "low_confidence_static", 9),
    "tail3_static3": (11, 3, "low_confidence_static", 12),
    "tail0_dynamic": (12, 4, "low_confidence_dynamic", 8),
    "tail2_dynamic": (14, 2, "low_confidence_dynamic", 7),
    "tail1_sequential": (13, 4, "sequential", 11),
    "tail3_sequential": (7, 2, "sequential", 6),
    "two_pieces": (20, 4, "low_confidence_static", 12),
    "three_pieces_tail3": (23, 2, "low_confidence_static", 5),
    "shorter_than_a_block": (3, 4, "low_confidence_static", 6),
    "budget_one": (9, 4, "low_confidence_static", 1),
}


def ask(engine, prompt, steps, strategy, max_tokens, **kw):
    req, why = engine.submit(prompt, greedy=True, max_tokens=max_tokens,
                             denoising_steps=steps, remasking=strategy, **kw)
    assert why is None
    return req


def answer(engine, req) -> dict:
    tokens = list(engine.stream(req, timeout=60))
    return {"tokens": tokens, "unmask_steps": list(req.unmask_steps),
            "cut_tokens": list(req.cut_tokens),
            "cut_unmask_steps": list(req.cut_unmask_steps)}


@pytest.fixture(scope="module")
def served(params):
    """Every case through ONE engine of three rows, asked at once: later
    requests are admitted as rows come free, mid-chunk, beside rows that
    stand at other steps of other blocks."""
    with ServingEngine(params, CFG, **ENGINE) as engine:
        asked = {name: (prompt_of(c[0], 11), c) for name, c in CASES.items()}
        reqs = {name: ask(engine, p, c[1], c[2], c[3])
                for name, (p, c) in asked.items()}
        out = {name: (asked[name][0], answer(engine, r))
               for name, r in reqs.items()}
        out["metrics"] = engine.metrics()
        out["radix"] = engine.radix.snapshot()
    return out


def follows_the_reference(params, prompt, resp, steps, strategy, **controls):
    """(largest gap of an unmasked token under the reference's top at its
    position in its forward, forwards whose choice of positions is not the
    rule's from the reference's confidences, forwards)."""
    with jax.default_matmul_precision("highest"):
        r = ref.replay(params, HF, prompt,
                       resp["tokens"] + resp["cut_tokens"],
                       resp["unmask_steps"] + resp["cut_unmask_steps"],
                       **controls)
    gap = (r["top"] - r["probes"][..., 0])[r["chosen"]]
    conf = np.exp(r["top"] - r["lse"])
    wrong = 0
    for n in range(len(r["start"])):
        want = expected_choice(
            conf[n], r["masked"][n],
            int(blockdiff.transfer_count(int(r["step"][n]), steps, B)),
            strategy)
        wrong += want != np.flatnonzero(r["chosen"][n]).tolist()
    return float(gap.max()), wrong, len(r["start"])


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_engine_generates_what_the_reference_replays(params, served, name):
    plen, steps, strategy, budget = CASES[name]
    prompt, resp = served[name]
    assert len(resp["tokens"]) == budget == len(resp["unmask_steps"])
    # streamed and cut tokens together are the row's whole blocks
    assert (plen % B + budget + len(resp["cut_tokens"])) % B == 0
    assert len(resp["cut_tokens"]) < B
    assert max(resp["unmask_steps"] + resp["cut_unmask_steps"]) < steps
    gap, wrong, forwards = follows_the_reference(
        params, prompt, resp, steps, strategy)
    assert gap < TOL * 20 and wrong == 0, (gap, wrong, forwards)


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_replay_tells_a_control_model_apart(params, served, control):
    """The comparison can fail: against each control the served tokens sit
    far under the reference's top or the chosen positions are not its."""
    prompt, resp = served["tail1_static2"]
    gap, wrong, _ = follows_the_reference(
        params, prompt, resp, 2, "low_confidence_static", **CONTROLS[control])
    assert gap > 1e3 * TOL or wrong > 0, (gap, wrong)


def test_the_replay_tells_a_commit_that_wrote_nothing_apart(params, served):
    """The counters cannot (`commit_forwards` = `blocks_done` holds for a
    commit forward that runs and writes nothing): a reference whose later
    blocks read each block's K/V as its last denoise forward left them puts
    the served tokens far under its top."""
    prompt, resp = served["tail1_static2"]
    gap, wrong, _ = follows_the_reference(
        params, prompt, resp, 2, "low_confidence_static", stale_commit=True)
    assert gap > 1e3 * TOL or wrong > 0, (gap, wrong)
    with pytest.raises(ValueError, match="stale_commit"):
        follows_the_reference(params, prompt, resp, 2,
                              "low_confidence_static", stale_commit=True,
                              literal=True)


@pytest.mark.parametrize("name", ["tail1_static2", "two_pieces"])
def test_the_replays_two_streams_are_the_literal_rebuild(params, served, name):
    prompt, resp = served[name]
    args = (params, HF, prompt, resp["tokens"] + resp["cut_tokens"],
            resp["unmask_steps"] + resp["cut_unmask_steps"])
    with jax.default_matmul_precision("highest"):
        fast, slow = ref.replay(*args), ref.replay(*args, literal=True)
    for key in ("top", "lse", "probes"):
        assert np.abs(fast[key] - slow[key]).max() < TOL
    np.testing.assert_array_equal(fast["argmax"], slow["argmax"])


def test_the_counters_add_up(served):
    m = served["metrics"]
    n_cases = len(CASES)
    streamed = sum(c[3] for c in CASES.values())
    cut = sum(len(served[name][1]["cut_tokens"]) for name in CASES)
    assert m["serving/completed"] == n_cases
    assert m["serving/tokens_streamed"] == streamed
    assert m["serving/tokens_cut"] == cut
    assert m["serving/tokens_unmasked"] == streamed + cut
    assert m["serving/commit_forwards"] == m["serving/blocks_done"] == (
        streamed + cut + sum(c[0] % B for c in CASES.values())) // B
    assert m["serving/prompt_tail_tokens"] == sum(
        c[0] % B for c in CASES.values())
    assert m["serving/block_forwards"] > m["serving/commit_forwards"]
    assert m["serving/decode_steps"] * ENGINE["rows"] >= m[
        "serving/block_forwards"]
    assert m["serving/held_experts_hit"] > 0
    assert m["serving/block_length"] == B


def test_the_family_takes_no_prefix_hit(served):
    """A row's pages never enter the radix tree (the prompt's tail has no
    K/V until its block commits), so a repeated prompt is prefilled again."""
    assert served["metrics"]["serving/prefix_hit_tokens"] == 0
    assert served["radix"]["hit_tokens"] == 0
    assert served["metrics"]["pages/shared"] == 0


def test_a_repeated_prompt_is_served_cold_and_alike(params):
    with ServingEngine(params, CFG, **ENGINE) as engine:
        prompt = prompt_of(17, 12)      # two pages and a tail: a hit would
        first = answer(engine, ask(     # cross a page boundary
            engine, prompt, 2, "low_confidence_static", 8))
        again = answer(engine, ask(
            engine, prompt, 2, "low_confidence_static", 8))
        m = engine.metrics()
    assert first == again
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/prefill_token_dispatch"] == 2 * (8 + 8)


def test_eos_inside_a_block_ends_the_row_and_cuts_the_rest(params, served):
    """The row ends with the block that holds EOS; what the block holds past
    it is unmasked all the same, cut before emission and kept for a replay."""
    prompt, resp = served["tail1_static2"]
    # a generated token in the middle of its block (positions 9.. : the
    # block [8, 12) holds the generated indices 0..2), seen nowhere earlier
    tokens = resp["tokens"]
    at = next(g for g in range(len(tokens)) if (9 + g) % B in (1, 2)
              and tokens[g] not in tokens[:g])
    kw = dict(ENGINE, eos_token_id=tokens[at])
    with ServingEngine(params, CFG, **kw) as engine:
        got = answer(engine, ask(engine, prompt, 2, "low_confidence_static", 10))
        m = engine.metrics()
    assert got["tokens"] == tokens[:at + 1]
    assert len(got["cut_tokens"]) == B - 1 - (9 + at) % B
    assert got["cut_tokens"] == (tokens + resp["cut_tokens"])[
        at + 1:at + 1 + len(got["cut_tokens"])]
    assert m["serving/tokens_unmasked"] == at + 1 + len(got["cut_tokens"])
    assert m["serving/commit_forwards"] == m["serving/blocks_done"]


# ----------------------------------------------- the session, driven by hand

def session_of(params, **kw):
    radix = RadixCache(headroom=0.0)
    return DecodeSession(
        params, CFG, rows=2, prompt_len=16, max_tokens=8, page_size=8,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, prefix_cache=radix, prefill_chunk=8,
        **{"sync_every": 1, **kw})


def admit(sess, r, prompt, index, budget, steps, strategy):
    toks = np.full(sess.Tp, PAD, np.int32)
    toks[sess.Tp - len(prompt):] = prompt
    mask = np.zeros(sess.Tp, bool)
    mask[sess.Tp - len(prompt):] = True
    assert sess.admit(r, toks, mask, index, budget=budget, greedy=True,
                      denoising_steps=steps, remasking=strategy) is None


def test_a_blocks_kv_is_provisional_until_its_commit(params):
    """Forward by forward: while a block has a masked position none of its
    slots is valid (the next forward writes them again and no other forward
    can see them); the forward after its last unmask is the commit, which
    makes exactly its B slots valid; rows stand at different steps."""
    sess = session_of(params)
    admit(sess, 0, prompt_of(9, 13), 0, 8, 4, "low_confidence_static")
    admit(sess, 1, prompt_of(6, 14), 1, 8, 2, "low_confidence_static")
    seen, commits = {0: [], 1: []}, 0
    for _ in range(40):
        before = [np.asarray(a) for a in (sess.state[4], sess.state[11],
                                          sess.state[13], sess.state[5])]
        sess.dispatch()
        report = sess.read()
        key_mask, masked = np.asarray(sess.state[4]), np.asarray(sess.state[11])
        for r in range(2):
            if before[3][r]:
                continue
            fill = sess.Tp - int(np.asarray(sess.state[8])[r]) + int(before[2][r])
            slots = slice(fill, fill + B)
            assert not before[0][r, slots].any()     # provisional so far
            if before[1][r].any():                   # a denoise forward
                assert not key_mask[r, slots].any()
                assert masked[r].sum() < before[1][r].sum()
            else:                                    # its commit
                assert key_mask[r, slots].all()
                assert key_mask[r].sum() == before[0][r].sum() + B
                assert masked[r].all()
                commits += 1
            seen[r] += report.new_tokens(r, len(seen[r])).tolist()
        if report.done.all():
            break
    assert report.done.all() and commits == int(sess.block_counts[1]) == 6
    assert len(seen[0]) == 11 and len(seen[1]) == 10    # whole blocks
    assert int(sess.block_counts[3]) == 6 and sess.prompt_tail_tokens == 3


@pytest.mark.parametrize("ahead", [False, True])
def test_the_session_by_hand_is_the_engines_stream(params, served, ahead):
    """`tests/test_session._drive`'s loop over a block session, serial and
    with one chunk in flight: the same tokens the engine streamed."""
    plen, steps, strategy, budget = CASES["tail1_static2"]
    prompt, resp = served["tail1_static2"]
    sess = DecodeSession(
        params, CFG, rows=ENGINE["rows"], prompt_len=ENGINE["prompt_len"],
        max_tokens=ENGINE["max_new_tokens"], page_size=ENGINE["page_size"],
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, prefix_cache=RadixCache(headroom=0.0),
        prefill_chunk=ENGINE["prefill_chunk"], sync_every=2)
    assert sess.looks_ahead
    admit(sess, 1, prompt, 5, budget, steps, strategy)
    got, steps_got, done = [], [], False
    for _ in range(30):
        sess.dispatch()
        while sess.unread() > int(ahead) or (done and sess.unread()):
            report = sess.read()
            if report.current[1]:
                got += report.new_tokens(1, len(got)).tolist()
                steps_got += report.new_steps(1, len(steps_got)).tolist()
                done = done or bool(report.done[1])
        if done:
            break
    assert got == resp["tokens"] + resp["cut_tokens"]
    assert steps_got == resp["unmask_steps"] + resp["cut_unmask_steps"]


# ------------------------------------------------------------- the gateway

def test_the_gateway_carries_the_requests_parameters(params):
    from nanorlhf_tpu.serving.gateway import ServingGateway

    def post(port, spec):
        return json.loads(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=json.dumps(spec).encode(),
            headers={"Content-Type": "application/json"}), timeout=60).read())

    prompt = prompt_of(10, 15)
    with ServingEngine(params, CFG, **ENGINE) as engine, \
            ServingGateway(engine, port=-1) as gw:
        two = post(gw.port, {"tokens": prompt, "greedy": True, "max_tokens": 5,
                             "denoising_steps": 2,
                             "remasking": "low_confidence_dynamic"})
        default = post(gw.port, {"tokens": prompt, "greedy": True,
                                 "max_tokens": 6})
        with pytest.raises(urllib.error.HTTPError) as e:
            post(gw.port, {"tokens": prompt, "denoising_steps": 5})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            post(gw.port, {"tokens": prompt, "remasking": "random"})
        assert e.value.code == 400
    assert len(two["tokens"]) == 5 == len(two["unmask_steps"])
    assert max(two["unmask_steps"] + two["cut_unmask_steps"]) <= 1
    assert len(two["cut_tokens"]) == 1      # 2 + 5 + 1: two whole blocks
    assert max(default["unmask_steps"] + default["cut_unmask_steps"]) == 3
    gap, wrong, _ = follows_the_reference(
        params, prompt, two, 2, "low_confidence_dynamic")
    assert gap < TOL * 20 and wrong == 0


def test_an_autoregressive_engine_refuses_the_parameters():
    cfg = ModelConfig.qwen2_tiny(vocab_size=V)
    weights = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with ServingEngine(weights, cfg, **dict(ENGINE, prefill_chunk=0)) as engine:
        with pytest.raises(ValueError, match="does not generate by blocks"):
            engine.submit([5, 6, 7], denoising_steps=2)


# --------------------------------------------------------------- refusals

def _rollout(params):
    from nanorlhf_tpu.sampler import SamplingParams, generate

    prompts = jnp.asarray([[0, 5, 6, 7]])
    generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
             SamplingParams(n=1, max_tokens=4, greedy=True),
             eos_token_id=EOS, pad_token_id=PAD)


def _one_jit_rollout(params):
    from nanorlhf_tpu.sampler.sampler import generate_tokens

    prompts = jnp.asarray([[0, 5, 6, 7]])
    generate_tokens(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                    max_tokens=4, eos_token_id=EOS, pad_token_id=PAD)


def _speculation(params):
    from nanorlhf_tpu.sampler.speculative import generate_tokens_spec

    prompts = jnp.asarray([[0, 5, 6, 7]])
    generate_tokens_spec(params, CFG, prompts, prompts != 0,
                         jax.random.PRNGKey(0), max_tokens=4, eos_token_id=EOS,
                         pad_token_id=PAD, spec_k=2)


def _scheduler(params):
    from nanorlhf_tpu.sampler.paged.scheduler import generate_tokens_queued

    prompts = jnp.asarray([[0, 5, 6, 7]] * 2)
    generate_tokens_queued(params, CFG, prompts, prompts != 0,
                           jax.random.PRNGKey(0), max_tokens=4,
                           eos_token_id=EOS, pad_token_id=PAD, page_size=8,
                           decode_rows=1)


def _decode_step(params):
    caches = M.init_kv_cache(CFG, 1, 8, jnp.float32)
    M.decode_step(params, CFG, jnp.asarray([5]), jnp.asarray([0]), 0,
                  jnp.ones((1, 8), bool), caches)


def _trainer(params):
    from nanorlhf_tpu.trainer.trainer import RLTrainer

    RLTrainer(None, CFG, None, params, None, None)


def _sparse_trainer(params):
    from nanorlhf_tpu.trainer.sparse_grpo import SparseGRPOTrainer

    SparseGRPOTrainer(None, CFG, None, params, None, None)


def _adapter(params):
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

    init_lora_params(CFG, LoraConfig(r=4), jax.random.PRNGKey(0))


def _rollout_session(params):
    DecodeSession(
        params, CFG, rows=2, prompt_len=16, max_tokens=8, page_size=8,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=False, prefix_cache=RadixCache(headroom=0.0))


def _spec_session(params):
    DecodeSession(
        params, CFG, rows=2, prompt_len=16, max_tokens=8, page_size=8,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        per_row=True, greedy=True, spec_k=2,
        prefix_cache=RadixCache(headroom=0.0))


REFUSALS = {"rollout": _rollout, "one_jit_rollout": _one_jit_rollout,
            "speculation": _speculation, "scheduler": _scheduler,
            "decode_step": _decode_step, "trainer": _trainer,
            "sparse_trainer": _sparse_trainer, "adapter": _adapter,
            "rollout_session": _rollout_session, "spec_session": _spec_session}


@pytest.mark.parametrize("who", sorted(REFUSALS))
def test_what_takes_one_token_a_step_refuses_the_family_by_name(params, who):
    with pytest.raises(NotImplementedError, match="diffusion over blocks") as e:
        REFUSALS[who](params)
    assert "sdar_moe" in str(e.value)


@pytest.mark.parametrize("kw,match", [
    ({"prefix_cache": None}, "RadixCache"),
    ({"prefill_chunk": 6}, "multiple of the block"),
])
def test_a_session_refuses_what_it_is_not_built_for(params, kw, match):
    args = dict(rows=2, prompt_len=16, max_tokens=8, page_size=8,
                eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
                per_row=True, prefix_cache=RadixCache(headroom=0.0))
    with pytest.raises((NotImplementedError, ValueError), match=match):
        DecodeSession(params, CFG, **{**args, **kw})


def test_int8_kv_is_refused_by_name(params):
    cfg = dataclasses.replace(CFG, kv_cache_quant="int8")
    with pytest.raises(NotImplementedError, match="int8") as e:
        DecodeSession(params, cfg, rows=2, prompt_len=16, max_tokens=8,
                      page_size=8, eos_token_id=EOS, pad_token_id=PAD,
                      key=jax.random.PRNGKey(0), per_row=True,
                      prefix_cache=RadixCache(headroom=0.0))
    assert "sdar_moe" in str(e.value)


def test_block_forward_is_the_familys_alone():
    cfg = ModelConfig.qwen2_tiny(vocab_size=V)
    with pytest.raises(ValueError, match="generates by blocks"):
        M.block_forward({}, cfg, jnp.zeros((1, 4), jnp.int32),
                        jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,)),
                        jnp.zeros((1, 8), bool), None)


# ------------------------------------------------------------ device scopes

def test_the_block_chunks_scopes(params):
    """`attn.block` around the block read and `sample.unmask` around the
    choice, in the compiled chunk's text, under `decode`."""
    import re

    from nanorlhf_tpu.sampler.paged import session as S
    from nanorlhf_tpu.utils.profiling import DEVICE_SCOPES

    assert {"attn.block", "sample.unmask"} <= set(DEVICE_SCOPES)
    sess = session_of(params, sync_every=2)
    z = lambda dt: jnp.zeros((2,), dt)        # noqa: E731
    text = S._block_chunk.lower(
        params, CFG, sess.state, jnp.array(sess.table_np), z(jnp.float32),
        z(jnp.float32), z(bool), z(jnp.int32), z(jnp.int32) + 4, z(jnp.int32),
        **sess._block_statics).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attn/attn.read/attn.block/", "sample/sample.unmask/",
                  "sample/", "mlp/moe.experts/", "head/"):
        assert any(re.search("/decode/.*" + re.escape(scope), n)
                   for n in names), scope
