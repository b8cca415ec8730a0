"""Trinity (`model_type: afmoe`, docs/AFMOE.md): gated attention over window
layers with rotary beside global layers without, four norms a layer, a
leading dense layer INSIDE the window pattern, and a chip's share of
bias-selected sigmoid experts beside a shared one, against the plain float32
reference of benchmark/harness/reference_trinity.py on seeded weights. Tiny
widths; logits, not tokens."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from harness import reference_trinity as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from nanorlhf_tpu.sampler.paged.pages import RingPages, ring_blocks  # noqa: E402

with open(os.path.join(BENCH, "configs", "trinity-large-ep8-l5.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-trinity.json")) as f:
    # every expert held: the share has tests of its own below
    HF = {**json.load(f), "vocab_size": 128, "num_experts_held": 0}
V, W = HF["vocab_size"], HF["sliding_window"]
CFG = ModelConfig.from_hf_config(HF)
# float32 on both sides under "highest": what is left is the order of sums
# (the grouped matmul against one expert at a time, the online softmax)
TOL = 1e-4
EOS, PAD = 1, 0
CONTROLS = {"no_gate": {"gate": False}, "no_attn_norm": {"attn_norm": False},
            "no_mlp_norm": {"mlp_norm": False},
            "no_embed_scale": {"embed_scale": False}, "no_bias": {"bias": False},
            "no_window": {"window": False}, "rope_everywhere": {"nope": False}}


def lay_weights(cfg, key=0):
    """Seeded weights with everything the controls need to matter: norm
    weights that are not ones, a gate that spreads, a bias that changes the
    choice, an embedding of RMS 1 after its scale."""
    p = init_params(cfg, jax.random.PRNGKey(key), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 5), 32))
    for stack in ("dense_layers", "layers"):
        tree = p[stack]
        for name in ("input_layernorm", "post_attention_layernorm",
                     "attn_branch_norm", "mlp_branch_norm", "q_norm", "k_norm"):
            tree[name] = jnp.exp(0.3 * jax.random.normal(next(keys),
                                                         tree[name].shape))
        tree["g_proj"]["kernel"] = 2.0 * tree["g_proj"]["kernel"]
    bias = p["layers"]["router"]["bias"]
    p["layers"]["router"]["bias"] = 0.05 * jax.random.normal(next(keys),
                                                             bias.shape)
    p["embed_tokens"] = 6.25 * p["embed_tokens"]
    p["lm_head"] = p["lm_head"].at[:, jnp.asarray([EOS, PAD])].set(0)
    return p


@pytest.fixture(scope="module")
def params():
    return lay_weights(CFG)


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(0)
    x = rng.integers(3, V, (3, 40)).astype(np.int32)
    x[0, :8] = PAD      # left-padded rows of unequal length beside a full one
    x[1, :3] = PAD
    return jnp.asarray(x)


@pytest.fixture(scope="module")
def reference_logits(params, ids):
    with jax.default_matmul_precision("highest"):
        return {name: np.asarray(ref.logits(params, HF, ids, PAD, **flags))
                for name, flags in (("sound", {}), *CONTROLS.items())}


def far(a, b, real):
    return float(np.abs(np.asarray(a) - np.asarray(b))[real].max())


# ------------------------------------------------------------ configuration

def test_from_hf_config_on_the_published_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    c = ModelConfig.from_hf_config(row["config"])
    assert c == ModelConfig.trinity_large()
    assert (c.hidden_size, c.intermediate_size, c.moe_intermediate_size,
            c.actual_head_dim, c.vocab_size) == (3072, 12288, 3072, 128, 200192)
    assert (c.num_experts, c.num_experts_per_tok, c.num_dense_layers,
            c.n_shared_experts, c.experts_held) == (256, 4, 6, 1, 0)
    assert (c.window_layers, c.conv_layers, c.sliding_window) == (45, 0, 4096)
    assert c.layer_kinds[:4] == ((True, True),) * 3 + ((False, False),)
    assert c.embed_scale == pytest.approx(3072 ** 0.5)
    assert (c.attention_gate, c.branch_norms, c.use_expert_bias,
            c.qk_norm_per_head, c.route_norm_eps) == (True,) * 4 + (1e-20,)
    cut = ModelConfig.from_hf_config(FILE)
    assert (cut.num_hidden_layers, cut.num_dense_layers, cut.window_layers,
            cut.experts_held, cut.experts_offset, cut.vocab_size) == (
        5, 1, 4, 32, 0, 25024)
    # the dense stack is a stack of the pattern: one window layer with rotary
    assert cut.stack_pattern(0, 1) == ((True, True),)
    assert cut.attention_pattern == ((True, True),) * 3 + ((False, False),)
    assert FILE["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():    # no width differs
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    assert FILE["num_experts"] == 256       # the router's width stays
    assert {k: FILE["published"][k] for k in (
        "num_hidden_layers", "num_dense_layers", "vocab_size")} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "vocab_size": 200192}
    assert CFG == ModelConfig.trinity_tiny(vocab_size=V)
    assert ModelConfig.qwen2_tiny().embed_scale == 1.0


@pytest.mark.parametrize("change, what", [
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"num_expert_groups": 4}, "num_expert_groups"),
    ({"num_limited_groups": 2}, "num_limited_groups"),
    ({"score_func": "softmax"}, "score_func"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["sliding_attention"] * 4}, "4 entries for 5"),
    ({"layer_types": ["sliding_attention"] * 4 + ["conv"]}, "conv"),
    ({"layer_types": ["sliding_attention"] * 5}, "window layers only"),
    ({"mup_enabled": False}, "mup_enabled"),
    ({"attention_bias": True}, "attention_bias"),
    ({"sliding_window": None}, "without a sliding_window"),
    ({"num_experts_held": 4, "num_experts_offset": 14}, "not among"),
])
def test_from_hf_config_raises_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config({**HF, **change})


def test_hf_names_round_trip():
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    cfg = dataclasses.replace(CFG, experts_held=4, experts_offset=8)
    params = lay_weights(cfg)
    sd = hf_state_dict_from_params(cfg, params)
    assert sd["model.layers.0.self_attn.gate_proj.weight"].shape == (64, 64)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (96, 64)
    assert sd["model.layers.1.mlp.router.gate.weight"].shape == (16, 64)
    assert sd["model.layers.1.mlp.expert_bias"].shape == (16,)
    assert sd["model.layers.4.self_attn.q_norm.weight"].shape == (16,)
    assert "model.layers.2.mlp.shared_experts.up_proj.weight" in sd
    # the held experts under their own numbers in the router's order
    assert "model.layers.3.mlp.experts.8.down_proj.weight" in sd
    assert "model.layers.3.mlp.experts.7.down_proj.weight" not in sd
    for name in ("input_layernorm", "post_attention_layernorm",
                 "pre_mlp_layernorm", "post_mlp_layernorm"):
        assert sd[f"model.layers.2.{name}.weight"].shape == (64,)
    # this tree's `post_attention_layernorm` is the MLP's input norm
    np.testing.assert_array_equal(
        np.asarray(sd["model.layers.2.pre_mlp_layernorm.weight"]),
        np.asarray(params["layers"]["post_attention_layernorm"][1]))
    back = params_from_hf_state_dict(cfg, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_adapter_sits_on_the_attention_and_its_gate():
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params, lora_targets

    lora = LoraConfig(r=4)
    assert lora_targets(CFG, lora) == ("q_proj", "k_proj", "v_proj", "o_proj",
                                       "g_proj")
    tree = init_lora_params(CFG, lora, jax.random.PRNGKey(0), jnp.float32)
    assert set(tree) == {"dense_layers", "layers"}
    assert tree["layers"]["g_proj"]["a"].shape == (4, 64, 4)
    assert tree["dense_layers"]["o_proj"]["b"].shape == (1, 4, 64)
    p = {**lay_weights(CFG), "lora": tree}
    x = jnp.asarray(np.random.default_rng(1).integers(3, V, (2, 12)), jnp.int32)
    base = padded_forward_logits(lay_weights(CFG), CFG, x, PAD)
    np.testing.assert_allclose(                 # B = 0: the base model
        np.asarray(padded_forward_logits(p, CFG, x, PAD)), np.asarray(base),
        atol=1e-6)


# ------------------------------------------------------ forwards and caches

def test_uncached_forward_is_the_reference(params, ids, reference_logits):
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, reference_logits["sound"], real) < TOL


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_model_without_one_mechanism_fails_the_comparison(
        params, ids, reference_logits, control):
    """The comparison can fail: against the reference without the gate,
    either branch norm, the embedding's scale, the bias or the window, or
    with rotary on the global layer, the sound forward is far off."""
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, reference_logits[control], real) > 0.05


def test_contiguous_prefill_and_decode_are_the_reference(params, ids,
                                                         reference_logits):
    want = reference_logits["sound"]
    B, T_max, Tp = ids.shape[0], ids.shape[1], 24
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        caches = init_kv_cache(CFG, B, T_max, jnp.float32)
        # one global layer; the dense layer's K and V lie with the window's
        assert caches[0][0].shape == (1, B, 2, T_max, 16)
        assert caches[1][0].shape == (4, B, 2, T_max, 16)
        lg, caches = prefill(params, CFG, ids[:, :Tp], mask[:, :Tp], caches)
        worst = far(lg, want[:, Tp - 1], np.ones(B, bool))
        km = jnp.zeros((B, T_max), bool).at[:, :Tp].set(mask[:, :Tp])
        plen = mask[:, :Tp].sum(1)
        step = jax.jit(lambda t, pos, slot, km, c: decode_step(
            params, CFG, t, pos, slot, km, c))
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - Tp), t, km, caches)
            worst = max(worst, far(lg, want[:, t], np.ones(B, bool)))
    assert worst < TOL


# ----------------------------------------------------------- the paged path

def paged_logits(params, ids, P, chunk, impl="auto"):
    """Chunked prefill (`decode_verify` over `chunk` tokens at a time) of the
    first 16 slots, then single-token steps, through a page pool of two kinds
    whose window ring is shorter than the row: the rows pass the window of 8
    and their rings wrap WHILE THEY DECODE. [B, 40, V] logits."""
    cfg = dataclasses.replace(CFG, attention_impl=impl)
    B, T_max, Tp = ids.shape[0], 40, 16
    nb, ring = T_max // P, ring_blocks(W, P, chunk)
    assert ring < nb
    pad = np.asarray((ids == PAD).sum(1))
    pages = RingPages(B * ring, B, nb, ring)
    for r, first in enumerate(pad // P):
        pages.claim(r, first, nb - 1)
    tabs = (jnp.asarray(np.arange(B * nb, dtype=np.int32).reshape(B, nb)),
            jnp.asarray(pages.table))
    caches = init_paged_kv_cache(cfg, (B * nb, B * ring), P, jnp.float32)
    mask = np.asarray(ids != PAD)
    pos = np.cumsum(mask, 1) - 1
    out = {}
    # (jitted: an eager call lowers its layer scans anew each time and every
    # such executable stays mapped; tests/test_smallthinker.py has the story)
    verify, step = (jax.jit(
        functools.partial(f, page_table=tabs, page_size=P),
        static_argnums=1) for f in (decode_verify, decode_step))
    with jax.default_matmul_precision("highest"):
        for f in range(0, Tp, chunk):
            km = np.zeros((B, T_max), bool)
            km[:, :f] = mask[:, :f]
            lg, caches = verify(
                params, cfg, ids[:, f:f + chunk], jnp.asarray(pos[:, f:f + chunk]),
                jnp.full((B,), f, jnp.int32), jnp.asarray(km), caches)
            for i in range(chunk):
                out[f + i] = np.asarray(lg[:, i])
        km = jnp.zeros((B, T_max), bool).at[:, :Tp].set(ids[:, :Tp] != PAD)
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(
                params, cfg, ids[:, t], jnp.asarray(pos[:, t]),
                jnp.full((B,), t, jnp.int32), km, caches,
                live=jnp.ones((B,), bool))
            out[t] = np.asarray(lg)
    return np.stack([out[t] for t in range(T_max)], axis=1), pages


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_paged_pieces_and_steps_are_the_reference(params, ids, impl):
    # (this walk feeds every row the same slots: a row's pads are one whole
    # piece, as the session starts a row's pieces at its first real token)
    ids = ids.at[1, :3].set(ids[2, :3])
    with jax.default_matmul_precision("highest"):
        want, no_window = (np.asarray(ref.logits(params, HF, ids, PAD, **flags))
                           for flags in ({}, {"window": False}))
    got, pages = paged_logits(params, ids, P=4, chunk=8, impl=impl)
    real = np.asarray(ids != PAD)
    assert far(got, want, real) < TOL
    assert far(got, no_window, real) > 0.05
    # 16 prompt slots are four blocks of a ring of 6; the full row's 24
    # decode steps write six more: the ring wrapped under decode
    assert pages.ring == 6 and pages.reused(2, 3) == 0 and pages.reused(2, 9) == 4


# ------------------------------------------------------------- the session

def session(params, cfg=CFG, **kw):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    return DecodeSession(
        params, cfg, **{**dict(
            rows=4, prompt_len=24, max_tokens=32, page_size=4, eos_token_id=EOS,
            pad_token_id=PAD, key=jax.random.PRNGKey(1), per_row=True,
            prefix_cache=RadixCache(headroom=0.0), sync_every=4,
            prefill_chunk=8), **kw})


def serve(sess, lengths, budgets, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, V, n) for n in lengths]
    Tp = sess.Tp
    for r, p in enumerate(prompts):
        toks, mask = np.zeros(Tp, np.int32), np.zeros(Tp, bool)
        toks[Tp - len(p):], mask[Tp - len(p):] = p, True
        sess.admit(r, toks, mask, r, budget=budgets[r], temperature=1.0,
                   top_p=1.0, greedy=True)
    for _ in range(80):
        done, _ = sess.step()
        if done.all() and not sess.has_pending():
            break
    out = np.asarray(sess.state[1])
    return prompts, [out[r, :n] for r, n in enumerate(budgets)]


def gaps(params, hf, prompts, answers, **flags):
    """How far under the reference's top each served token lies, a row."""
    out = []
    for p, a in zip(prompts, answers):
        seq = jnp.asarray(np.concatenate([p, a])[None])
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(params, hf, seq, PAD, last=len(a) + 1,
                                       mask=jnp.ones(seq.shape, bool),
                                       **flags))[0, :-1]
        out.append(lg.max(-1) - lg[np.arange(len(a)), a])
    return out


def test_session_rows_pass_the_window_while_they_decode(params):
    """Four rows at once, two pools. Row 0: 29 prompt tokens in four pieces
    (eight blocks into a ring of six: the prompt itself wraps it twice), then
    32 decode steps; row 1: 5 tokens, inside the window of 8 when its
    decode starts, past it four steps later, its ring of 6 pages wrapping
    while decode chunks run; rows 2 and 3 short."""
    sess = session(params, prompt_len=32)
    prompts, answers = serve(sess, (29, 5, 3, 7), (32, 30, 6, 11))
    assert sess.chunked_admissions == 1 and sess.nbw == 6
    assert sess.window_layers == 4
    assert sess.window_pages_reused_in_decode > 0
    assert sess.window_pages_reused > sess.window_pages_reused_in_decode
    assert 0 < sess.rows_past_window < 4 * sess.iterations()
    assert 0 < sess.window_slots_read < sess.global_slots_read
    assert sess.held_experts_hit > 0 and sess.hit_tokens == 0
    for g in gaps(params, HF, prompts, answers):
        assert g.max() < TOL
    # the rows that passed the window tell the model from one without it
    off = gaps(params, HF, prompts[:2], answers[:2], window=False)
    assert max(g.max() for g in off) > 0.05
    for r in range(4):
        sess.release(r)
    assert sess._ring.free_count == sess.num_pages_window


def test_a_row_that_only_decodes_past_the_window_counts_as_decode_reuse(params):
    """A 3-token prompt and 30 new tokens: every wrap of its ring is decode's
    (`serving/window_pages_reused_in_decode` = all of `window_pages_reused`)."""
    sess = session(params, rows=1)
    prompts, answers = serve(sess, (3,), (30,))
    assert sess.window_pages_reused_in_decode == sess.window_pages_reused > 0
    assert gaps(params, HF, prompts, answers)[0].max() < TOL


def test_engine_serves_and_counts(params):
    from nanorlhf_tpu.serving.engine import ServingEngine

    with ServingEngine(params, CFG, eos_token_id=EOS, pad_token_id=PAD,
                       page_size=4, prompt_len=24, max_new_tokens=24, rows=2,
                       headroom=0.0, sync_every=4, prefill_chunk=8) as engine:
        rng = np.random.default_rng(3)
        reqs = [engine.submit(rng.integers(3, V, n), greedy=True,
                              max_tokens=20)[0] for n in (18, 5, 18)]
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    assert [len(s) for s in streams] == [20, 20, 20]
    assert m["serving/window_layers"] == 4
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/kv_bytes_per_token_global"] == 1 * 2 * 2 * 16 * 4
    assert m["serving/kv_bytes_per_token_window"] == 4 * 2 * 2 * 16 * 4
    assert m["serving/window_pages_reused_in_decode"] > 0
    assert 0 < m["serving/rows_past_window"] <= 2 * m["serving/decode_steps"]
    assert m["serving/held_experts_hit"] > 0


@pytest.mark.parametrize("kw, what", [
    ({"per_row": False}, "serving session only"),
    ({"spec_k": 2, "greedy": True}, "serving session only"),
    ({"prefix_cache": None}, "serving session only"),
])
def test_session_raises_on_what_is_not_built(params, kw, what):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    base = dict(rows=2, prompt_len=16, max_tokens=8, page_size=4,
                eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
                per_row=True, prefix_cache=RadixCache(headroom=0.0))
    with pytest.raises(NotImplementedError, match=what + ".|afmoe"):
        DecodeSession(params, CFG, **{**base, **kw})


def test_generate_refuses_the_paths_a_window_model_has_not(params):
    from nanorlhf_tpu.sampler import SamplingParams, generate

    x = jnp.asarray(np.random.default_rng(2).integers(3, V, (2, 12)), jnp.int32)
    out = generate(params, CFG, x, x != PAD, jax.random.PRNGKey(0),
                   SamplingParams(n=1, max_tokens=6, greedy=True),
                   eos_token_id=EOS, pad_token_id=PAD)
    assert out.shape == (2, 6)
    for bad in ({"spec_k": 2}, {"page_size": 4}):
        with pytest.raises(NotImplementedError, match="afmoe"):
            generate(params, CFG, x, x != PAD, jax.random.PRNGKey(0),
                     SamplingParams(n=1, max_tokens=6, greedy=True, **bad),
                     eos_token_id=EOS, pad_token_id=PAD)


# ----------------------------------------------------------------- the share

def test_eight_shares_add_up_to_the_whole_layer():
    """The routed parts that all 8 shares give (2 of 16 experts each), plus
    the shared expert and the replicated parts counted once, add up to the
    uncut model's expert layer: one layer of experts, every share's forward
    against the whole model's and against the reference's."""
    from nanorlhf_tpu.core.model import _mlp

    layer = jax.tree.map(lambda a: a[1], lay_weights(CFG)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        full, aux = _mlp(CFG, h, layer, None, 1.0)
        want = ref._expert_mlp(h, layer, 2, True, 2.448, True, 0)
        shared = ref._swiglu(h, layer["shared_expert"])
        routed = jnp.zeros_like(full)
        absent = 0
        for share in range(8):
            cfg = dataclasses.replace(CFG, experts_held=2,
                                      experts_offset=2 * share)
            mine = {**layer, "experts": jax.tree.map(
                lambda a: a[2 * share:2 * share + 2], layer["experts"])}
            out, share_aux = _mlp(cfg, h, mine, None, 1.0)
            ref_share = ref._expert_mlp(h, mine, 2, True, 2.448, True, 2 * share)
            assert far(out, ref_share, np.ones(h.shape[:2], bool)) < TOL
            routed = routed + (out - shared)
            absent += int(share_aux["absent"])
    real = np.ones(h.shape[:2], bool)
    assert far(full, want, real) < TOL
    assert far(routed + shared, full, real) < TOL
    # every assignment is held by exactly one share: absent on the other 7
    assert absent == 7 * h.shape[0] * h.shape[1] * 2
    assert float(np.mean(np.asarray(aux["bias_changed"]))) > 0.02


def test_a_share_of_the_model_is_the_references_share(ids):
    hf = {**HF, "num_experts_held": 4, "num_experts_offset": 8}
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.experts_held, cfg.experts_offset) == (4, 8)
    p = lay_weights(cfg)
    assert p["layers"]["experts"]["gate_proj"]["kernel"].shape == (4, 4, 64, 32)
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got, stats = padded_forward_logits(p, cfg, ids, PAD, router_stats=True)
        want = ref.logits(p, hf, ids, PAD, held=4)
        elsewhere = ref.logits(p, hf, ids, PAD, offset=4)
    assert far(got, want, real) < TOL
    assert far(got, elsewhere, real) > 0.01
    from nanorlhf_tpu.ops.moe import moe_counters

    row = moe_counters([jax.tree.map(np.asarray, stats)], held=(4, 8))
    assert row["moe/dropped_tokens"] == 0 and row["moe/absent_assignments"] > 0
    assert 0.05 < row["moe/routed_here_frac"] < 0.6
    assert row["moe/bias_changed_frac"] > 0.02
