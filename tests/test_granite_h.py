"""Granite 4.0-H (docs/GRANITE_H.md): Mamba-2 mixers ALONE in their layers (a
state and no pages) beside global attention layers without rotary, a mixture
of experts with a shared expert after every one, under the published
multipliers, against the plain float32 reference of
benchmark/harness/reference_granite_h.py on seeded weights. Tiny widths;
logits, not tokens."""

import dataclasses
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

from harness import reference_granite_h as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from nanorlhf_tpu.ops import ssm as ops  # noqa: E402

with open(os.path.join(BENCH, "configs", "granite-4.0-h-small-ep2-l10.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-granite-h.json")) as f:
    TINY = json.load(f)
# the whole tiny model (every expert here) and a chip's share of it
HF = {**TINY, "vocab_size": 128, "num_experts_held": 0,
      "num_experts_offset": 0}
V = HF["vocab_size"]
CFG = ModelConfig.from_hf_config(HF)
TOL = 1e-4
EOS, PAD = 1, 0
CONTROLS = ("rope", "attention_multiplier", "residual_multiplier_moe",
            "residual_multiplier_mixer", "shared_expert", "renormalise",
            "gate_before_norm", "attention", "embedding_multiplier",
            "logits_scaling", "conv_bias", "D", "dt_bias", "norm")


def spread(p):
    """Everything the controls need to matter: `D`, the conv bias and the
    mixer's norm away from ones and zeros, attention scores that pick keys,
    logits of a size that shows, and an EOS and a pad no row can emit."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    s = p["layers"]["ssm"]
    s["D"] = 1 + 0.5 * jax.random.normal(keys[0], s["D"].shape)
    s["conv"]["bias"] = 0.5 * jax.random.normal(keys[1], s["conv"]["bias"].shape)
    s["norm"] = jnp.exp(0.5 * jax.random.normal(keys[2], s["norm"].shape))
    p["layers"]["q_proj"]["kernel"] = p["layers"]["q_proj"]["kernel"] * 3
    # (the head is the tied embedding: scaled up, a token's own row would
    # lead its logits whatever the layers add, and every variant would
    # serve the same tokens; the final norm's weight gives the logits size)
    p["norm"] = p["norm"] * 25
    p["embed_tokens"] = p["embed_tokens"].at[jnp.asarray([EOS, PAD])].set(0)
    return p


@pytest.fixture(scope="module")
def params():
    return spread(init_params(CFG, jax.random.PRNGKey(0), jnp.float32))


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(0)
    x = rng.integers(3, V, (3, 40)).astype(np.int32)
    x[0, :8] = PAD      # left-padded rows of unequal length beside a full one
    x[1, :3] = PAD
    return jnp.asarray(x)


@pytest.fixture(scope="module")
def sound(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, HF, ids, PAD))


def far(a, b, real=None):
    d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    return float((d if real is None else d[real]).max())


# ------------------------------------------------------------ configuration

def test_from_hf_config_on_the_catalog_rows_keys():
    c = ModelConfig.from_hf_config({**FILE, **FILE["published"],
                                    "num_experts_held": 0})
    assert c == ModelConfig.granite_h_small()
    assert (c.num_hidden_layers, c.ssm_layers, c.mamba_layers) == (40, 36, 36)
    assert (c.page_layers, c.state_layers, c.window_layers) == (4, 36, 0)
    period = ("mamba",) * 5 + ((False, False),) + ("mamba",) * 4
    assert c.attention_pattern == period and c.traits == {"ssm"}
    assert (c.ssm_inner, c.ssm_conv_width) == (8192, 8448)
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state) \
        == (128, 64, 1, 128)
    assert (c.embed_scale, c.residual_scale, c.lm_head_multiplier) \
        == (12.0, 0.22, 1 / 16)
    # the softmax's scale is the key's, 1 / 128, not 1 / sqrt(128)
    assert c.attention_multiplier == 0.0078125
    assert c.query_multiplier * 128 ** -0.5 == pytest.approx(0.0078125)
    assert (c.num_experts, c.num_experts_per_tok, c.shared_expert_width) \
        == (72, 10, 1536)
    cut = ModelConfig.from_hf_config(FILE)
    assert cut == dataclasses.replace(
        c, num_hidden_layers=10, layer_types=period[:5] + ("attention",)
        + period[6:], rope_layout=(0,) * 10, vocab_size=50176,
        experts_held=36, experts_offset=0)
    assert (cut.ssm_layers, cut.page_layers) == (9, 1)
    assert CFG == ModelConfig.granite_h_tiny(vocab_size=V)
    assert ModelConfig.falcon_h1_tiny().mamba_layers == 0


def test_the_layers_parameters_are_the_issues():
    """A mixer 102.3 M, an attention 41.9 M, an expert 9.44 M, the shared
    expert 18.9 M, the router 0.29 M: a mamba layer 801 M, an attention
    layer 741 M, the embedding 411 M; this chip's ten layers and half of the
    vocabulary 4.76 G."""
    count = lambda tree: sum(int(np.prod(a.shape))                 # noqa: E731
                             for a in jax.tree.leaves(tree))
    whole = jax.eval_shape(lambda: init_params(
        ModelConfig.from_hf_config({**FILE, "num_experts_held": 0,
                                    "vocab_size": 100352}),
        jax.random.PRNGKey(0)))
    layers = whole["layers"]
    mixer = count(layers["ssm"]) // 9
    attention = sum(count(layers[k]) for k in ("q_proj", "k_proj", "v_proj",
                                               "o_proj"))
    experts, shared, router = (count(layers[k]) // 10 for k in (
        "experts", "shared_expert", "router"))
    assert (mixer, attention) == (102_286_976, 41_943_040)
    assert (experts, shared, router) == (72 * 9_437_184, 18_874_368, 294_912)
    norms = 2 * 4096
    assert mixer + experts + shared + router + norms == 800_941_696
    assert attention + experts + shared + router + norms == 740_597_760
    assert count(whole["embed_tokens"]) == 411_041_792 and "lm_head" not in whole
    here = jax.eval_shape(lambda: init_params(
        ModelConfig.from_hf_config(FILE), jax.random.PRNGKey(0)))
    assert count(here["layers"]["experts"]) // 10 == 36 * 9_437_184
    assert 4.75e9 < count(here) < 4.77e9


@pytest.mark.parametrize("change, what", [
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"normalization_function": "layernorm"}, "normalization_function"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"mamba_expand": 2}, "mamba_expand"),
    ({"mamba_d_conv": 1}, "mamba_d_conv"),
    ({"layer_types": ["mamba"] * 7 + ["sliding_attention"]}, "layer_types"),
    ({"layer_types": ["mamba"] * 3}, "layer_types"),
])
def test_from_hf_config_raises_by_name_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=f"granitemoehybrid: {what}"):
        ModelConfig.from_hf_config({**HF, **change})


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="are not among the router's 8"):
        ModelConfig.from_hf_config({**HF, "num_experts_held": 4,
                                    "num_experts_offset": 6})


@pytest.mark.parametrize("held", [(0, 0), (4, 4)])
def test_hf_names_round_trip(held):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    cfg = dataclasses.replace(CFG, experts_held=held[0], experts_offset=held[1])
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    sd = hf_state_dict_from_params(cfg, params)
    E = held[0] or 8
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (80, 1, 4)
    assert sd["model.layers.1.mamba.in_proj.weight"].shape == (148, 64)
    assert "model.layers.2.mamba.in_proj.weight" not in sd
    assert sd["model.layers.2.self_attn.q_proj.weight"].shape == (64, 64)
    assert "model.layers.0.self_attn.q_proj.weight" not in sd
    assert sd["model.layers.3.mamba.A_log"].shape == (4,)
    assert sd["model.layers.0.mamba.norm.weight"].shape == (64,)
    assert sd["model.layers.5.block_sparse_moe.router.layer.weight"].shape \
        == (8, 64)
    assert sd["model.layers.5.block_sparse_moe.input_linear.weight"].shape \
        == (E, 64, 64)
    assert sd["model.layers.5.block_sparse_moe.output_linear.weight"].shape \
        == (E, 64, 32)
    assert sd["model.layers.6.shared_mlp.input_linear.weight"].shape == (96, 64)
    assert sd["model.layers.6.shared_mlp.output_linear.weight"].shape == (64, 48)
    assert "model.norm.weight" in sd and "lm_head.weight" not in sd
    back = params_from_hf_state_dict(cfg, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ the recurrence

def _operands(key, B, T, H, P, G, N):
    k = jax.random.split(key, 6)
    xs = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm, Cm = (jax.random.normal(k_, (B, T, G, N)) for k_ in k[3:5])
    return xs, dt, A, Bm, Cm, jax.random.normal(k[5], (B, H, P, N))


def test_the_chunked_scan_at_one_group_and_chunks_of_256_is_the_token_scan():
    """The published sizes of the scan's every axis but the heads (16 of the
    128): P 64, ONE group of N 128, a piece of 300 tokens in chunks of 256."""
    xs, dt, A, Bm, Cm, S = _operands(jax.random.PRNGKey(3), 1, 300, 16, 64,
                                     1, 128)
    dt = dt.at[0, :40].set(0)       # pads: neither decay nor feed
    y, out = jax.jit(lambda *a: ops.ssd_scan(*a, 256))(xs, dt, A, Bm, Cm, S)
    want_y, want = jax.jit(ops.ssm_token_scan)(xs, dt, A, Bm, Cm, S)
    assert far(y, want_y) < 2e-3 * float(jnp.abs(want_y).max())
    assert far(out, want) < 1e-4 * float(jnp.abs(want).max())


def test_the_in_place_update_at_the_published_sizes_takes_the_kernel():
    """(H, P, G, N) = (128, 64, 1, 128): a head's `[64, 128]` float32 state
    is whole tiles, so on a TPU the step is the Pallas call and not the XLA
    form (traced with `interpret=False`, as a TPU would), all 128 heads one
    block; interpreted here it is `ssm_update` on the live rows."""
    L, R, B, H, P, G, N = 2, 4, 3, 128, 64, 1, 128
    assert ops._heads_a_block(H, G, P, N) == 128
    xs, dt, A, Bm, Cm, _ = _operands(jax.random.PRNGKey(4), B, 1, H, P, G, N)
    step = (xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
    stack = jax.random.normal(jax.random.PRNGKey(5), (L, R, H, P, N))
    live = jnp.asarray([True, False, True])
    text = str(jax.make_jaxpr(lambda s: ops.ssm_update_in_place(
        s, 1, 1, live, None, *step, interpret=False))(stack))
    assert "pallas_call" in text
    # (a head of 60 is no whole tile: the XLA form, by shape)
    narrow = str(jax.make_jaxpr(lambda s: ops.ssm_update_in_place(
        s, 1, 1, live, None, xs[:, 0, :, :60], *step[1:],
        interpret=False))(stack[..., :60, :]))
    assert "pallas_call" not in narrow
    y, out = jax.jit(lambda s: ops.ssm_update_in_place(
        s, 1, 1, live, None, *step))(stack)
    want_y, want = jax.jit(ops.ssm_update)(*step, stack[1, 1:1 + B])
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(out[1, 1:1 + B])[on],
                               np.asarray(want)[on], atol=1e-6, rtol=3e-7)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(stack[0]))
    np.testing.assert_array_equal(np.asarray(out[1, 2]), np.asarray(stack[1, 2]))
    assert far(np.asarray(y)[on], np.asarray(want_y)[on]) < 1e-4
    assert not np.asarray(y)[~on].any()


# ------------------------------------------------------ forwards and caches

def test_uncached_forward_is_the_reference(params, ids, sound):
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, sound, real) < TOL
    assert np.asarray(sound)[real].std() > 0.3      # logits that show


@pytest.mark.parametrize("left_out", CONTROLS)
def test_every_variant_is_far_from_the_reference(params, ids, sound, left_out):
    """No multiplier of the tiny config is 1 or its published default, and
    the reference with rotary on the attention layers, the scale
    1 / sqrt(hd), a residual multiplier dropped, no shared expert, a softmax
    over all the experts, the norm before the gate (or a mixer's leaf
    dropped) is another model: the system follows the sound one."""
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        wrong = ref.logits(params, HF, ids, PAD, without=(left_out,))
    assert far(wrong, sound, real) > 100 * TOL, left_out


def test_a_multiplier_left_out_stages_nothing(params, ids):
    ones = dataclasses.replace(
        CFG, embed_scale=1.0, attention_multiplier=0.0,
        residual_multiplier=0.0, lm_head_multiplier=1.0)
    count = lambda cfg: str(jax.make_jaxpr(                        # noqa: E731
        lambda p: padded_forward_logits(p, cfg, ids, PAD))(params)).count(" mul ")
    # embedding, logits; a period of four layers: three mixers' residual,
    # the attention's q and residual, four mixtures' residual
    assert count(CFG) - count(ones) == 2 + 3 + 2 + 4


def test_the_two_shares_add_up_to_the_uncut_layer(params):
    """`held=(E/2, 0)` and `held=(E/2, E/2)` of one layer's mixture, the
    shared expert counted once, are the uncut reference's layer; and the
    program's `_mlp` with a share is the reference's with the same share."""
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 11, 64))
    layer = jax.tree.map(lambda a: a[5], {k: params["layers"][k] for k in (
        "router", "experts", "shared_expert")})
    half = lambda lo: {**layer, "experts": jax.tree.map(            # noqa: E731
        lambda a: a[lo:lo + 4], layer["experts"])}
    with jax.default_matmul_precision("highest"):
        whole, shared = ref.moe_layer(x, layer, HF)
        parts = [ref.moe_layer(x, half(lo), HF, held=4, offset=lo)
                 for lo in (0, 4)]
        assert far(parts[0][0] + parts[1][0], whole) < 1e-5
        for _, s in parts:      # what every chip computes alike
            assert far(s, shared) == 0.0
        assert float(jnp.abs(parts[0][0]).mean()) > 0.01    # neither is empty
        assert float(jnp.abs(parts[1][0]).mean()) > 0.01
        for lo, (routed, s) in zip((0, 4), parts):
            cfg = dataclasses.replace(CFG, experts_held=4, experts_offset=lo)
            got, _ = M._mlp(cfg, x, half(lo), None, 1.0)
            assert far(got, routed + s) < 1e-5


def test_a_chips_share_of_the_model_is_the_references_share(ids):
    cfg = ModelConfig.from_hf_config({**TINY, "vocab_size": V})
    assert (cfg.experts_held, cfg.experts_offset) == (4, 4)
    p = spread(init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    assert p["layers"]["experts"]["gate_proj"]["kernel"].shape == (8, 4, 64, 32)
    file = {**TINY, "vocab_size": V}
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(p, file, ids, PAD)
        got = padded_forward_logits(p, cfg, ids, PAD)
        other = ref.logits(p, file, ids, PAD, offset=0)
    assert far(got, want, real) < TOL
    assert far(other, want, real) > 100 * TOL       # which experts matters


def test_contiguous_prefill_in_two_pieces_and_decode_are_the_reference(
        params, ids, sound):
    B, T_max, Tp = ids.shape[0], ids.shape[1], 24
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        caches = init_kv_cache(CFG, B, T_max, jnp.float32)
        tail, S = caches[2]
        # the state's stacks are the SIX mixer layers', the pages' the TWO
        # attention layers'
        assert tail.shape == (6, 3, B, 80) and S.shape == (6, B, 4, 16, 8)
        assert caches[0][0].shape == (2, B, 2, T_max, 16)
        assert caches[1][0].shape[0] == 0           # no window layer
        first = 13      # no multiple of the scan's chunk of 4
        lg, caches = prefill(params, CFG, ids[:, :first], mask[:, :first],
                             caches)
        worst = far(lg, sound[:, first - 1])
        km = jnp.zeros((B, T_max), bool).at[:, :first].set(mask[:, :first])
        plen = mask[:, :first].sum(1)
        n = Tp - first
        lg, caches = decode_verify(
            params, CFG, ids[:, first:Tp], plen[:, None] + jnp.arange(n)[None],
            jnp.full((B,), first), km, caches)
        worst = max(worst, far(lg[:, -1], sound[:, Tp - 1]))
        km = km.at[:, first:Tp].set(True)
        plen = mask[:, :Tp].sum(1)
        step = jax.jit(lambda t, pos, slot, km, c: decode_step(
            params, CFG, t, pos, slot, km, c))
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - Tp), t, km, caches)
            worst = max(worst, far(lg, sound[:, t]))
        want = ref.final_states(params, HF, ids, PAD)
    assert worst < TOL
    # the state after the same tokens is the reference's, a mixer layer each
    assert far(caches[2][1], want) < 1e-5 * float(jnp.abs(want).max())
    # and a state ROUNDED to bfloat16 is not
    rounded = caches[2][1].astype(jnp.bfloat16).astype(jnp.float32)
    assert far(rounded, want) > 1e-3 * float(jnp.abs(want).max())


def test_the_recurrent_state_is_float32_whatever_the_cache():
    tail, S = init_kv_cache(CFG, 2, 8, jnp.bfloat16)[2]
    assert (tail.dtype, S.dtype) == (jnp.bfloat16, jnp.float32)
    groups = init_paged_kv_cache(CFG, (8, 1), 4, jnp.bfloat16, state_rows=3)
    tail, S = groups[2]
    assert (tail.dtype, S.dtype) == (jnp.bfloat16, jnp.float32)
    assert S.shape == (6, 3, 4, 16, 8) and groups[0][0].shape[0] == 2


def test_paged_prefill_and_decode_are_the_reference(params, ids, sound):
    """The paged cache without a session: the attention layers' pages
    through a table, the state at the rows the state's "table" names."""
    B, P, T_max, Tp = ids.shape[0], 4, ids.shape[1], 24
    nb = T_max // P
    mask = ids != PAD
    tabs = (jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb),
            jnp.zeros((B, 1), jnp.int32),
            jnp.arange(B, dtype=jnp.int32)[:, None])
    with jax.default_matmul_precision("highest"):
        caches = init_paged_kv_cache(CFG, (B * nb, 1), P, jnp.float32,
                                     state_rows=B)
        lg, caches = prefill(params, CFG, ids[:, :Tp], mask[:, :Tp], caches,
                             page_table=tabs, page_size=P, logical_len=T_max)
        worst = far(lg, sound[:, Tp - 1])
        km = jnp.zeros((B, T_max), bool).at[:, :Tp].set(mask[:, :Tp])
        plen = mask[:, :Tp].sum(1)
        step = jax.jit(lambda t, pos, slot, km, c: decode_step(
            params, CFG, t, pos, slot, km, c, page_table=tabs, page_size=P))
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - Tp),
                              jnp.full((B,), t, jnp.int32), km, caches)
            worst = max(worst, far(lg, sound[:, t]))
    assert worst < TOL


# ------------------------------------------------------------- the session

def session(params, cfg=CFG, **kw):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    return DecodeSession(
        params, cfg, **{**dict(
            rows=3, prompt_len=48, max_tokens=24, page_size=4, eos_token_id=EOS,
            pad_token_id=PAD, key=jax.random.PRNGKey(1), per_row=True,
            prefix_cache=RadixCache(headroom=0.0), sync_every=4,
            prefill_chunk=8), **kw})


def serve(sess, lengths, budgets, seed):
    """A wave: the prompts admitted into rows 0.., driven to the end with
    look-ahead off (`step`), the rows released. (prompts, greedy answers)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, V, n) for n in lengths]
    for r, p in enumerate(prompts):
        toks, mask = np.zeros(48, np.int32), np.zeros(48, bool)
        toks[48 - len(p):], mask[48 - len(p):] = p, True
        sess.admit(r, toks, mask, r, budget=budgets[r], temperature=1.0,
                   top_p=1.0, greedy=True)
    for _ in range(80):
        done, _ = sess.step()
        if done.all() and not sess.has_pending():
            break
    out = np.asarray(sess.state[1])
    answers = [out[r, :n] for r, n in enumerate(budgets)]
    for r in range(len(prompts)):
        sess.release(r)
    return prompts, answers


WAVES = (((41, 6, 19), (24, 14, 9)),    # 41 tokens from slot 7: five pieces
         ((3, 27, 2), (12, 10, 16)))    # the same rows again, two nearly empty


def gaps(params, prompts, answers, **flags):
    """How far under the reference's top each served token lies, a row."""
    out = []
    for p, a in zip(prompts, answers):
        seq = jnp.asarray(np.concatenate([p, a])[None])
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(params, HF, seq, PAD, last=len(a) + 1,
                                       mask=jnp.ones(seq.shape, bool),
                                       **flags))[0, :-1]
        out.append(lg.max(-1) - lg[np.arange(len(a)), a])
    return out


@pytest.mark.parametrize("impl", [
    "auto", pytest.param("pallas", marks=pytest.mark.slow)])    # (40 s interpreted)
def test_session_pieces_pages_reuse_and_chunks_follow_the_reference(params, impl):
    """Prompts in pieces of 8 across pages of 4, decode across page
    boundaries, rows used again after another's state."""
    sess = session(params, dataclasses.replace(CFG, attention_impl=impl))
    assert sess.state_layers == 6 and sess.window_layers == 0
    # a mixer layer: the tail 3 x 80 and the state 4 x 16 x 8, float32 here
    assert sess.state_bytes_per_row == 6 * (3 * 80 + 4 * 16 * 8) * 4
    # pages of the TWO attention layers alone
    assert sess.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        for g in gaps(params, prompts, answers):
            assert g.max() < TOL
        if seed == 0:
            for wrong in ("attention", "shared_expert", "gate_before_norm"):
                assert max(g.max() for g in gaps(
                    params, prompts, answers, without=(wrong,))) > 0.05
    assert sess.state_resets == 6 and sess.state_piece_carries == 5 + 2 + 3
    assert sess.chunked_admissions == 3 and sess.hit_tokens == 0


@pytest.mark.parametrize("fault, wave, fresh_to", [
    ("zeroed_at_every_piece", 0, jnp.ones_like),
    ("not_reset_on_reuse", 1, jnp.zeros_like),
])
def test_a_state_fault_leaves_the_reference(params, monkeypatch, fault, wave,
                                            fresh_to):
    # (a config of its own: the jitted programs are keyed by it)
    cfg = dataclasses.replace(CFG, max_position_embeddings=1000 + wave)
    sound_ctx = M._conv_ctx
    monkeypatch.setattr(
        M, "_conv_ctx", lambda config, valid=None, fresh=None: sound_ctx(
            config, valid, None if fresh is None else lambda: fresh_to(fresh())))
    sess = session(params, cfg)
    worst = []
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        worst.append(max(g.max() for g in gaps(params, prompts, answers)))
    assert worst[wave] > 0.05, (fault, worst)
    if wave == 1:       # rows that were never used start from zeros anyway
        assert worst[0] < TOL


def test_engine_serves_generates_tokens_and_counts_what_the_sizes_say(params):
    from nanorlhf_tpu.sampler import SamplingParams, generate
    from nanorlhf_tpu.serving.engine import ServingEngine

    rng = np.random.default_rng(3)
    same, other = rng.integers(3, V, 30), rng.integers(3, V, 5)
    with ServingEngine(params, CFG, eos_token_id=EOS, pad_token_id=PAD,
                       page_size=4, prompt_len=48, max_new_tokens=16, rows=2,
                       headroom=0.0, sync_every=4, prefill_chunk=8) as engine:
        reqs = [engine.submit(p, greedy=True, max_tokens=8)[0]
                for p in (same, other, same)]
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    assert [len(s) for s in streams] == [8, 8, 8]
    # the third request took a row another's state was left in
    assert streams[0] == streams[2]
    assert gaps(params, [same], [np.asarray(streams[0])])[0].max() < TOL
    # greedy tokens equal to the contiguous `generate`'s
    prompt = jnp.asarray(same[None])
    out = generate(params, CFG, prompt, prompt != PAD, jax.random.PRNGKey(0),
                   SamplingParams(n=1, max_tokens=8, greedy=True),
                   eos_token_id=EOS, pad_token_id=PAD)
    assert np.asarray(out)[0].tolist() == streams[0]
    state_row = 6 * (3 * 80 + 4 * 16 * 8) * 4
    kv_token = 2 * 2 * 2 * 16 * 4
    assert m["serving/prefix_hit_tokens"] == 0
    assert (m["serving/state_layers"], m["serving/page_layers"]) == (6, 2)
    assert m["serving/window_layers"] == 0
    assert m["serving/state_bytes_per_row"] == state_row
    assert m["serving/kv_bytes_per_token"] == kv_token
    assert m["serving/state_resets"] == 3
    assert m["serving/state_piece_carries"] == 2 * 3    # 30 tokens: 8, 8, 8, 6
    assert m["serving/state_live_bytes"] \
        == m["serving/live_row_steps"] * state_row > 0
    assert m["serving/page_live_bytes"] \
        == m["serving/global_slots_read"] * kv_token > 0
    assert m["serving/held_experts_hit"] > 0


# ---------------------------------------------------------------- refusals

def test_a_radix_hit_raises(params):
    from nanorlhf_tpu.serving.radix import prompt_key

    sess = session(params)
    toks, mask = np.zeros(48, np.int32), np.zeros(48, bool)
    toks[20:], mask[20:] = np.arange(3, 31), True
    sess.admit(0, toks, mask, 0, budget=4, temperature=1.0, top_p=1.0,
               greedy=True)
    sess._radix.insert(prompt_key(toks, mask), sess.table_np[0], 48)
    with pytest.raises(NotImplementedError,
                       match="snapshot of the recurrent state") as e:
        sess.admit(1, toks, mask, 1, budget=4, temperature=1.0, top_p=1.0,
                   greedy=True)
    assert "granitemoehybrid" in str(e.value)


@pytest.mark.parametrize("kw, cfg_kw, what", [
    ({"spec_k": 2, "greedy": True}, {}, "rolled back"),
    ({"per_row": False}, {}, "rollout scheduler"),
    ({}, {"kv_cache_quant": "int8"}, "int8"),
    ({}, {"spmd_mesh": "a mesh"}, "mesh"),
])
def test_session_raises_by_name_on_what_is_not_built(params, kw, cfg_kw, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        session(params, dataclasses.replace(CFG, **cfg_kw), **kw)
    assert "state-space layers (granitemoehybrid)" in str(e.value)


@pytest.mark.parametrize("what", ["spec", "paged", "trainer", "lora"])
def test_rollout_paths_the_trainer_and_lora_raise_by_name(params, what):
    from nanorlhf_tpu.sampler import SamplingParams, generate

    prompts = jnp.asarray([[0, 5, 6, 7], [9, 8, 7, 6]])
    run = lambda sp: generate(params, CFG, prompts, prompts != 0,  # noqa: E731
                              jax.random.PRNGKey(0), sp, eos_token_id=EOS,
                              pad_token_id=PAD)
    if what == "spec":
        with pytest.raises(NotImplementedError, match="rollback") as e:
            run(SamplingParams(max_tokens=4, spec_k=2))
    elif what == "paged":
        with pytest.raises(NotImplementedError,
                           match="no state that is not a page") as e:
            run(SamplingParams(max_tokens=4, page_size=4))
    elif what == "trainer":
        from nanorlhf_tpu.trainer import RLTrainer

        with pytest.raises(NotImplementedError, match="training a model") as e:
            RLTrainer(None, CFG, None, params, None, None)
    else:
        from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

        with pytest.raises(NotImplementedError, match="LoRA adapter") as e:
            init_lora_params(CFG, LoraConfig(), jax.random.PRNGKey(0))
    assert "granitemoehybrid" in str(e.value)
