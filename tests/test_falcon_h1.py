"""Falcon-H1 (docs/SSM.md): a Mamba-2 state-space mixer and an attention side
by side in every layer, so a layer keeps pages AND a state (a convolution's
tail and a float32 recurrent state), under the published muP multipliers,
against the plain float32 reference of
benchmark/harness/reference_falcon_h1.py on seeded weights. Tiny widths;
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

from harness import reference_falcon_h1 as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from nanorlhf_tpu.ops import ssm as ops  # noqa: E402

with open(os.path.join(BENCH, "configs", "falcon-h1-34b-l5.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-falcon-h1.json")) as f:
    HF = {**json.load(f), "vocab_size": 128}
V = HF["vocab_size"]
CFG = ModelConfig.from_hf_config(HF)
TOL = 1e-4
EOS, PAD = 1, 0
# every number of the config that multiplies something, by the name the
# reference's `without` takes
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "key_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier",
               "mlp_gate_multiplier", "mlp_down_multiplier",
               "lm_head_multiplier", "mup")
LEAVES = ("conv_bias", "D", "dt_bias", "norm")


def spread(p):
    """Everything the controls need to matter: `D`, the conv bias and the
    mixer's norm away from ones and zeros, logits of a size that shows, and
    an EOS and a pad no row can emit."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    s = p["layers"]["ssm"]
    s["D"] = 1 + 0.5 * jax.random.normal(keys[0], s["D"].shape)
    s["conv"]["bias"] = 0.5 * jax.random.normal(keys[1], s["conv"]["bias"].shape)
    s["norm"] = jnp.exp(0.5 * jax.random.normal(keys[2], s["norm"].shape))
    p["lm_head"] = (p["lm_head"] * 30).at[:, jnp.asarray([EOS, PAD])].set(0)
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
    c = ModelConfig.from_hf_config({**FILE, **FILE["published"]})
    assert c == ModelConfig.falcon_h1_34b()
    assert (c.num_hidden_layers, c.ssm_layers, c.state_layers) == (72, 72, 72)
    assert (c.conv_layers, c.window_layers) == (0, 0)
    assert c.attention_pattern == ("hybrid",)
    assert (c.ssm_inner, c.ssm_conv_width) == (4096, 5120)
    assert c.embed_scale == FILE["embedding_multiplier"]
    assert c.ssm_multipliers == tuple(FILE["ssm_multipliers"])
    cut = ModelConfig.from_hf_config(FILE)
    assert cut == dataclasses.replace(c, num_hidden_layers=5,
                                      layer_types=("hybrid",) * 5)
    assert CFG == ModelConfig.falcon_h1_tiny(vocab_size=V)
    assert ModelConfig.qwen2_tiny().state_layers == 0


def test_the_layers_parameters_are_the_catalogs():
    """430,120,032 a layer, the catalog's "about 430M"."""
    shapes = jax.eval_shape(lambda: init_params(
        ModelConfig.from_hf_config({**FILE, "num_hidden_layers": 1}),
        jax.random.PRNGKey(0)))
    count = lambda tree: sum(int(np.prod(a.shape))                 # noqa: E731
                             for a in jax.tree.leaves(tree))
    assert count(shapes["layers"]["ssm"]) == 68_351_072
    assert count(shapes["layers"]) == 430_120_032
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
        == 1_336_934_400


@pytest.mark.parametrize("change, what", [
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"attn_layer_indices": [0]}, "attn_layer_indices"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"projectors_bias": True}, "projectors_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"mamba_d_ssm": 48}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"ssm_multipliers": [1.0, 1.0]}, "ssm_multipliers"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_from_hf_config_raises_by_name_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=f"falcon_h1: {what}"):
        ModelConfig.from_hf_config({**HF, **change})


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    sd = hf_state_dict_from_params(CFG, params)
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (96, 1, 4)
    assert sd["model.layers.1.mamba.in_proj.weight"].shape == (164, 64)
    assert params["layers"]["ssm"]["in_proj"]["kernel"].shape == (2, 64, 160)
    assert params["layers"]["ssm"]["dt_proj"]["kernel"].shape == (2, 64, 4)
    assert sd["model.layers.1.mamba.A_log"].shape == (4,)
    assert sd["model.layers.0.mamba.norm.weight"].shape == (64,)
    assert sd["model.layers.0.pre_ff_layernorm.weight"].shape == (64,)
    assert sd["lm_head.weight"].shape == (V, 64)
    assert "model.final_layernorm.weight" in sd
    assert "model.layers.1.feed_forward.down_proj.weight" in sd
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ the recurrence

@pytest.mark.parametrize("T, chunk", [(11, 4), (8, 4), (3, 8), (1, 4)])
def test_the_chunked_scan_is_the_token_scan(T, chunk):
    k = jax.random.split(jax.random.PRNGKey(T), 6)
    B, H, P, G, N = 2, 4, 16, 2, 8
    xs = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)))
    dt = dt.at[0, :T // 2].set(0)       # pads: neither decay nor feed
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, T, G, N))
    Cm = jax.random.normal(k[4], (B, T, G, N))
    S = jax.random.normal(k[5], (B, H, P, N))
    y, out = ops.ssd_scan(xs, dt, A, Bm, Cm, S, chunk)
    want_y, want = ops.ssm_token_scan(xs, dt, A, Bm, Cm, S)
    assert far(y, want_y) < 1e-4 and far(out, want) < 1e-5
    # a row of pads alone leaves its state bit for bit
    _, kept = ops.ssd_scan(xs, jnp.zeros_like(dt), A, Bm, Cm, S, chunk)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(S))


@pytest.mark.parametrize("live, layer, row0, fresh, heads, dtype", [
    pytest.param(None, 0, 0, None, None, "float32", id="every_row"),
    pytest.param((1, 1, 1, 1, 1, 1), 2, 0, None, None, "float32",
                 id="all_live"),
    pytest.param((0, 0, 0, 0, 0, 0), 1, 2, None, None, "float32",
                 id="none_live"),
    pytest.param((0, 1, 0, 0, 1, 0), 2, 1, None, 2, "float32",
                 id="a_scattered_third"),
    pytest.param((0, 0, 0, 0, 0, 1), 1, 3, None, 1, "float32",
                 id="the_last_row_only"),
    pytest.param((1, 0, 1, 1, 0, 1), 2, 2, (0, 1, 1, 0, 0, 0), None,
                 "float32", id="a_fresh_live_row"),
    pytest.param(None, 1, 1, (0, 0, 0, 1, 0, 0), 4, "float32",
                 id="fresh_and_every_row"),
    # benchmark/tools/ssm_control.py's control: rounded as it is written
    pytest.param((1, 0, 1, 0, 0, 1), 0, 4, None, None, "bfloat16",
                 id="a_bfloat16_stack"),
])
def test_the_in_place_update_is_the_update_on_the_live_rows(
        live, layer, row0, fresh, heads, dtype):
    """`ssm_update_in_place` (interpret mode) against `ssm_update` on the
    sliced layer: a live row's `y` and state, and EVERY other row of the
    stack (not live, another layer's, outside the call's rows) bit for bit
    what went in; `heads`: the block of heads a grid step takes."""
    k = jax.random.split(jax.random.PRNGKey(layer + 7 * row0), 6)
    L, R, B, H, P, G, N = 3, 10, 6, 4, 16, 2, 8
    stack = jax.random.normal(k[0], (L, R, H, P, N)).astype(dtype)
    xs = jax.random.normal(k[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)))
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    Bm, Cm = (jax.random.normal(k_, (B, G, N)) for k_ in k[4:])
    live, fresh = (None if m is None else jnp.asarray(m, bool)
                   for m in (live, fresh))
    y, out = jax.jit(lambda s, at, r: ops.ssm_update_in_place(
        s, at, r, live, fresh, xs, dt, A, Bm, Cm, heads_a_block=heads))(
            stack, jnp.int32(layer), jnp.int32(row0))
    before = stack[layer, row0:row0 + B]
    if fresh is not None:
        before = jnp.where(fresh[:, None, None, None], 0, before)
    want_y, want = jax.jit(ops.ssm_update)(xs, dt, A, Bm, Cm, before)
    on = np.ones(B, bool) if live is None else np.asarray(live)
    assert out.dtype == stack.dtype and out.shape == stack.shape
    y, out, stack, want = (np.asarray(a, np.float32) for a in (
        y, out, stack, want.astype(dtype)))
    visited = np.zeros((L, R), bool)
    visited[layer, row0 + np.flatnonzero(on)] = True
    np.testing.assert_array_equal(out[~visited], stack[~visited])
    assert not y[~on].any()
    if on.any():
        # elementwise: equal up to one rounding of a fused multiply-add
        np.testing.assert_allclose(
            out[layer, row0:row0 + B][on], want[on], atol=1e-6,
            rtol=3e-7 if dtype == "float32" else 2 ** -7)
        assert far(y[on], np.asarray(want_y)[on]) < 1e-5


# ------------------------------------------------------ forwards and caches

def test_uncached_forward_is_the_reference(params, ids, sound):
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, sound, real) < TOL
    assert np.asarray(sound)[real].std() > 0.3      # logits that show


@pytest.mark.parametrize("left_out", MULTIPLIERS + LEAVES
                         + ("mixer", "attention"))
def test_every_multiplier_and_leaf_is_applied(params, ids, sound, left_out):
    """No multiplier of the tiny config is 1, and the reference without any
    one of them (or with a mixer's leaf dropped, or a branch zeroed) is
    another model: the system follows the sound one."""
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        wrong = ref.logits(params, HF, ids, PAD, without=(left_out,))
    assert far(wrong, sound, real) > 100 * TOL, left_out


def test_a_multiplier_of_one_stages_nothing(params, ids):
    ones = dataclasses.replace(
        CFG, embed_scale=1.0, attention_in_multiplier=1.0, key_multiplier=1.0,
        attention_out_multiplier=1.0, ssm_in_multiplier=1.0,
        ssm_multipliers=(1.0,) * 5, ssm_out_multiplier=1.0,
        mlp_multipliers=(1.0, 1.0), lm_head_multiplier=1.0)
    count = lambda cfg: str(jax.make_jaxpr(                        # noqa: E731
        lambda p: padded_forward_logits(p, cfg, ids, PAD))(params)).count(" mul ")
    # embedding, logits; a layer: attention in, k, out; mixer in, mup and
    # dt's part of it, out; gate, down
    assert count(CFG) - count(ones) == 2 + 9


def test_contiguous_prefill_and_decode_are_the_reference(params, ids, sound):
    B, T_max, Tp = ids.shape[0], ids.shape[1], 24
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        caches = init_kv_cache(CFG, B, T_max, jnp.float32)
        tail, S = caches[2]
        assert tail.shape == (2, 3, B, 96) and S.shape == (2, B, 4, 16, 8)
        assert caches[0][0].shape == (2, B, 2, T_max, 16)
        assert caches[1][0].shape[0] == 0           # no window layer
        lg, caches = prefill(params, CFG, ids[:, :Tp], mask[:, :Tp], caches)
        worst = far(lg, sound[:, Tp - 1])
        km = jnp.zeros((B, T_max), bool).at[:, :Tp].set(mask[:, :Tp])
        plen = mask[:, :Tp].sum(1)
        step = jax.jit(lambda t, pos, slot, km, c: decode_step(
            params, CFG, t, pos, slot, km, c))
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - Tp), t, km, caches)
            worst = max(worst, far(lg, sound[:, t]))
    assert worst < TOL


def test_the_recurrent_state_is_float32_whatever_the_cache():
    tail, S = init_kv_cache(CFG, 2, 8, jnp.bfloat16)[2]
    assert (tail.dtype, S.dtype) == (jnp.bfloat16, jnp.float32)
    tail, S = init_paged_kv_cache(CFG, (8, 1), 4, jnp.bfloat16,
                                  state_rows=3)[2]
    assert (tail.dtype, S.dtype) == (jnp.bfloat16, jnp.float32)
    assert S.shape == (2, 3, 4, 16, 8)


def _pieces(params, row, cuts, T_max=48, bucket=0):
    """One row's tokens through `decode_verify` in pieces cut at `cuts`
    (contiguous cache; `bucket` pad tokens after each piece, marked not
    valid): (the last real token's logits, the state group)."""
    caches = init_kv_cache(CFG, 1, T_max, jnp.float32)
    km = jnp.zeros((1, T_max), bool)
    logits = None
    for lo, hi in zip((0,) + cuts, cuts + (len(row),)):
        n = hi - lo
        toks = jnp.asarray(np.concatenate([row[lo:hi], np.full(bucket, 7)]),
                           jnp.int32)[None]
        pos = lo + jnp.arange(n + bucket)[None]
        logits, caches = decode_verify(
            params, CFG, toks, pos, jnp.asarray([lo]), km, caches,
            token_valid=jnp.arange(n + bucket)[None] < n)
        km = km.at[0, lo:hi].set(True)
        logits = logits[0, n - 1]
    return logits, caches[2]


@pytest.mark.parametrize("cuts, bucket", [((7,), 0), ((5, 14), 0),
                                          ((9,), 3), ((1, 2, 22), 2)])
def test_a_prompt_in_pieces_is_the_prompt_whole(params, cuts, bucket):
    """Pieces that are no multiple of the scan's chunk (4), with a bucket's
    pads after each: both state leaves are handed over and a pad after the
    last real token neither decays nor feeds them."""
    row = np.random.default_rng(len(cuts)).integers(3, V, 23)
    with jax.default_matmul_precision("highest"):
        whole, state = _pieces(params, row, ())
        got, got_state = _pieces(params, row, cuts, bucket=bucket)
        want = np.asarray(ref.logits(params, HF, jnp.asarray(row[None]), PAD))
    assert far(whole, want[0, -1]) < TOL and far(got, want[0, -1]) < TOL
    for a, b in zip(got_state, state):
        assert far(a, b) < 1e-5


def test_left_pads_and_rows_nobody_listens_to_leave_the_state(params, ids):
    """A left-padded prompt leaves the state of the same prompt unpadded; a
    decode step leaves both leaves of a row that is not `live` bit for bit
    and moves the live rows'."""
    B, T_max, Tp = ids.shape[0], ids.shape[1], 24
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        _, caches = prefill(params, CFG, ids[:, :Tp], mask[:, :Tp],
                            init_kv_cache(CFG, B, T_max, jnp.float32))
        bare = ids[:1, 8:Tp]            # row 0 without its eight pads
        _, alone = prefill(params, CFG, bare, jnp.ones_like(bare, bool),
                           init_kv_cache(CFG, 1, T_max, jnp.float32))
        for padded, unpadded in zip(caches[2], alone[2]):
            lead = 2 if padded.ndim == 4 else 1     # the rows' axis
            assert far(jnp.take(padded, 0, axis=lead),
                       jnp.take(unpadded, 0, axis=lead)) < 1e-5
        km = jnp.zeros((B, T_max), bool).at[:, :Tp + 1].set(True)
        live = jnp.asarray([True, False, True])
        _, after = decode_step(params, CFG, ids[:, Tp], mask[:, :Tp].sum(1),
                               Tp, km, caches, live=live)
    for before, now in zip(caches[2], after[2]):
        lead = 2 if before.ndim == 4 else 1
        np.testing.assert_array_equal(np.asarray(jnp.take(before, 1, axis=lead)),
                                      np.asarray(jnp.take(now, 1, axis=lead)))
        assert far(jnp.take(before, 0, axis=lead),
                   jnp.take(now, 0, axis=lead)) > 1e-3


def test_paged_prefill_and_decode_are_the_reference(params, ids, sound):
    """The paged cache without a session: pages through a table, the state
    at the rows the state's "table" names."""
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
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = decode_step(
                params, CFG, ids[:, t], plen + (t - Tp),
                jnp.full((B,), t, jnp.int32), km, caches, page_table=tabs,
                page_size=P)
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


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_session_pieces_pads_reuse_and_chunks_follow_the_reference(params, impl):
    sess = session(params, dataclasses.replace(CFG, attention_impl=impl))
    assert sess.state_layers == 2 and sess.window_layers == 0
    # a layer: the tail 3 x 96 and the state 4 x 16 x 8, float32 both here
    assert sess.state_bytes_per_row == 2 * (3 * 96 + 4 * 16 * 8) * 4
    assert sess.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        for g in gaps(params, prompts, answers):
            assert g.max() < TOL
        if seed == 0:
            for wrong in ("mixer", "attention", "mup"):
                assert max(g.max() for g in gaps(
                    params, prompts, answers, without=(wrong,))) > 0.05
    # every admission started its row from zeros; the 41 tokens took the
    # state over five times, the 19 twice, the 27 three times
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


def test_engine_serves_counts_and_takes_no_prefix_hit(params):
    from nanorlhf_tpu.serving.engine import ServingEngine

    with ServingEngine(params, CFG, eos_token_id=EOS, pad_token_id=PAD,
                       page_size=4, prompt_len=48, max_new_tokens=16, rows=2,
                       headroom=0.0, sync_every=4, prefill_chunk=8) as engine:
        rng = np.random.default_rng(3)
        same = rng.integers(3, V, 30)
        reqs = [engine.submit(p, greedy=True, max_tokens=8)[0]
                for p in (same, rng.integers(3, V, 5), same)]
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    assert [len(s) for s in streams] == [8, 8, 8]
    assert streams[0] == streams[2]             # the same prompt, served cold
    assert gaps(params, [same], [np.asarray(streams[0])])[0].max() < TOL
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/state_layers"] == 2 and m["serving/window_layers"] == 0
    assert m["serving/state_bytes_per_row"] == 2 * (3 * 96 + 4 * 16 * 8) * 4
    assert m["serving/kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert m["serving/state_resets"] == 3
    assert m["serving/state_piece_carries"] == 2 * 3    # 30 tokens: 8, 8, 8, 6


# ---------------------------------------------------------------- refusals

def test_a_radix_hit_raises(params):
    from nanorlhf_tpu.serving.radix import prompt_key

    sess = session(params)
    toks, mask = np.zeros(48, np.int32), np.zeros(48, bool)
    toks[20:], mask[20:] = np.arange(3, 31), True
    sess.admit(0, toks, mask, 0, budget=4, temperature=1.0, top_p=1.0,
               greedy=True)
    # what a tree that held this model's pages would do on the next admission
    sess._radix.insert(prompt_key(toks, mask), sess.table_np[0], 48)
    with pytest.raises(NotImplementedError, match="snapshot of the recurrent state") as e:
        sess.admit(1, toks, mask, 1, budget=4, temperature=1.0, top_p=1.0,
                   greedy=True)
    assert "falcon_h1" in str(e.value)


@pytest.mark.parametrize("kw, cfg_kw, what", [
    ({"spec_k": 2, "greedy": True}, {}, "rolled back"),
    ({"per_row": False}, {}, "rollout scheduler"),
    ({}, {"kv_cache_quant": "int8"}, "int8"),
    ({}, {"spmd_mesh": "a mesh"}, "mesh"),
])
def test_session_raises_by_name_on_what_is_not_built(params, kw, cfg_kw, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        session(params, dataclasses.replace(CFG, **cfg_kw), **kw)
    assert "state-space layers (falcon_h1)" in str(e.value)


def test_the_contiguous_rollout_is_the_reference_and_fans_the_state(params):
    from nanorlhf_tpu.sampler import SamplingParams, generate

    prompts = jnp.asarray([[0, 5, 6, 7], [9, 8, 7, 6]])
    out = generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                   SamplingParams(n=1, max_tokens=8, greedy=True),
                   eos_token_id=EOS, pad_token_id=PAD)
    seq = np.concatenate([np.asarray(prompts), np.asarray(out)], axis=1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, HF, jnp.asarray(seq), PAD))
    gap = want[:, 3:-1].max(-1) - np.take_along_axis(
        want[:, 3:-1], np.asarray(out)[..., None], axis=-1)[..., 0]
    assert gap.max() < TOL
    # n > 1 prefills a prompt once and fans its cache out: both state leaves
    fanned = generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                      SamplingParams(n=2, max_tokens=8, greedy=True),
                      eos_token_id=EOS, pad_token_id=PAD)
    assert np.array_equal(np.asarray(fanned), np.repeat(np.asarray(out), 2, 0))


@pytest.mark.parametrize("what", ["spec", "paged", "int8", "state_rows",
                                  "trainer", "lora"])
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
    elif what == "int8":
        with pytest.raises(NotImplementedError, match="int8") as e:
            init_kv_cache(dataclasses.replace(CFG, kv_cache_quant="int8"), 1, 8)
    elif what == "state_rows":
        with pytest.raises(ValueError, match="state_rows") as e:
            init_paged_kv_cache(CFG, (8, 2), 4)
    elif what == "trainer":
        from nanorlhf_tpu.trainer import RLTrainer

        with pytest.raises(NotImplementedError, match="training a model") as e:
            RLTrainer(None, CFG, None, params, None, None)
    else:
        from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

        with pytest.raises(NotImplementedError, match="LoRA adapter") as e:
            init_lora_params(CFG, LoraConfig(), jax.random.PRNGKey(0))
    assert "falcon_h1" in str(e.value)
