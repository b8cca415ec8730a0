"""LFM2-MoE (docs/STATE.md): gated short-convolution layers with a state of
fixed size beside paged attention layers of packed 64-wide heads, a per-head
q/k norm and a sigmoid router whose selection is bias-corrected, against the
plain float32 reference of benchmark/harness/reference_lfm2.py on seeded
weights. Tiny widths; logits, not tokens."""

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

from harness import reference_lfm2 as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, init_kv_cache, init_paged_kv_cache, prefill,
)

with open(os.path.join(BENCH, "configs", "lfm2-24b-l10.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-lfm2.json")) as f:
    HF = {**json.load(f), "vocab_size": 128}
V = HF["vocab_size"]
CFG = ModelConfig.from_hf_config(HF)
TOL = 1e-4
EOS, PAD = 1, 0


@pytest.fixture(scope="module")
def params():
    """Seeded weights with everything the controls need to matter: a bias
    that changes the choice, q/k norm weights that are not ones, and an EOS
    and a pad no row can emit (their tied rows are zero)."""
    p = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    layers = p["layers"]
    layers["router"]["bias"] = 0.1 * jax.random.normal(
        keys[0], layers["router"]["bias"].shape)
    for name, k in (("q_norm", keys[1]), ("k_norm", keys[2])):
        layers[name] = jnp.exp(0.5 * jax.random.normal(k, layers[name].shape))
    p["embed_tokens"] = p["embed_tokens"].at[jnp.asarray([EOS, PAD])].set(0)
    return p


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
                for name, flags in (("sound", {}), ("no_bias", {"bias": False}),
                                    ("no_qk_norm", {"qk_norm": False}))}


def far(a, b, real):
    return float(np.abs(np.asarray(a) - np.asarray(b))[real].max())


# ------------------------------------------------------------ configuration

def test_from_hf_config_on_the_published_keys():
    c = ModelConfig.from_hf_config({**FILE, **FILE["published"]})
    assert c == ModelConfig.lfm2_24b()
    assert (c.hidden_size, c.intermediate_size, c.moe_intermediate_size,
            c.actual_head_dim, c.vocab_size) == (2048, 11776, 1536, 64, 65536)
    assert (c.num_experts, c.num_experts_per_tok, c.num_dense_layers) == (64, 4, 2)
    assert (c.conv_layers, c.window_layers, c.conv_L_cache) == (30, 0, 3)
    # 38 expert layers starting at an attention layer: [a, c, c, c] x 9 + [a, c]
    assert c.attention_pattern == c.layer_kinds[2:]
    cut = ModelConfig.from_hf_config(FILE)
    assert cut.num_hidden_layers == 10 and cut.conv_layers == 8
    assert cut.attention_pattern == ((False, True), "conv", "conv", "conv")
    assert cut.stack_pattern(0, 2) == ("conv",)
    assert FILE["reduced"] == ["num_hidden_layers", "layer_types"]
    assert dataclasses.replace(cut, num_hidden_layers=40, layer_types=tuple(
        FILE["published"]["layer_types"])) == ModelConfig.lfm2_24b()
    assert ModelConfig.qwen2_tiny().conv_layers == 0
    assert CFG == ModelConfig.lfm2_tiny(vocab_size=V)


@pytest.mark.parametrize("change, what", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv", "sliding_attention"] * 5}, "sliding_attention"),
    ({"layer_types": ["conv"] * 10}, "one layer kind"),
    ({"layer_types": ["conv"] * 4}, "4 entries for 10"),
    ({"conv_L_cache": 1}, "conv_L_cache"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope scaling"),
])
def test_from_hf_config_raises_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config({**HF, **change})


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    sd = hf_state_dict_from_params(CFG, params)
    assert sd["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert sd["model.layers.2.self_attn.q_layernorm.weight"].shape == (16,)
    assert sd["model.layers.2.feed_forward.expert_bias"].shape == (8,)
    assert "model.layers.0.feed_forward.w1.weight" in sd
    assert "model.layers.3.feed_forward.experts.7.w2.weight" in sd
    assert "model.layers.1.self_attn.q_proj.weight" not in sd
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ forwards and caches

def test_uncached_forward_is_the_reference(params, ids, reference_logits):
    real = np.asarray(ids != PAD)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, reference_logits["sound"], real) < TOL
    assert far(got, reference_logits["no_bias"], real) > 0.05
    assert far(got, reference_logits["no_qk_norm"], real) > 0.05


def test_a_period_that_does_not_tile_runs_as_one(params):
    """Eight layers: the six expert layers [a, c, c, c, a, c] have no
    shorter period than themselves (the published 38 have none either)."""
    hf = {**HF, "num_hidden_layers": 8, "layer_types": HF["layer_types"][:8]}
    cfg = ModelConfig.from_hf_config(hf)
    assert len(cfg.attention_pattern) == 6
    p = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).integers(3, V, (2, 12)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(p, cfg, x, PAD)
        want = ref.logits(p, hf, x, PAD)
    assert far(got, want, np.ones(x.shape, bool)) < TOL


def test_contiguous_prefill_and_decode_are_the_reference(params, ids,
                                                         reference_logits):
    want = reference_logits["sound"]
    B, T_max, Tp = ids.shape[0], ids.shape[1], 24
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        caches = init_kv_cache(CFG, B, T_max, jnp.float32)
        assert caches[2][0].shape == (8, 2, B, 64)      # the state, not a page
        assert caches[0][0].shape == (2, B, 1, T_max, 32)   # heads in pairs
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


def test_the_router_selects_with_the_bias_and_weighs_without():
    from nanorlhf_tpu.ops.moe import moe_mlp

    rng = np.random.default_rng(3)
    N, D, E, F, k = 32, 16, 8, 8, 2
    h, router = rng.standard_normal((N, D)), rng.standard_normal((D, E))
    gate, up = rng.standard_normal((2, E, D, F)) / 4
    down = rng.standard_normal((E, F, D)) / 3
    bias = 0.3 * rng.standard_normal(E)
    args = [jnp.asarray(a, jnp.float32) for a in (h, router, gate, up, down)]
    y, aux = moe_mlp(*args, k, True, scoring="sigmoid",
                     select_bias=jnp.asarray(bias, jnp.float32), norm_eps=1e-6)
    s = 1 / (1 + np.exp(-(h @ router)))
    chosen = np.argsort(-(s + bias), axis=1)[:, :k]
    plain = np.argsort(-s, axis=1)[:, :k]
    changed = np.asarray([set(a) != set(b) for a, b in zip(chosen, plain)])
    assert 0.1 < changed.mean() < 0.9       # the bias changes the choice
    np.testing.assert_array_equal(np.asarray(aux["bias_changed"]), changed)
    np.testing.assert_array_equal(np.sort(np.asarray(aux["experts"]), 1),
                                  np.sort(chosen, 1))
    want = np.zeros((N, D))
    for n in range(N):
        w = s[n, chosen[n]] / (s[n, chosen[n]].sum() + 1e-6)
        for e, we in zip(chosen[n], w):
            a = h[n] @ gate[e]
            want[n] += we * ((a / (1 + np.exp(-a)) * (h[n] @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)
    _, no_bias = moe_mlp(*args, k, True, scoring="sigmoid")
    assert "bias_changed" not in no_bias


# ----------------------------------------- the paged kernels at heads of 64

def _packed_case(rng, T):
    """Rows of unequal start, K and V heads of 64 by slot, and the same
    written in pairs into pages 128 lanes wide through a shuffled table."""
    B, KV, G, hd, P, nb, L, layer = 3, 4, 2, 64, 8, 6, 2, 1
    S = nb * P
    k_row, v_row = (rng.standard_normal((B, KV, S, hd)).astype(np.float32)
                    for _ in "kv")
    q = jnp.asarray(rng.standard_normal((B, KV * G, T, hd)), jnp.float32)
    N = B * nb + 1
    table = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    qp, kp, vp = M._pack_heads(q, jnp.asarray(k_row), jnp.asarray(v_row), 2)
    pools = []
    for rows in (kp, vp):
        pool = np.zeros((L, N, KV // 2, P, 2 * hd), np.float32)
        for r in range(B):
            for blk in range(nb):
                pool[layer, table[r, blk]] = np.asarray(rows)[
                    r, :, blk * P:(blk + 1) * P]
        pools.append(jnp.asarray(pool))
    assert pools[0].shape[-1] == 128
    return q, qp, k_row, v_row, pools, jnp.asarray(table), layer, (B, KV, G, hd, P, S)


def _plain(q, k_row, v_row, mask, dims):
    B, KV, G, hd, _, _ = dims
    T = q.shape[2]
    s = jnp.einsum("bkgqh,bkth->bkgqt", q.reshape(B, KV, G, T, hd), k_row)
    s = jnp.where(mask[:, None, None], s / np.sqrt(hd), -1e30)
    out = jnp.einsum("bkgqt,bkth->bkgqh", jax.nn.softmax(s, -1), v_row)
    return out.reshape(B, KV * G, T, hd)


def test_the_in_place_decode_read_serves_heads_of_64_in_pairs():
    from nanorlhf_tpu.ops.decode_attention import (
        paged_decode_attention, paged_decode_plan, paged_pages_per_item,
    )

    rng = np.random.default_rng(11)
    q, qp, k_row, v_row, pools, table, layer, dims = _packed_case(rng, 1)
    B, KV, G, hd, P, S = dims
    start = jnp.asarray([0, 5, 17], jnp.int32)
    filled = jnp.asarray([41, 30, 48], jnp.int32)
    plan = paged_decode_plan(table, start, filled, page_size=P,
                             num_pages=pools[0].shape[1],
                             pages_per_item=paged_pages_per_item(pools[0]))
    got = paged_decode_attention(qp[:, :, 0], *pools, jnp.int32(layer), plan,
                                 interpret=True)[:, :, None]
    got = M._unpack_heads(got, KV, 2)
    slot = jnp.arange(S)[None, None, :]
    mask = (slot >= start[:, None, None]) & (slot < filled[:, None, None])
    want = _plain(q, k_row, v_row, mask, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_the_paged_flash_read_serves_heads_of_64_in_pairs():
    from nanorlhf_tpu.ops.paged_prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(12)
    T = 16
    q, qp, k_row, v_row, pools, table, layer, dims = _packed_case(rng, T)
    B, KV, G, hd, P, S = dims
    start = jnp.asarray([0, 5, 9], jnp.int32)
    fill = jnp.asarray([24, 5, 32], jnp.int32)      # row 1 starts here
    got = paged_prefill_attention(qp, *pools, layer, table, start, fill, 0,
                                  block_q=8, pages_per_item=2, interpret=True)
    got = M._unpack_heads(got, KV, 2)
    slot = jnp.arange(S)[None, None, :]
    qi = jnp.arange(T)[None, :, None]
    mask = (slot >= start[:, None, None]) & (slot <= fill[:, None, None] + qi)
    want = _plain(q, k_row, v_row, mask, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


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
    assert sess.state_layers == 8 and sess.window_layers == 0
    assert sess.state_bytes_per_row == 8 * 2 * 64 * 4
    assert sess.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4    # two attention layers
    assert sess.prefill_read_in_place == int(impl == "pallas")
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        for g in gaps(params, prompts, answers):
            assert g.max() < TOL
        if seed == 0:
            assert max(g.max() for g in gaps(params, prompts, answers,
                                             bias=False)) > 0.05
            assert max(g.max() for g in gaps(params, prompts, answers,
                                             qk_norm=False)) > 0.05
    # every admission started its row from zeros; the 41 tokens took the
    # state over five times, the 19 twice, the 27 three times
    assert sess.state_resets == 6 and sess.state_piece_carries == 5 + 2 + 3
    assert sess.chunked_admissions == 3 and sess.hit_tokens == 0
    assert sess.held_experts_hit > 0


@pytest.mark.parametrize("fault, wave, fresh_to", [
    ("zeroed_at_every_piece", 0, jnp.ones_like),
    ("not_reset_on_reuse", 1, jnp.zeros_like),
])
def test_a_state_fault_leaves_the_reference(params, monkeypatch, fault, wave,
                                            fresh_to):
    # (a config of its own: the jitted programs are keyed by it)
    cfg = dataclasses.replace(CFG, max_position_embeddings=1000 + wave)
    monkeypatch.setattr(M, "_conv_ctx", _state_fault(fresh_to))
    sess = session(params, cfg)
    worst = []
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        worst.append(max(g.max() for g in gaps(params, prompts, answers)))
    assert worst[wave] > 0.05, (fault, worst)
    if wave == 1:       # rows that were never used start from zeros anyway
        assert worst[0] < TOL


def _state_fault(fresh_to):
    """`core.model._conv_ctx` with the rows that start at an admission
    forward (`decode_verify`'s `fresh`) replaced: the CONTROL faults of
    benchmark/tools/state_control.py."""
    sound = M._conv_ctx

    def faulty(config, valid=None, fresh=None):
        return sound(config, valid,
                     None if fresh is None else lambda: fresh_to(fresh()))

    return faulty


# ---------------------------------------------------------------- refusals

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
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/state_layers"] == 8 and m["serving/window_layers"] == 0
    assert m["serving/state_bytes_per_row"] == 8 * 2 * 64 * 4
    assert m["serving/kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert m["serving/state_resets"] == 3
    assert m["serving/state_piece_carries"] == 2 * 3    # 30 tokens: 8, 8, 8, 6


def test_a_radix_hit_raises(params):
    from nanorlhf_tpu.serving.radix import prompt_key

    sess = session(params)
    toks, mask = np.zeros(48, np.int32), np.zeros(48, bool)
    toks[20:], mask[20:] = np.arange(3, 31), True
    sess.admit(0, toks, mask, 0, budget=4, temperature=1.0, top_p=1.0,
               greedy=True)
    # what a tree that held this model's pages would do on the next admission
    sess._radix.insert(prompt_key(toks, mask), sess.table_np[0], 48)
    with pytest.raises(NotImplementedError, match="snapshot of the conv state"):
        sess.admit(1, toks, mask, 1, budget=4, temperature=1.0, top_p=1.0,
                   greedy=True)


@pytest.mark.parametrize("kw, cfg_kw, what", [
    ({"spec_k": 2, "greedy": True}, {}, "rolled back"),
    ({"per_row": False}, {}, "rollout scheduler"),
    ({}, {"kv_cache_quant": "int8"}, "int8"),
    ({}, {"spmd_mesh": "a mesh"}, "mesh"),
])
def test_session_raises_by_name_on_what_is_not_built(params, kw, cfg_kw, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        session(params, dataclasses.replace(CFG, **cfg_kw), **kw)
    assert "lfm2_moe" in str(e.value)


def test_rollout_paths_and_the_trainer_raise_by_name(params):
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
    assert gap.max() < TOL      # the contiguous rollout is the reference's
    # n > 1 prefills a prompt once and fans its cache out: the state's rows too
    fanned = generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                      SamplingParams(n=2, max_tokens=8, greedy=True),
                      eos_token_id=EOS, pad_token_id=PAD)
    assert np.array_equal(np.asarray(fanned), np.repeat(np.asarray(out), 2, 0))
    for bad, what in ((SamplingParams(max_tokens=4, spec_k=2), "rollback"),
                      (SamplingParams(max_tokens=4, page_size=4),
                       "no state that is not a page")):
        with pytest.raises(NotImplementedError, match=what) as e:
            generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                     bad, eos_token_id=EOS, pad_token_id=PAD)
        assert "lfm2_moe" in str(e.value)
    with pytest.raises(NotImplementedError, match="int8"):
        init_kv_cache(dataclasses.replace(CFG, kv_cache_quant="int8"), 1, 8)
    with pytest.raises(ValueError, match="state_rows"):
        init_paged_kv_cache(CFG, (8, 2), 4)
    from nanorlhf_tpu.trainer import RLTrainer

    with pytest.raises(NotImplementedError, match="training a model with conv"):
        RLTrainer(None, CFG, None, params, None, None)
