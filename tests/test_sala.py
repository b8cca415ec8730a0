"""MiniCPM-SALA (docs/SALA.md): lightning linear-attention layers that keep a
float32 MATRIX state and no pages, beside sparse-attention layers whose
queries choose the blocks of their pages they read by the scores of
compressed keys kept beside K and V, under the published muP scales, against
the plain float32 reference of benchmark/harness/reference_sala.py on seeded
weights. Tiny widths (compressed keys over 8 tokens every 4, blocks of 16,
the top 4, dense under 96 keys); logits, not tokens."""

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

from harness import reference_sala as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.core import sala  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from nanorlhf_tpu.ops import ssm as ops  # noqa: E402

with open(os.path.join(BENCH, "configs", "minicpm-sala-l8.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-sala.json")) as f:
    HF = {**json.load(f), "vocab_size": 128, "lightning_chunk": 4}
V = HF["vocab_size"]
CFG = ModelConfig.from_hf_config(HF)
TOL = 1e-3
EOS, PAD = 1, 0
CONTROLS = ("selection", "group_sum", "dense_len", "decay", "scale_depth",
            "scale_emb", "logit_scale", "lightning", "sparse", "gate",
            "o_norm")


def spread(p):
    """Everything the controls need to matter: every norm weight away from
    ones, the sparse layers' q/k norms wide enough that the compressed
    scores are not near-uniform, logits of a size that shows, and an EOS and
    a pad no row can emit."""
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 32))

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" not in name:
            return a
        a = jnp.exp(0.5 * jax.random.normal(next(keys), a.shape))
        return a * 3.0 if name.endswith(("['q_norm']", "['k_norm']")) \
            and "lightning" not in name else a

    p = jax.tree_util.tree_map_with_path(leaf, p)
    p["lm_head"] = (p["lm_head"] * 30).at[:, jnp.asarray([EOS, PAD])].set(0)
    return p


@pytest.fixture(scope="module")
def params():
    return spread(init_params(CFG, jax.random.PRNGKey(0), jnp.float32))


@pytest.fixture(scope="module")
def ids():
    """Three left-padded rows of 160 slots: 70 real tokens (dense at a
    prompt of 120 slots, which it holds 30 of... and past `dense_len` while
    it decodes), 117 and 160 (selecting from the prompt on)."""
    rng = np.random.default_rng(0)
    x = rng.integers(3, V, (3, 160)).astype(np.int32)
    x[0, :90] = PAD
    x[1, :43] = PAD
    return jnp.asarray(x)


TP = 120        # the prompt's slots of `ids`; the 40 after them are decoded


@pytest.fixture(scope="module")
def sound(params, ids):
    """The reference's logits of `ids`, the first TP slots ONE call and
    every later token a decode step of its own."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, HF, ids, PAD,
                                     decoded=ids.shape[1] - TP))


def far(a, b, real=None):
    d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    return float((d if real is None else d[real]).max())


def same(a, b):
    """Equal to float32 roundoff, at the size of the larger."""
    return far(a, b) < 2e-5 * max(1.0, float(np.abs(np.asarray(b)).max()))


# ------------------------------------------------------------ configuration

def test_from_hf_config_on_the_catalog_rows_keys():
    whole = ModelConfig.from_hf_config(
        {**FILE, **FILE["published"], "first_published_layer": 0})
    assert whole == ModelConfig.minicpm_sala()
    assert (whole.num_hidden_layers, whole.linear_layers,
            whole.sparse_layers, whole.state_layers) == (32, 24, 8, 24)
    assert (whole.conv_layers, whole.ssm_layers, whole.window_layers) == (0, 0, 0)
    assert whole.embed_scale == 12 and whole.lm_head_multiplier == 1 / 16
    assert abs(whole.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    cut = ModelConfig.from_hf_config(FILE)
    assert cut.layer_kinds == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert cut.attention_pattern == cut.layer_kinds
    assert cut.residual_scale == whole.residual_scale   # the PUBLISHED depth
    # the cut's decays are the published layers 10 to 15's
    np.testing.assert_array_equal(cut.lightning_log_decays(),
                                  whole.lightning_log_decays()[8:14])
    assert (cut.sparse_kernel_size, cut.sparse_kernel_stride,
            cut.sparse_block_size, cut.sparse_topk, cut.sparse_init_blocks,
            cut.sparse_window_size, cut.sparse_dense_len) == (
                32, 16, 64, 64, 1, 2048, 8192)
    assert CFG == ModelConfig.minicpm_sala_tiny(vocab_size=V)
    assert ModelConfig.qwen2_tiny().residual_scale == 1.0
    assert ModelConfig.falcon_h1_tiny().linear_layers == 0


def test_the_layers_parameters_are_the_catalogs():
    """253.8 M a sparse layer, 285.2 M a lightning one: the catalog's
    "about 273M" over 8 and 24 of them."""
    shapes = jax.eval_shape(lambda: init_params(
        ModelConfig.from_hf_config(FILE), jax.random.PRNGKey(0)))
    count = lambda tree: sum(int(np.prod(a.shape))                 # noqa: E731
                             for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    light = count(layers["lightning"]) // 6
    shared = sum(count(layers[name]) for name in (
        "gate_proj", "up_proj", "down_proj", "input_layernorm",
        "post_attention_layernorm")) // 8
    sparse = (count(layers) - count(layers["lightning"]) - 8 * shared) // 2
    assert light + shared == 285_225_216
    assert sparse + shared == 253_763_840
    assert abs((8 * 253.76 + 24 * 285.23) / 32 - 273) < 5
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
        == 300_843_008


@pytest.mark.parametrize("change, what", [
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"lightning_use_rope": False}, "lightning_use_rope"),
    ({"qk_norm": False}, "qk_norm"),
    ({"use_output_gate": False}, "use_output_gate"),
    ({"attn_use_output_gate": False}, "attn_use_output_gate"),
    ({"use_output_norm": False}, "use_output_norm"),
    ({"attention_bias": True}, "attention_bias"),
    ({"lightning_nkv": 2}, "lightning_nkv"),
    ({"lightning_scale": "1"}, "lightning_scale"),
    ({"mixer_types": ["minicpm4", "mamba", "lightning-attn", "minicpm4"]},
     "mixer_types"),
    ({"mixer_types": ["minicpm4"]}, "mixer_types"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"sparse_config": {"kernel_size": 8, "kernel_stride": 8}},
     "sparse_config"),
])
def test_from_hf_config_raises_by_name_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=f"minicpm_sala: {what}"):
        ModelConfig.from_hf_config({**HF, **change})


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    sd = hf_state_dict_from_params(CFG, params)
    assert sd["model.layers.0.self_attn.o_gate.weight"].shape == (64, 64)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (32, 64)
    assert sd["model.layers.1.self_attn.z_proj.weight"].shape == (64, 64)
    assert sd["model.layers.2.self_attn.o_norm.weight"].shape == (64,)
    assert "model.layers.1.self_attn.o_gate.weight" not in sd
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- the recurrence's two forms

def _lightning_inputs(B, T, H, hd, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (B, T, H, hd)) for key in keys[:3])
    A = jnp.asarray(-2.0 ** (-8.0 * (np.arange(H) + 1) / H) * 0.7, jnp.float32)
    before = jax.random.normal(keys[3], (B, H, hd, hd))
    return q, k, v, A, before


@pytest.mark.parametrize("T, chunk", [(11, 4), (8, 4), (3, 8), (20, 16)])
def test_the_chunked_scan_carries_a_constant_decay_at_128(T, chunk):
    """`ssd_scan` with `d_t = 1`, a constant `A = log lam`, one group a head
    and N = P = 128 (the lightning layer's mapping) against the token scan,
    a pad (`d_t = 0`) among the tokens."""
    q, k, v, A, before = _lightning_inputs(2, T, 2, 128)
    dt = jnp.ones((2, T, 2)).at[0, T // 2].set(0)
    with jax.default_matmul_precision("highest"):
        y, S = ops.ssd_scan(v, dt, A, k, q, before, chunk)
        y_want, S_want = ops.ssm_token_scan(v, dt, A, k, q, before)
    assert far(y, y_want) < 2e-3 * float(jnp.abs(y_want).max())
    assert far(S, S_want) < 2e-3 * float(jnp.abs(S_want).max())


@pytest.mark.parametrize("live, fresh", [
    (None, None), ([True, False, True], None),
    ([True, True, False], [False, True, False])])
def test_the_in_place_update_carries_a_constant_decay_at_128(live, fresh):
    """`ssm_update_in_place` (interpreted) at H = G = 4, N = P = 128 with
    `d_t = 1` against `ssm_update`; a row not live keeps its state bit for
    bit."""
    B, H, hd = 3, 4, 128
    q, k, v, A, _ = _lightning_inputs(B, 1, H, hd, seed=1)
    stack = jax.random.normal(jax.random.PRNGKey(9), (2, B + 1, H, hd, hd))
    live_ = None if live is None else jnp.asarray(live)
    fresh_ = None if fresh is None else jnp.asarray(fresh)
    dt = jnp.ones((B, H))
    y, out = ops.ssm_update_in_place(stack, 1, 1, live_, fresh_, v[:, 0], dt,
                                     A, k[:, 0], q[:, 0])
    before = stack[1, 1:]
    if fresh is not None:
        before = jnp.where(fresh_[:, None, None, None], 0, before)
    dt_ = dt if live is None else jnp.where(live_[:, None], dt, 0)
    y_want, S_want = ops.ssm_update(v[:, 0], dt_, A, k[:, 0], q[:, 0], before)
    rows = np.flatnonzero(live if live is not None else [True] * B)
    assert far(np.asarray(y)[rows], np.asarray(y_want)[rows]) < 1e-3
    assert far(np.asarray(out[1, 1:])[rows], np.asarray(S_want)[rows]) < 1e-4
    for r in set(range(B)) - set(rows.tolist()):
        np.testing.assert_array_equal(np.asarray(out[1, 1 + r]),
                                      np.asarray(stack[1, 1 + r]))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(stack[0]))


# --------------------------------------------------------------- the model

def test_uncached_forward_is_the_reference(params, ids, sound):
    """Both branches in one batch: a row of 30 real tokens among the
    prompt's slots and 70 in all (dense as a prompt, selecting once it holds
    96 keys), and two that select from the prompt on."""
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, PAD,
                                    response_context_length=TP)
    real = np.asarray(ids != PAD)[:, TP - 1:-1]
    assert far(got, sound[:, TP - 1:-1], real) < TOL
    # a row as ONE call selects by its whole length
    with jax.default_matmul_precision("highest"):
        one = padded_forward_logits(params, CFG, ids, PAD)
        want = ref.logits(params, HF, ids, PAD)
    assert far(one, want, np.asarray(ids != PAD)) < TOL


@pytest.mark.parametrize("left_out", CONTROLS)
def test_every_mechanism_and_scale_is_applied(params, ids, sound, left_out):
    """The reference without one of them is another model: the system is
    near the sound one and far from it."""
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.logits(params, HF, ids, PAD,
                                      decoded=ids.shape[1] - TP,
                                      without=(left_out,)))
    real = np.asarray(ids != PAD)
    assert far(other, sound, real) > 0.02, left_out


def test_the_selection_is_the_references(params, ids):
    """In float32 the system's `select_blocks` over `compress_at_hand`'s
    keys chooses the SAME blocks as the reference's selection, for every
    query of the longest row, and not the newest ones."""
    row = ids[2:3]
    last = 60
    q, k, t, chosen, score = ref.first_layer_selection(params, HF, row, PAD,
                                                       last)
    kc = sala.compress_at_hand(CFG, k[None], jnp.zeros((1,), jnp.int32))
    idx, ok = sala.select_blocks(CFG, q[None], kc, t[None])
    NB = chosen.shape[-1]
    got = np.zeros(chosen.shape, bool)
    for g in range(got.shape[0]):
        for i in range(last):
            got[g, i, np.asarray(idx)[0, g, i][np.asarray(ok)[0, g, i]]] = True
    np.testing.assert_array_equal(got[..., :NB], np.asarray(chosen))
    assert got.sum(-1).max() == CFG.sparse_topk
    newest = np.arange(NB)[None, :] > (np.asarray(t) // 16)[:, None] - 4
    assert (got[0] & ~newest).any()     # (a free block lies further back)


def _block_scores(case, NB, Tq=8, H=32.0):
    """`[1, 2, Tq, NB]` scores of the kinds `select_blocks` ranks: -1 (not
    seen), `[0, H]` (free) and `2 H` (forced)."""
    rng = np.random.default_rng(NB)
    x = rng.random((1, 2, Tq, NB)).astype(np.float32) * 3
    if case == "eighths":           # heavy ties, at the threshold too
        x = np.round(x * 8) / 8
    elif case == "forced_run":      # more forced blocks than k
        x[..., 5:5 + min(100, NB - 5)] = 2 * H
    elif case == "few":             # fewer candidates than k
        x[..., 20:] = -1.0
    elif case == "zeros":           # every free score 0.0
        x[:] = 0.0
        x[..., :3] = 2 * H
        x[..., NB // 2:] = -1.0
    elif case == "pad":             # a padded query: everything -1
        x[:] = -1.0
    return jnp.asarray(x)


@pytest.mark.parametrize("NB", [1040, 40])
@pytest.mark.parametrize("case", ["random", "eighths", "forced_run", "few",
                                  "zeros", "pad"])
def test_top_blocks_is_top_k_bit_for_bit(case, NB):
    """ISSUE 58: `sala.top_blocks` (a threshold search, a placement and a
    sort of width k) gives `jax.lax.top_k`'s values AND indices bit for bit
    at the cell's 1,040 blocks and at fewer blocks than `sparse_topk`."""
    x, k = _block_scores(case, NB), min(64, NB)
    vals, idx = jax.jit(sala.top_blocks, static_argnums=1)(x, k)
    want, at = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(at))
    np.testing.assert_array_equal(np.asarray(vals).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    assert idx.dtype == jnp.int32 and vals.dtype == jnp.float32
    if case == "few":
        assert (np.asarray(vals)[..., :20] >= 0).all()
        assert (np.asarray(vals)[..., 20:] == -1).all()


@pytest.mark.parametrize("Tq, takes", [(2, "top_k"), (16, "top_blocks")])
def test_select_blocks_picks_by_the_queries_it_ranks(params, ids, monkeypatch,
                                                     Tq, takes):
    """The shape rule (`sala._PICK_QUERIES`, from tools/bench_block_pick.py's
    timings on the chip): below it `select_blocks` ranks with `lax.top_k`,
    from it on with `top_blocks`; either side gives what the other would."""
    q, k, t, _, _ = ref.first_layer_selection(params, HF, ids[2:3], PAD, Tq)
    kc = sala.compress_at_hand(CFG, k[None], jnp.zeros((1,), jnp.int32))
    assert (kc.shape[1] * Tq >= sala._PICK_QUERIES) == (takes == "top_blocks")
    ran = []

    def noting(name, pick):
        return lambda x, k: ran.append(name) or pick(x, k)

    monkeypatch.setattr(sala, "top_blocks", noting("top_blocks", sala.top_blocks))
    monkeypatch.setattr(jax.lax, "top_k", noting("top_k", jax.lax.top_k))
    got = sala.select_blocks(CFG, q[None], kc, t[None])
    assert ran == [takes]
    monkeypatch.setattr(sala, "_PICK_QUERIES",
                        0 if takes == "top_k" else 1 << 30)
    other = sala.select_blocks(CFG, q[None], kc, t[None])
    assert ran[1:] == ["top_k" if takes == "top_blocks" else "top_blocks"]
    for a, b in zip(got, other):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(got[1]).any()


def test_contiguous_prefill_and_decode_are_the_reference(params, ids, sound):
    B, T_max = ids.shape
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        lg, caches = prefill(params, CFG, ids[:, :TP], mask[:, :TP],
                             init_kv_cache(CFG, B, T_max, jnp.float32))
        worst = far(lg, sound[:, TP - 1])
        km = jnp.zeros((B, T_max), bool).at[:, :TP].set(mask[:, :TP])
        plen = mask[:, :TP].sum(1)
        step = jax.jit(lambda tok, pos, at, km, c: decode_step(
            params, CFG, tok, pos, at, km, c))
        for t in range(TP, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - TP), t, km, caches)
            worst = max(worst, far(lg, sound[:, t]))
    assert worst < TOL
    k, v, kc = caches[0]
    assert kc.shape == (2, B, 2, T_max // 4, 16)
    (S,) = caches[2]
    assert S.shape == (2, B, 4, 16, 16) and S.dtype == jnp.float32


def test_the_state_is_float32_whatever_the_cache():
    caches = init_kv_cache(CFG, 3, 16, jnp.bfloat16)
    assert [a.dtype for a in caches[0]] == [jnp.bfloat16] * 3
    assert caches[2][0].dtype == jnp.float32
    paged = init_paged_kv_cache(CFG, (6, 1), 8, jnp.bfloat16, state_rows=3)
    assert paged[0][2].shape == (2, 6, 2 * 2, 16)   # 2 compressed keys a page
                                                    # a head, on one axis
    assert paged[2][0].shape == (2, 3, 4, 16, 16)
    with pytest.raises(ValueError, match="sparse_kernel_stride"):
        init_paged_kv_cache(CFG, (6, 1), 6, jnp.bfloat16, state_rows=3)


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_paged_prefill_and_decode_are_the_reference(params, ids, sound, impl):
    """The paged cache without a session; under `"pallas"` a decode step's
    read is ops/sparse_attention.py's kernel (interpreted) over the work
    list the selection makes. The cache's compressed keys are the
    reference's keys' means, the ones decode steps completed among them."""
    cfg = dataclasses.replace(CFG, attention_impl=impl)
    B, P, T_max = ids.shape[0], 8, ids.shape[1]
    nb = T_max // P
    mask = ids != PAD
    tabs = (jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb),
            jnp.zeros((B, 1), jnp.int32),
            jnp.arange(B, dtype=jnp.int32)[:, None])
    kw = dict(page_table=tabs, page_size=P)
    with jax.default_matmul_precision("highest"):
        caches = init_paged_kv_cache(cfg, (B * nb, 1), P, jnp.float32,
                                     state_rows=B)
        lg, caches = prefill(params, cfg, ids[:, :TP], mask[:, :TP], caches,
                             logical_len=T_max, **kw)
        worst = far(lg, sound[:, TP - 1])
        km = jnp.zeros((B, T_max), bool).at[:, :TP].set(mask[:, :TP])
        plen = mask[:, :TP].sum(1)
        step = jax.jit(lambda tok, pos, at, km, c: decode_step(
            params, cfg, tok, pos, at, km, c, **kw))
        for t in range(TP, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - TP),
                              jnp.full((B,), t, jnp.int32), km, caches)
            worst = max(worst, far(lg, sound[:, t]))
        assert worst < TOL
        # the longest row's compressed keys of the first layer, as cached
        _, k, _, _, _ = ref.first_layer_selection(params, HF, ids[2:3], PAD, 1)
        want = sala.compress_at_hand(CFG, k[None], jnp.zeros((1,), jnp.int32))
        view = M.KindView(mask=None, table=tabs[0][2:3], page_size=P,
                          span=(jnp.zeros((1,), jnp.int32), None))
        got = sala.compressed_keys(cfg, caches[0][2], 0, view)
    whole = (T_max - 8) // 4 + 1        # the windows that have ended
    assert far(got[0, :, :whole], want[0, :, :whole]) < 1e-5


def _pieces(params, row, cuts, T_max=176, bucket=0, first=0, paged=0):
    """One row's tokens through `decode_verify` in pieces cut at `cuts`,
    from slot `first` on (`bucket` pad tokens after each piece, marked not
    valid; `paged`: the page size, 0 the contiguous cache): (the last real
    token's logits, the cache)."""
    kw = {}
    if paged:
        nb = T_max // paged
        caches = init_paged_kv_cache(CFG, (nb, 1), paged, jnp.float32,
                                     state_rows=1)
        kw = dict(page_table=(jnp.arange(nb, dtype=jnp.int32)[None],
                              jnp.zeros((1, 1), jnp.int32),
                              jnp.zeros((1, 1), jnp.int32)), page_size=paged)
    else:
        caches = init_kv_cache(CFG, 1, T_max, jnp.float32)
    km = jnp.zeros((1, T_max), bool)
    logits = None
    for lo, hi in zip((0,) + cuts, cuts + (len(row),)):
        n = hi - lo
        toks = jnp.asarray(np.concatenate([row[lo:hi], np.full(bucket, 7)]),
                           jnp.int32)[None]
        pos = lo + jnp.arange(n + bucket)[None]
        logits, caches = decode_verify(
            params, CFG, toks, pos, jnp.asarray([first + lo]), km, caches,
            token_valid=jnp.arange(n + bucket)[None] < n,
            call_keys=jnp.asarray([len(row)]), **kw)
        km = km.at[0, first + lo:first + hi].set(True)
        logits = logits[0, n - 1]
    return logits, caches


@pytest.mark.parametrize("cuts, bucket, first, paged", [
    ((37,), 0, 0, 0), ((16, 70, 101), 0, 5, 0), ((50,), 3, 0, 8),
    ((7, 29, 30, 99), 2, 11, 8)])
def test_a_prompt_in_pieces_is_the_prompt_whole(params, cuts, bucket, first,
                                                paged):
    """A prompt past `dense_len` in pieces that are no multiple of the
    scan's chunk (4) and end inside a compressed key's window (8) and
    inside a block (16), from a slot that is no multiple of either, with a
    bucket's pads after each: every piece selects (the CALL holds 131
    keys), the state and the compressed keys are handed over."""
    row = np.random.default_rng(len(cuts)).integers(3, V, 131)
    with jax.default_matmul_precision("highest"):
        whole, one = _pieces(params, row, (), first=first, paged=paged)
        got, many = _pieces(params, row, cuts, bucket=bucket, first=first,
                            paged=paged)
        want = np.asarray(ref.logits(params, HF, jnp.asarray(row[None]), PAD))
    assert far(whole, want[0, -1]) < TOL and far(got, want[0, -1]) < TOL
    assert same(many[2][0], one[2][0])
    ended = (first + 131) // 4 - 2      # compressed slots no pad has reached
    lead = (slice(None), slice(None), slice(None), slice(0, ended)) \
        if not paged else None
    if paged:
        a, b = (c[0][2].reshape(2, -1, 2, 2, 16).transpose(
            0, 2, 1, 3, 4).reshape(2, 2, -1, 16) for c in (many, one))
        assert same(a[:, :, :ended], b[:, :, :ended])
    else:
        assert same(many[0][2][lead], one[0][2][lead])


def test_a_prompt_under_dense_len_in_pieces_is_read_dense(params):
    """The call's keys decide, not the piece's: 80 tokens in pieces are the
    dense prompt, whatever a piece holds."""
    row = np.random.default_rng(7).integers(3, V, 80)
    with jax.default_matmul_precision("highest"):
        got, _ = _pieces(params, row, (30, 70))
        want = np.asarray(ref.logits(params, HF, jnp.asarray(row[None]), PAD))
        other = np.asarray(ref.logits(params, HF, jnp.asarray(row[None]), PAD,
                                      without=("dense_len",)))
    assert far(got, want[0, -1]) < TOL < far(got, other[0, -1])


def test_left_pads_and_rows_nobody_listens_to_leave_the_cache(params, ids):
    """A left-padded prompt leaves the state of the same prompt unpadded; a
    decode step leaves the state and the compressed keys of a row that is
    not `live` bit for bit and moves the live rows'."""
    B, T_max = ids.shape
    mask = ids != PAD
    with jax.default_matmul_precision("highest"):
        _, caches = prefill(params, CFG, ids[:, :TP], mask[:, :TP],
                            init_kv_cache(CFG, B, T_max, jnp.float32))
        bare = ids[1:2, 43:TP]          # row 1 without its 43 pads
        _, alone = prefill(params, CFG, bare, jnp.ones_like(bare, bool),
                           init_kv_cache(CFG, 1, T_max, jnp.float32))
        assert same(caches[2][0][:, 1], alone[2][0][:, 0])
        # its compressed keys are the same keys, 43 slots on
        n = (TP - 43 - 8) // 4 + 1
        at = 43 // 4
        assert same(caches[0][2][:, 1, :, at:at + n],
                    alone[0][2][:, 0, :, :n])
        # three steps, so that a live row completes a compressed key
        live = jnp.asarray([True, False, True])
        after = caches
        for t in range(TP, TP + 4):
            km = jnp.zeros((B, T_max), bool).at[:, :t + 1].set(True)
            km = km & (jnp.arange(T_max)[None] >= jnp.argmax(mask, 1)[:, None])
            _, after = decode_step(params, CFG, ids[:, t],
                                   mask[:, :TP].sum(1) + t - TP, t, km, after,
                                   live=live)
    # (the contiguous K and V take every row's token, behind the key mask;
    # the paged write drops a row that is not live: tests/test_paged_cache.py)
    for before, now in zip(caches[0][2:] + caches[2], after[0][2:] + after[2]):
        np.testing.assert_array_equal(np.asarray(before[:, 1]),
                                      np.asarray(now[:, 1]))
        assert far(before[:, 0], now[:, 0]) > 1e-3


# ------------------------------------------------------- the decode kernel

def test_the_kernel_reads_the_plans_runs(params):
    """ops/sparse_attention.py at heads of 128 and pages of 16 (interpreted):
    the work list of a selecting row, a dense row, a row not live and a
    released row, against the plan's read in plain jnp; a selecting pair's
    items cover its chosen blocks and nothing else."""
    from nanorlhf_tpu.ops import sparse_attention as sa

    cfg = dataclasses.replace(CFG, head_dim=128, sparse_topk=4)
    B, KV, G, hd, P, nb = 4, 2, 2, 128, 16, 12
    N = B * nb
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (B, KV * G, hd), jnp.float32)
    k_pool, v_pool = (jax.random.normal(key, (2, N, KV, P, hd), jnp.float32)
                      for key in keys[1:])
    table = jnp.arange(N, dtype=jnp.int32).reshape(B, nb)
    table = table.at[3].set(N)                      # a released row
    start = jnp.asarray([5, 40, 3, 0], jnp.int32)
    filled = jnp.asarray([5 + 150, 40 + 60, 3 + 170, 100], jnp.int32)
    selects = jnp.asarray([True, False, True, True])
    live = jnp.asarray([True, True, False, True])
    idx = jnp.asarray([[[0, 9, 8, 3], [0, 9, 8, 5]]] * B, jnp.int32)
    ok = jnp.ones((B, KV, 4), bool)
    plan = sa.sparse_decode_plan(cfg, idx, ok, start, filled, selects, live,
                                 table, page_size=P, num_pages=N)
    off = np.asarray(plan.pair_off)
    assert (off[3:5] == off[2]).all() or off[4] == off[6]   # (dead pairs: none)
    assert off[-1] == off[4]                        # rows 2 and 3: no items
    # row 0, head 0: block 3 alone, the local run [start + 128, filled) and
    # block 0; blocks 8 and 9 lie in the window and are no items of their own
    lo, hi = (np.asarray(a)[off[0]:off[1]] for a in (plan.item_lo, plan.item_hi))
    assert sorted(zip(lo.tolist(), hi.tolist()))[:2] == [(5, 21), (53, 69)]
    assert (133, 155) in set(zip(lo.tolist(), hi.tolist()))
    got = sa.sparse_paged_decode_attention(q, k_pool, v_pool, 1, plan,
                                           interpret=True)
    want = sa.reference_sparse_decode(q, k_pool, v_pool, 1, plan, KV)
    assert far(got, want) < 1e-4
    assert float(jnp.abs(got[2:]).max()) == 0.0


def test_a_dense_rows_sparse_read_is_the_dense_kernels_bit_for_bit():
    """Rows that do not select read `[start, filled)` in items of four pages
    from `start // P`, as `ops/decode_attention.paged_decode_attention`
    does, through the same `_paged_item_fold` (a KV head at a time here, all
    at once there): the same bits, before and after the dense kernel's loop
    took four items a step (ISSUE 61 left this kernel and the fold as they
    were); a one-page item alone, whole items and the short ones after them,
    a row not live and a released row."""
    from nanorlhf_tpu.ops import decode_attention as dec
    from nanorlhf_tpu.ops import sparse_attention as sa

    cfg = dataclasses.replace(CFG, head_dim=128, sparse_topk=4)
    B, KV, G, hd, P, nb = 5, 2, 2, 128, 16, 12
    N = B * nb
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, KV * G, hd), jnp.float32)
    k_pool, v_pool = (jax.random.normal(key, (2, N, KV, P, hd), jnp.float32)
                      for key in keys[1:])
    table = jnp.arange(N, dtype=jnp.int32).reshape(B, nb).at[4].set(N)
    start = jnp.asarray([5, 40, 3, 0, 0], jnp.int32)
    filled = jnp.asarray([5 + 9, 40 + 60, 3 + 90, 100, 50], jnp.int32)
    live = jnp.asarray([True, True, True, False, True])
    idx = jnp.zeros((B, KV, 4), jnp.int32)
    plan = sa.sparse_decode_plan(
        cfg, idx, jnp.ones((B, KV, 4), bool), start, filled,
        jnp.zeros((B,), bool), live, table, page_size=P, num_pages=N)
    # pages of 16: one page; five, a whole item and one page; six
    assert np.diff(np.asarray(plan.pair_off)).tolist() == [
        1, 1, 2, 2, 2, 2, 0, 0, 0, 0]
    sparse = sa.sparse_paged_decode_attention(q, k_pool, v_pool, 1, plan,
                                              interpret=True)
    assert dec.paged_pages_per_item(k_pool) == sa._ITEM_PAGES
    assert dec._paged_items_per_step(k_pool, sa._ITEM_PAGES) == 4
    dense = dec.paged_decode_attention(
        q, k_pool, v_pool, jnp.int32(1), dec.paged_decode_plan(
            table, start, filled, page_size=P, num_pages=N,
            pages_per_item=sa._ITEM_PAGES, live=live), interpret=True)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    assert float(jnp.abs(sparse[:3]).min()) > 0 and not sparse[3:].any()


# ------------------------------------------- the selection's list of rows

def _all_rows_selection(config, q, kc_stack, layer, view, t, need):
    """What a step's selection was before it had a list of rows (ISSUE 54):
    every resident row's compressed keys gathered, scored and ranked."""
    kc = sala.compressed_keys(config, kc_stack, layer, view)
    return sala.select_blocks(config, q, kc, t)


@pytest.mark.parametrize("selecting, dead, released", [
    ((), (), ()),                       # no row selects: the loop takes no trip
    ((3,), (), ()),
    ((1, 6), (), ()),
    ((0, 2, 7), (), ()),
    ((0, 1, 2, 5), (), ()),
    ((0, 1, 2, 5, 7), (), ()),
    (tuple(range(8)), (), ()),          # every row
    ((2, 4), (4,), ()),                 # a selecting row that is not live
    ((2, 4, 5), (), (5,)),              # one whose pages are released
    ((7, 1, 3, 0), (1,), (7,)),         # both, the rows in no order
    ((6,), (0, 1, 2, 3, 4, 5, 7), ()),  # the one live row
])
def test_the_row_listed_selection_hands_the_plan_what_all_rows_did(
        selecting, dead, released):
    """`select_needed` against the selection over all resident rows, through
    `sparse_decode_plan`: the same pairs, and the same items (block, `lo`,
    `hi`) bit for bit, for rows at scattered pages, starts and lengths; a
    row the plan gives no chosen block's items reads zeros and False."""
    from nanorlhf_tpu.ops import sparse_attention as sa

    cfg = CFG
    B, P, nb = 8, 8, 24
    KV, H, hd = cfg.num_key_value_heads, cfg.num_attention_heads, cfg.head_dim
    N = B * nb
    rng = np.random.default_rng(len(selecting) + 10 * len(dead))
    kc_stack = jnp.asarray(rng.normal(size=(
        2, N, KV * P // cfg.sparse_kernel_stride, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, 1, hd)) * 3, jnp.float32)
    rows = np.arange(B)
    table = rng.permutation(N).reshape(B, nb).astype(np.int32)
    table[list(released)] = N
    start = rng.integers(0, 40, B).astype(np.int32)
    keys = np.where(np.isin(rows, selecting),
                    rng.integers(cfg.sparse_dense_len, nb * P - 40, B),
                    rng.integers(1, cfg.sparse_dense_len, B)).astype(np.int32)
    start, filled = jnp.asarray(start), jnp.asarray(start + keys)
    view = M.KindView(mask=None, table=jnp.asarray(table), page_size=P,
                      span=(start, jnp.asarray(keys)),
                      live=jnp.asarray(~np.isin(rows, dead)))
    t = (filled - 1 - start)[:, None]
    selects = jnp.asarray(keys >= cfg.sparse_dense_len)
    need = selects & sa.plan_rows(start, filled, view.live, view.table,
                                  page_size=P, num_pages=N)
    used = sorted(set(selecting) - set(dead) - set(released))
    assert np.flatnonzero(np.asarray(need)).tolist() == used
    idx0, ok0 = _all_rows_selection(cfg, q, kc_stack, 1, view, t, need)
    idx1, ok1 = jax.jit(lambda: sala.select_needed(
        cfg, q, kc_stack, 1, view, t, need))()
    plan0, plan1 = (sa.sparse_decode_plan(
        cfg, idx[:, :, 0], ok[:, :, 0], start, filled, selects, view.live,
        view.table, page_size=P, num_pages=N)
        for idx, ok in ((idx0, ok0), (idx1, ok1)))
    needed = np.asarray(need)
    for got, want in ((idx1, idx0), (ok1, ok0)):
        np.testing.assert_array_equal(np.asarray(got)[needed],
                                      np.asarray(want)[needed])
        assert not np.asarray(got)[~needed].any()
    n = int(plan0.pair_off[-1])
    np.testing.assert_array_equal(np.asarray(plan1.pair_off),
                                  np.asarray(plan0.pair_off))
    for a, b in zip(plan1[1:5], plan0[1:5]):    # (past n: no pair's items)
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n])


# ------------------------------------------------------------- the session

def session(params, cfg=CFG, **kw):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    return DecodeSession(
        params, cfg, **{**dict(
            rows=3, prompt_len=160, max_tokens=24, page_size=8,
            eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(1),
            per_row=True, prefix_cache=RadixCache(headroom=0.0), sync_every=4,
            prefill_chunk=16), **kw})


def serve(sess, lengths, budgets, seed):
    """A wave: the prompts admitted into rows 0.., driven to the end with
    look-ahead off (`step`), the rows released. (prompts, greedy answers)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, V, n) for n in lengths]
    Tp = sess.Tp
    for r, p in enumerate(prompts):
        toks, mask = np.zeros(Tp, np.int32), np.zeros(Tp, bool)
        toks[Tp - len(p):], mask[Tp - len(p):] = p, True
        sess.admit(r, toks, mask, r, budget=budgets[r], temperature=1.0,
                   top_p=1.0, greedy=True)
    for _ in range(120):
        done, _ = sess.step()
        if done.all() and not sess.has_pending():
            break
    out = np.asarray(sess.state[1])
    answers = [out[r, :n] for r, n in enumerate(budgets)]
    for r in range(len(prompts)):
        sess.release(r)
    return prompts, answers


WAVES = (((141, 6, 90), (24, 14, 20)),  # nine pieces past dense_len; a row
                                        # that crosses it as it decodes
         ((3, 127, 2), (12, 10, 16)))   # the same rows again


def gaps(params, prompts, answers, **flags):
    """How far under the reference's top each served token lies, a row."""
    out = []
    for p, a in zip(prompts, answers):
        seq = jnp.asarray(np.concatenate([p, a])[None])
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(params, HF, seq, PAD, last=len(a) + 1,
                                       mask=jnp.ones(seq.shape, bool),
                                       decoded=len(a), **flags))[0, :-1]
        out.append(lg.max(-1) - lg[np.arange(len(a)), a])
    return out


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_session_pieces_pads_reuse_and_chunks_follow_the_reference(params, impl):
    sess = session(params, dataclasses.replace(CFG, attention_impl=impl))
    assert sess.state_layers == 2 and sess.window_layers == 0
    assert sess.state_bytes_per_row == 2 * 4 * 16 * 16 * 4
    # K, V and a compressed key every 4 slots, two sparse layers
    assert sess.kv_bytes_per_token == 2 * (2 * 2 * 16 * 4) + 2 * 2 * 16 * 4 // 4
    for seed, (lengths, budgets) in enumerate(WAVES):
        prompts, answers = serve(sess, lengths, budgets, seed)
        for g in gaps(params, prompts, answers):
            assert g.max() < TOL
        if seed == 0:
            for wrong in ("selection", "dense_len", "decay", "lightning"):
                assert max(g.max() for g in gaps(
                    params, prompts, answers, without=(wrong,))) > 0.02, wrong
    assert sess.state_resets == 6
    assert sess.state_piece_carries == 8 + 5 + 7
    assert sess.chunked_admissions == 3 and sess.hit_tokens == 0
    # the 141-token row's 23 steps, the 127's 9 and the 90's from 96 keys on
    assert sess.sparse_rows == 23 + 9 + 14
    assert sess.sparse_slots_read == 64 * sess.sparse_rows
    assert sess.sparse_slots_held > 2 * sess.sparse_slots_read - 64 * 30
    # the selection ran over the selecting rows, a trip a row a sparse layer
    assert sess.select_rows_run == 2 * sess.sparse_rows
    assert sess.select_rows_resident == 2 * 3 * sess.iterations()
    assert sess.select_rows_run <= sess.select_rows_resident


def test_a_state_not_carried_leaves_the_reference(params, monkeypatch):
    cfg = dataclasses.replace(CFG, max_position_embeddings=1001)
    sound_ctx = M._conv_ctx
    monkeypatch.setattr(
        M, "_conv_ctx", lambda config, valid=None, fresh=None: sound_ctx(
            config, valid,
            None if fresh is None else lambda: jnp.ones_like(fresh())))
    prompts, answers = serve(session(params, cfg), *WAVES[0], 0)
    assert max(g.max() for g in gaps(params, prompts, answers)) > 0.02


def test_a_chunk_over_the_listed_rows_is_the_chunk_over_all_rows(
        params, monkeypatch):
    """The session under the kernel (interpreted), its selection over the
    rows that select against the selection over every resident row: the same
    tokens, and the same compressed keys left in the cache."""
    cfg = dataclasses.replace(CFG, attention_impl="pallas")
    traced = []

    def every_row(*args):
        traced.append(1)
        return _all_rows_selection(*args)

    def wave(cfg):
        sess = session(params, cfg)
        _, answers = serve(sess, *WAVES[0], 0)
        return answers, np.asarray(sess.state[3][0][2]), sess

    answers, keys, sess = wave(cfg)
    monkeypatch.setattr(sala, "select_needed", every_row)
    # (another static argument, so the chunk is traced again)
    before, keys_before, _ = wave(dataclasses.replace(
        cfg, max_position_embeddings=1002))
    assert traced
    for a, b in zip(answers, before):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(keys, keys_before)
    assert sess.select_rows_run == 2 * sess.sparse_rows > 0


def test_engine_serves_counts_and_takes_no_prefix_hit(params):
    from nanorlhf_tpu.serving.engine import ServingEngine

    with ServingEngine(params, CFG, eos_token_id=EOS, pad_token_id=PAD,
                       page_size=8, prompt_len=160, max_new_tokens=16, rows=2,
                       headroom=0.0, sync_every=4, prefill_chunk=16) as engine:
        rng = np.random.default_rng(3)
        same = rng.integers(3, V, 110)
        reqs = [engine.submit(p, greedy=True, max_tokens=8)[0]
                for p in (same, rng.integers(3, V, 5), same)]
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    assert [len(s) for s in streams] == [8, 8, 8]
    assert streams[0] == streams[2]             # the same prompt, served cold
    assert gaps(params, [same], [np.asarray(streams[0])])[0].max() < TOL
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/state_layers"] == 2 and m["serving/window_layers"] == 0
    assert m["serving/state_bytes_per_row"] == 2 * 4 * 16 * 16 * 4
    assert m["serving/state_resets"] == 3
    assert m["serving/state_piece_carries"] == 2 * 6    # 110 tokens: 6 x 16, 14
    assert m["serving/sparse_rows"] == 2 * 7
    assert m["serving/sparse_slots_read"] == 64 * 14
    assert m["serving/sparse_slots_held"] == 2 * sum(range(111, 118))
    assert m["serving/select_rows_run"] == 2 * 2 * 7
    assert m["serving/select_rows_resident"] == 2 * 2 * m["serving/decode_steps"]


def test_a_model_without_sparse_layers_counts_no_selection():
    from nanorlhf_tpu.serving.engine import ServingEngine

    config = ModelConfig.qwen2_tiny(vocab_size=V)
    dense = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    with ServingEngine(dense, config, eos_token_id=V + 5, pad_token_id=PAD,
                       page_size=4, prompt_len=12, max_new_tokens=8, rows=2,
                       sync_every=2) as engine:
        req, _ = engine.submit(np.arange(3, 12), greedy=True, max_tokens=5)
        assert len(list(engine.stream(req))) == 5
        m = engine.metrics()
    assert m["serving/decode_steps"] > 0
    assert m["serving/select_rows_run"] == m["serving/select_rows_resident"] == 0
    assert m["serving/sparse_rows"] == 0


# ---------------------------------------------------------------- refusals

def test_a_radix_hit_raises(params):
    from nanorlhf_tpu.serving.radix import prompt_key

    sess = session(params)
    toks, mask = np.zeros(160, np.int32), np.zeros(160, bool)
    toks[132:], mask[132:] = np.arange(3, 31), True
    sess.admit(0, toks, mask, 0, budget=4, temperature=1.0, top_p=1.0,
               greedy=True)
    sess._radix.insert(prompt_key(toks, mask), sess.table_np[0], 160)
    with pytest.raises(NotImplementedError,
                       match="snapshot of the recurrent state") as e:
        sess.admit(1, toks, mask, 1, budget=4, temperature=1.0, top_p=1.0,
                   greedy=True)
    assert "minicpm_sala" in str(e.value)


@pytest.mark.parametrize("kw, cfg_kw, what", [
    ({"spec_k": 2, "greedy": True}, {}, "rolled back"),
    ({"per_row": False}, {}, "rollout scheduler"),
    ({}, {"kv_cache_quant": "int8"}, "int8"),
    ({}, {"spmd_mesh": "a mesh"}, "mesh"),
])
def test_session_raises_by_name_on_what_is_not_built(params, kw, cfg_kw, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        session(params, dataclasses.replace(CFG, **cfg_kw), **kw)
    assert "linear-attention layers (minicpm_sala)" in str(e.value)


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
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            run(SamplingParams(max_tokens=4, greedy=True, spec_k=2))
    elif what == "paged":
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            run(SamplingParams(max_tokens=4, greedy=True, page_size=4))
    elif what == "int8":
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            init_kv_cache(dataclasses.replace(CFG, kv_cache_quant="int8"),
                          2, 16)
    elif what == "state_rows":
        with pytest.raises(ValueError, match="linear-attention layers"):
            init_paged_kv_cache(CFG, (8, 1), 8)
    elif what == "trainer":
        from nanorlhf_tpu.trainer.trainer import RLTrainer

        trainer = object.__new__(RLTrainer)
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            RLTrainer.__init__(trainer, type("C", (), {})(), CFG, None, params,
                               None, None)
    else:
        from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            init_lora_params(CFG, LoraConfig(r=2), jax.random.PRNGKey(0))
