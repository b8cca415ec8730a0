"""SmallThinker (docs/SWA.md): window layers with rotary embedding beside
global layers without it, a router on the pre-attention state, ReLU-gated
experts, and the page pool of two kinds, against the plain float32 reference
of benchmark/harness/reference_smallthinker.py on seeded weights. Tiny
widths, a window of 8 so that every path crosses it; logits, not tokens."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
sys.path.insert(0, BENCH)

from harness import reference_smallthinker as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from nanorlhf_tpu.sampler.paged.pages import RingPages, ring_blocks  # noqa: E402

PUBLISHED = {
    "model_type": "smallthinker", "head_dim": 128, "hidden_size": 2560,
    "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct",
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}
W, V = 8, 128
CFG = ModelConfig.smallthinker_tiny(vocab_size=V, window=W, layers=4)
HF = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
      "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 1.5e6,
      "moe_num_active_primary_experts": 2, "norm_topk_prob": True,
      "num_hidden_layers": 4, "sliding_window_size": W,
      "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
      "tie_word_embeddings": False}
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(0)
    x = rng.integers(3, V, (2, 40)).astype(np.int32)
    x[0, :8] = 0        # a left-padded row beside a full one (one whole
                        # chunk of pads: `paged_logits` walks both rows in step)
    return jnp.asarray(x)


@pytest.fixture(scope="module")
def reference_logits(params, ids):
    with jax.default_matmul_precision("highest"):
        return {name: np.asarray(ref.logits(params, HF, ids, 0, **flags))
                for name, flags in (("sound", {}), ("no_window", {"window": False}),
                                    ("rope_everywhere", {"nope": False}))}


def far(a, b, real):
    return float(np.abs(np.asarray(a) - np.asarray(b))[real].max())


# ------------------------------------------------------------------ config

def test_from_hf_config_on_the_published_keys():
    c = ModelConfig.from_hf_config(PUBLISHED)
    assert c == ModelConfig.smallthinker_21b()
    assert c.attention_pattern == ((False, False),) + ((True, True),) * 3
    assert (c.window_layers, c.num_experts, c.num_experts_per_tok) == (39, 64, 6)
    assert (c.expert_activation, c.router_input) == ("relu", "pre_attention")
    assert c.intermediate_size == 768 and c.sliding_window == 4096
    # every expert model's session dispatches live rows only; the token
    # block is the configuration's, and OLMoE's trainer cell sets none
    assert c.live_rows_dispatch and ModelConfig.olmoe_tiny().live_rows_dispatch
    assert not ModelConfig.qwen2_tiny().live_rows_dispatch
    assert c.expert_token_block == 4096
    assert ModelConfig.olmoe_tiny().expert_token_block == 0
    assert ModelConfig.qwen2_tiny().attention_pattern is None
    cut = dict(PUBLISHED, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
               sliding_window_layout=[0, 1, 1, 1] * 2)
    assert ModelConfig.from_hf_config(cut).window_layers == 6


@pytest.mark.parametrize("change, what", [
    ({"moe_primary_router_apply_softmax": False}, "sigmoid primary router"),
    ({"moe_num_secondary_experts": 8}, "secondary experts"),
    ({"moe_layer_layout": [0] + [1] * 51}, "dense layers"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention biases"),
    ({"sliding_window_layout": [1] * 52}, "window layers only"),
    ({"rope_layout": [0, 1]}, "entries"),
])
def test_from_hf_config_raises_on_what_is_not_built(change, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config(dict(PUBLISHED, **change))


@pytest.mark.parametrize("keys", [
    {"model_type": "qwen2", "use_sliding_window": True, "sliding_window": 4096},
    {"model_type": "qwen3", "layer_types": ["full_attention", "sliding_attention"],
     "use_sliding_window": False},
    {"model_type": "mistral", "sliding_window": 4096},
])
def test_a_window_on_another_family_raises(keys):
    base = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 2,
            "num_key_value_heads": 2}
    with pytest.raises(ValueError, match="sliding window"):
        ModelConfig.from_hf_config({**base, **keys})
    # published Qwen2.5 files carry the keys with the window OFF: still built
    ok = {**base, "model_type": "qwen2", "use_sliding_window": False,
          "sliding_window": 131072, "max_window_layers": 28}
    assert ModelConfig.from_hf_config(ok).attention_pattern is None


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )

    sd = hf_state_dict_from_params(CFG, params)
    assert "model.layers.2.block_sparse_moe.primary_router.weight" in sd
    assert "model.layers.3.block_sparse_moe.experts.7.gate.weight" in sd
    assert "model.layers.0.self_attn.o_proj.weight" in sd
    assert sd["model.layers.1.block_sparse_moe.experts.0.down.weight"].shape == (64, 32)
    assert not any(".mlp." in k or "bias" in k for k in sd)
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    jax.tree.map(np.testing.assert_array_equal, back, params)


# ------------------------------------------------- uncached and contiguous

def test_uncached_forward_is_the_reference(params, ids, reference_logits):
    real = np.asarray(ids != 0)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, 0)
    assert far(got, reference_logits["sound"], real) < TOL
    # the comparison can fail: a model without the window, or one that
    # rotates its global layers, is tenths of a nat away
    assert far(got, reference_logits["no_window"], real) > 0.1
    assert far(got, reference_logits["rope_everywhere"], real) > 0.1


def test_long_rows_go_in_query_blocks(params, ids, reference_logits, monkeypatch):
    from nanorlhf_tpu.core import model

    monkeypatch.setattr(model, "_PATTERN_SCORE_BYTES", 2 * 4 * 16 * 40 * 4)
    with jax.default_matmul_precision("highest"):
        got = padded_forward_logits(params, CFG, ids, 0)
    assert far(got, reference_logits["sound"], np.asarray(ids != 0)) < TOL


def test_contiguous_prefill_and_decode_are_the_reference(params, ids,
                                                         reference_logits):
    Tp, T_max = 24, 40
    mask = ids != 0
    caches = init_kv_cache(CFG, 2, T_max, jnp.float32)
    assert [c.shape[0] for group in caches for c in group] == [1, 1, 3, 3]
    want = reference_logits["sound"]
    with jax.default_matmul_precision("highest"):
        lg, caches = prefill(params, CFG, ids[:, :Tp], mask[:, :Tp], caches)
        worst = far(lg, want[:, Tp - 1], np.ones(2, bool))
        km = jnp.zeros((2, T_max), bool).at[:, :Tp].set(mask[:, :Tp])
        plen = mask[:, :Tp].sum(1)
        step = jax.jit(lambda t, pos, slot, km, c: decode_step(
            params, CFG, t, pos, slot, km, c))
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            lg, caches = step(ids[:, t], plen + (t - Tp), t, km, caches)
            worst = max(worst, far(lg, want[:, t], np.ones(2, bool)))
    assert worst < TOL


def test_generate_runs_on_the_contiguous_cache_and_raises_on_the_rest(params):
    from nanorlhf_tpu.sampler import SamplingParams, generate

    prompts = jnp.asarray([[0, 5, 6, 7], [9, 8, 7, 6]])
    out = generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                   SamplingParams(n=1, max_tokens=12, greedy=True),
                   eos_token_id=1, pad_token_id=0)
    seq = np.concatenate([np.asarray(prompts), np.asarray(out)], axis=1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, HF, jnp.asarray(seq), 0))
    gap = want[:, 3:-1].max(-1) - np.take_along_axis(
        want[:, 3:-1], np.asarray(out)[..., None], axis=-1)[..., 0]
    assert gap.max() < TOL      # the reference's own greedy continuation
    for bad in (SamplingParams(max_tokens=4, spec_k=2),
                SamplingParams(max_tokens=4, page_size=4)):
        with pytest.raises(NotImplementedError, match="window layers"):
            generate(params, CFG, prompts, prompts != 0, jax.random.PRNGKey(0),
                     bad, eos_token_id=1, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="int8"):
        init_kv_cache(dataclasses.replace(CFG, kv_cache_quant="int8"), 1, 8)
    with pytest.raises(NotImplementedError, match="two kinds"):
        init_paged_kv_cache(CFG, 8, 4)


def test_sequence_parallel_attention_raises(params, ids):
    from nanorlhf_tpu.core.model import _hidden_from_inputs

    with pytest.raises(NotImplementedError, match="sequence-parallel"):
        _hidden_from_inputs(params, CFG, ids, ids != 0, jnp.cumsum(ids != 0, 1),
                            1.0, False, attn_fn=lambda q, k, v: q)


# ----------------------------------------------------------- the paged path

def ring_tables(rows, nb, ring, firsts, order=None):
    """(global identity table, RingPages) for `rows` rows; `order` permutes
    the window pool's free list (which physical pages a row is given)."""
    pages = RingPages(rows * ring, rows, nb, ring)
    if order is not None:
        pages._free = [pages._free[i] for i in order]
    for r, first in enumerate(firsts):
        pages.claim(r, first, nb - 1)
    table = np.arange(rows * nb, dtype=np.int32).reshape(rows, nb)
    return jnp.asarray(table), pages


def paged_logits(params, ids, P, chunk, order=None, impl="auto"):
    """Chunked prefill (`decode_verify` over `chunk` tokens at a time) of the
    first 24 slots, then single-token steps, through a page pool of two
    kinds whose window ring is shorter than the row: [2, 40, V] logits of
    slots 23..39 (earlier slots: the chunks' own), and the ring."""
    cfg = dataclasses.replace(CFG, attention_impl=impl)
    T_max, Tp = 40, 24
    nb, ring = T_max // P, ring_blocks(W, P, chunk)
    assert ring < nb
    pad = np.asarray((ids == 0).sum(1))
    table, pages = ring_tables(2, nb, ring, pad // P, order)
    tabs = (table, jnp.asarray(pages.table))
    caches = init_paged_kv_cache(cfg, (2 * nb, 2 * ring), P, jnp.float32)
    mask = np.asarray(ids != 0)
    pos = np.cumsum(mask, 1) - 1
    out = {}
    # jitted: an eager call lowers its layer scans anew each time (a scan's
    # jaxpr is a new object a call) and every such executable stays mapped,
    # ~10,000 memory maps a run of this function; a test worker that had run
    # a suite's worth before it reached vm.max_map_count (65,530) here and
    # died in `deserialize_executable` (three whole runs of three, PR 41)
    verify, step = (jax.jit(
        functools.partial(f, page_table=tabs, page_size=P),
        static_argnums=1) for f in (decode_verify, decode_step))
    with jax.default_matmul_precision("highest"):
        for f in range(0, Tp, chunk):
            km = np.zeros((2, T_max), bool)
            km[:, :f] = mask[:, :f]
            lg, caches = verify(
                params, cfg, ids[:, f:f + chunk], jnp.asarray(pos[:, f:f + chunk]),
                jnp.full((2,), f, jnp.int32), jnp.asarray(km), caches)
            for i in range(chunk):
                out[f + i] = np.asarray(lg[:, i])
        km = jnp.zeros((2, T_max), bool).at[:, :Tp].set(ids[:, :Tp] != 0)
        for t in range(Tp, T_max):
            km = km.at[:, t].set(True)
            live = jnp.asarray([True, t < 32])      # then nobody hears row 1
            lg, caches = step(
                params, cfg, ids[:, t], jnp.asarray(pos[:, t]),
                jnp.full((2,), t, jnp.int32), km, caches, live=live)
            out[t] = np.where(np.asarray(live)[:, None], np.asarray(lg), np.nan)
    return np.stack([out[t] for t in range(T_max)], axis=1), pages


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_paged_chunks_and_steps_are_the_reference(params, ids, reference_logits,
                                                  impl):
    got, pages = paged_logits(params, ids, P=4, chunk=8, impl=impl)
    want = reference_logits["sound"]
    real = np.asarray(ids != 0) & ~np.isnan(got[..., 0])
    assert far(np.nan_to_num(got), want, real) < TOL
    assert far(np.nan_to_num(got), reference_logits["no_window"], real) > 0.1
    # the full row wrote 10 blocks into a ring of 6: pages reused, twice over
    assert pages.reused(1, 9) == 4 and pages.ring == 6


def test_physical_page_order_changes_nothing(params, ids):
    a, _ = paged_logits(params, ids, P=4, chunk=8)
    order = np.random.default_rng(5).permutation(12)
    b, pages = paged_logits(params, ids, P=4, chunk=8, order=order)
    np.testing.assert_array_equal(a, b)
    assert sorted(set(pages.table[1, pages.table[1] < 12])) != list(range(6, 12)) \
        or sorted(set(pages.table[0, pages.table[0] < 12])) != list(range(6))


def test_ring_pages_claim_release_and_reuse():
    pages = RingPages(num_pages=8, rows=2, n_blocks=10, ring=4)
    assert pages.claim(0, 2, 9) == 4 and pages.free_count == 4
    row = pages.table[0]
    assert list(row[:2]) == [8, 8]                     # sentinel before the row
    assert list(row[2:6]) == list(row[6:10])            # the ring, laid out twice
    assert pages.claim(1, 8, 9) == 2                    # a short row takes two
    assert pages.reused(0, 5) == 0 and pages.reused(0, 9) == 4
    with pytest.raises(RuntimeError, match="exhausted"):
        RingPages(2, 1, 10, 4).claim(0, 0, 9)
    pages.release(0)
    assert pages.free_count == 6 and (pages.table[0] == 8).all()
    assert ring_blocks(4096, 128, 1024) == 42


def test_ring_table_ends_at_the_rows_last_block():
    """Past a row's last block the table holds the sentinel, whether the
    ring wraps (row 0) or holds a page a block (row 1): a write there is
    dropped, never laid over the row's own pages."""
    pages = RingPages(num_pages=12, rows=2, n_blocks=10, ring=4)
    pages.claim(0, 1, 7)
    pages.claim(1, 3, 5)
    assert list(pages.table[0, 1:5]) == list(pages.table[0, 5:8])[:3] + \
        [pages.table[0, 4]]
    assert (pages.table[0, 8:] == 12).all() and pages.table[0, 0] == 12
    assert (pages.table[1, :3] == 12).all() and (pages.table[1, 6:] == 12).all()
    assert len(set(pages.table[1, 3:6])) == 3 and pages.free_count == 12 - 4 - 3


# ------------------------------------------------------------- the session

def session(params, shuffle=None, cfg=CFG, **kw):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    sess = DecodeSession(
        params, cfg, rows=3, prompt_len=48, max_tokens=24, page_size=4,
        eos_token_id=1, pad_token_id=0, key=jax.random.PRNGKey(1), per_row=True,
        prefix_cache=RadixCache(headroom=0.0), sync_every=4,
        **{"prefill_chunk": 8, **kw})
    if shuffle is not None:
        np.random.default_rng(shuffle).shuffle(sess._ring._free)
    return sess


def serve_two(params, shuffle=None, lengths=(41, 6), budgets=(24, 14), **kw):
    sess = session(params, shuffle, **kw)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, V, n) for n in lengths]
    for r, p in enumerate(prompts):
        toks, mask = np.zeros(48, np.int32), np.zeros(48, bool)
        toks[48 - len(p):], mask[48 - len(p):] = p, True
        sess.admit(r, toks, mask, r, budget=budgets[r], temperature=1.0,
                   top_p=1.0, greedy=True)
    for _ in range(60):
        done, _ = sess.step()
        if done.all() and not sess.has_pending():
            break
    out = np.asarray(sess.state[1])
    return prompts, [out[r, :n] for r, n in enumerate(budgets)], sess


def test_session_long_and_short_rows_follow_the_reference(params):
    prompts, answers, sess = serve_two(params)
    assert sess.chunked_admissions == 1 and sess.nbw == 6
    assert sess.window_pages_reused >= 2 * sess.nbw - 2      # the ring, twice over
    assert 0 < sess.window_slots_read < sess.global_slots_read
    assert sess.held_experts_hit > 0 and sess.hit_tokens == 0
    for p, a in zip(prompts, answers):
        seq = jnp.asarray(np.concatenate([p, a])[None])
        with jax.default_matmul_precision("highest"):
            lg = {k: np.asarray(ref.logits(params, HF, seq, 0, last=len(a) + 1,
                                           mask=jnp.ones(seq.shape, bool),
                                           **flags))[0, :-1]
                  for k, flags in (("sound", {}), ("no_window", {"window": False}))}
        gap = {k: v.max(-1) - v[np.arange(len(a)), a] for k, v in lg.items()}
        assert gap["sound"].max() < TOL
        assert gap["no_window"].max() > 0.1      # both rows are past the window
    sess.release(0)
    sess.release(1)
    assert sess._ring.free_count == sess.num_pages_window
    # which physical window pages the rows were given changes nothing
    _, again, _ = serve_two(params, shuffle=7)
    for a, b in zip(answers, again):
        np.testing.assert_array_equal(a, b)


def test_chunked_admission_through_the_flash_kernel_serves_the_walks_tokens(
        params):
    """ISSUE 37: a chunked admission's pieces and suffix forwards read the
    row's pages through `ops/paged_prefill_attention` under the decode
    read's rule (`"pallas"`: interpret mode here) and through XLA's walk
    otherwise; the greedy tokens are the same, for the long row (41 tokens
    in pieces of 8, past the window of 8, the ring wrapped) and for the
    short one (one suffix forward of a bucket of 8), and the session says
    which read it built."""
    _, walk, s_walk = serve_two(params)
    _, flash, s_flash = serve_two(
        params, cfg=dataclasses.replace(CFG, attention_impl="pallas"))
    for a, b in zip(walk, flash):
        np.testing.assert_array_equal(a, b)
    assert (s_walk.prefill_read_in_place, s_flash.prefill_read_in_place) == (0, 1)
    # 41 tokens from slot 7: five pieces of 8 and the closing forward
    assert s_walk.prefill_pieces == s_flash.prefill_pieces == 6


def test_a_suffix_bucket_past_a_small_budget_is_dropped(params):
    """An admission forward writes a power-of-two bucket of its suffix: 17
    real tokens go as 32, to slot 62, and a budget of 8 ends the row's ring
    at block 13 (7 pages where the ring may have 12). The pad tokens of
    blocks 14 and 15 must be dropped, as the global table drops what lies
    past a row's budget; wrapped onto the ring they overwrite the prompt's
    first keys in every window layer inside the same scatter (REVIEW, PR 34:
    600 tokens with 100 new at the cell's sizes)."""
    prompts, answers, sess = serve_two(params, lengths=(17, 9), budgets=(8, 3),
                                       prefill_chunk=32)
    assert sess.chunked_admissions == 0 and sess.nbw == 12
    table = sess._ring.table
    assert (table[0, 7:14] < sess.num_pages_window).all()
    assert len(set(table[0, 7:14])) == 7            # a page a block: no wrap
    assert (table[0, 14:] == sess.num_pages_window).all()
    for p, a in zip(prompts, answers):
        seq = jnp.asarray(np.concatenate([p, a])[None])
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(params, HF, seq, 0, last=len(a) + 1,
                                       mask=jnp.ones(seq.shape, bool)))[0, :-1]
        assert (lg.max(-1) - lg[np.arange(len(a)), a]).max() < TOL


def test_engine_serves_and_counts(params):
    from nanorlhf_tpu.serving.engine import ServingEngine

    with ServingEngine(params, CFG, eos_token_id=1, pad_token_id=0, page_size=4,
                       prompt_len=48, max_new_tokens=16, rows=2, headroom=0.0,
                       sync_every=4, prefill_chunk=8) as engine:
        rng = np.random.default_rng(3)
        reqs = [engine.submit(rng.integers(3, V, n), greedy=True, max_tokens=8)[0]
                for n in (30, 5, 30)]       # the third repeats a length: no hit
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    assert [len(s) for s in streams] == [8, 8, 8]
    assert m["serving/window_layers"] == 3
    assert m["serving/prefix_hit_tokens"] == 0
    assert m["serving/pool_pages_window"] == 2 * 6
    assert m["serving/kv_bytes_per_token_global"] == 1 * 2 * 2 * 16 * 4
    assert m["serving/kv_bytes_per_token_window"] == 3 * 2 * 2 * 16 * 4
    assert m["serving/window_pages_reused"] > 0
    assert 0 < m["serving/window_slots_read"] < m["serving/global_slots_read"]
    # the two prompts of 30 go in pieces of 8 (three and a closing forward
    # each), read by XLA's walk off the TPU
    assert m["serving/prefill_pieces"] == 2 * 4
    assert m["serving/prefill_read_in_place"] == 0


@pytest.mark.parametrize("kw, what", [
    ({"per_row": False}, "serving session only"),
    ({"spec_k": 2, "greedy": True}, "serving session only"),
    ({"prefix_cache": None}, "serving session only"),
])
def test_session_raises_on_what_is_not_built(params, kw, what):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    base = dict(rows=2, prompt_len=16, max_tokens=8, page_size=4, eos_token_id=1,
                pad_token_id=0, key=jax.random.PRNGKey(0), per_row=True,
                prefix_cache=RadixCache(headroom=0.0))
    with pytest.raises(NotImplementedError, match=what):
        DecodeSession(params, CFG, **{**base, **kw})


# ------------------------- the layer scan: by index with a cache, by xs without

def _two_periods(model):
    """The tiny configuration of a pattern model at TWO periods of its
    pattern, so that the layer scan has two trips."""
    if model == "smallthinker":
        return ModelConfig.smallthinker_tiny(vocab_size=V, window=W, layers=8)
    if model == "lfm2":     # two dense conv layers, then [a, c, c, c] x 2
        return ModelConfig.lfm2_tiny(vocab_size=V, layers=10)
    layout = (1,) + (1, 1, 1, 0) * 2    # a dense window layer, [w, w, w, g] x 2
    return dataclasses.replace(
        ModelConfig.trinity_tiny(vocab_size=V, window=W), num_hidden_layers=9,
        sliding_window_layout=layout, rope_layout=layout)


def _two_rows(cfg):
    """A left-padded row beside a full one for the paged walks below: `(ids
    [B, T_max], valid, positions, the kinds' tables, the pools' pages)` at
    B = 2, T_max = 16, pages of 4; a model without a pattern gets one table
    and one pool."""
    B, P, T_max = 2, 4, 16
    nb = T_max // P
    rng = np.random.default_rng(3)
    ids = rng.integers(3, V, (B, T_max)).astype(np.int32)
    ids[0, :5] = 0
    valid = ids != 0
    pos = jnp.asarray(np.cumsum(valid, 1) - 1)
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    if cfg.attention_pattern is None:
        return jnp.asarray(ids), valid, pos, table, B * nb
    tabs = (table, table) + (
        (jnp.arange(B, dtype=jnp.int32)[:, None],) if cfg.state_layers else ())
    return jnp.asarray(ids), valid, pos, tabs, (B * nb, B * nb)


@pytest.mark.parametrize("model", ["smallthinker", "lfm2", "trinity", "qwen2"])
def test_the_cached_attention_fences_its_projections_from_the_head_split(
        model, monkeypatch):
    """ISSUE 44. Where a layer took its kernels at a traced index of the
    whole stacks (a pattern model's cached forward) `_attention` puts one
    `optimization_barrier` over its projections' results, before the head
    split: one a layer of the period's (and of a dense stack's) attention
    layers in the paged decode step's lowered text, none in the uncached
    forward's. The fence moves no value: as jitted programs the prefill's
    and three decode steps' logits are BITWISE those of the same programs
    traced without it, and the prefill's are bitwise the uncached forward's
    where they were before (SmallThinker, Trinity; LFM2's differ in the last
    bit, 2.4e-7, with the fence and without). A model without a pattern
    never reaches the fence: its decode step lowers with
    `optimization_barrier` made to raise."""
    cfg = (_two_periods(model) if model != "qwen2"
           else ModelConfig.qwen2_tiny(vocab_size=V))
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    B, T, P, T_max = 2, 12, 4, 16
    ids, valid, pos, tabs, pages = _two_rows(cfg)
    pattern = cfg.attention_pattern is not None

    def served():
        """(the prefill's and each step's logits, the decode step's lowered
        text), from programs traced NOW: new functions, so no trace is kept
        from the other side."""
        fill = jax.jit(functools.partial(
            prefill, page_table=tabs, page_size=P, logical_len=T_max),
            static_argnums=1)
        step = jax.jit(functools.partial(
            decode_step, page_table=tabs, page_size=P), static_argnums=1)
        caches = init_paged_kv_cache(
            cfg, pages, P, jnp.float32,
            **({"state_rows": B} if cfg.conv_layers else {}))
        lg, caches = fill(params, cfg, ids[:, :T], jnp.asarray(valid[:, :T]),
                          caches)
        out = [lg]
        km = jnp.zeros((B, T_max), bool).at[:, :T].set(valid[:, :T])
        for t in range(T, T + 3):
            km = km.at[:, t].set(True)
            args = (params, cfg, ids[:, t], pos[:, t],
                    jnp.full((B,), t, jnp.int32), km, caches)
            lg, caches = step(*args)
            out.append(lg)
        return [np.asarray(a) for a in out], step.lower(*args).as_text()

    def unfenced(tree):
        if pattern:
            return tree
        raise AssertionError("a model without a pattern reached the fence")

    got, text = served()
    monkeypatch.setattr(jax.lax, "optimization_barrier", unfenced)
    want, plain = served()
    monkeypatch.undo()
    # in the scan's body (and a one-trip dense stack's), once a layer
    fences = {"smallthinker": 4, "lfm2": 1, "trinity": 1 + 4, "qwen2": 0}
    assert text.count("optimization_barrier") == fences[model]
    assert "optimization_barrier" not in plain
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    scored = jax.jit(lambda p: padded_forward_logits(p, cfg, ids[:, :T], 0))
    assert "optimization_barrier" not in scored.lower(params).as_text()
    last = np.asarray(scored(params))[:, -1]
    if model in ("smallthinker", "trinity"):
        np.testing.assert_array_equal(got[0], last)
    else:       # (the last bit, as ever: another order of the same sums)
        np.testing.assert_allclose(got[0], last, rtol=0, atol=1e-6)


# ------------------------------------------------------------- the trainer

def test_one_grpo_update_runs(tmp_path):
    """Rollout on the contiguous cache (the window by mask), scoring and a
    LoRA update through the pattern scan: it runs, and its loss is finite."""
    import json

    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    tok = ToyTokenizer(vocab_size=256)
    mcfg = ModelConfig.smallthinker_tiny(vocab_size=256, window=W)
    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path), response_length=12,
        temperature=1.0, sample_n=2, total_episodes=16,
        per_device_train_batch_size=1, gradient_accumulation_steps=2,
        num_mini_batches=2, num_ppo_epochs=1, learning_rate=1e-4, kl_coef=0.05,
        use_lora=True, lora_r=4, lora_alpha=8, gradient_checkpointing=True,
        mesh=MeshConfig(2, 2, 2), save_steps=0, report_to="jsonl")
    dataset = load_prompt_dataset("synthetic:16", tok, max_prompt_len=12)
    reward = lambda texts, eos: np.asarray(     # noqa: E731
        [float(len(t) % 3) for t in texts], np.float32)
    trainer = RLTrainer(cfg, mcfg, tok, init_params(
        mcfg, jax.random.PRNGKey(0), jnp.float32), dataset, reward)
    assert "experts" not in trainer.params.get("lora", {}).get("layers", {})
    state = trainer.train(num_updates=1)
    assert state["global_step"] == 1
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    losses = [r[k] for r in rows for k in r if k.endswith("loss/policy_avg")
              or k == "loss/policy_avg"]
    assert all(np.isfinite(v) for v in losses)
    with pytest.raises(NotImplementedError, match="window layers"):
        RLTrainer(dataclasses.replace(cfg, kv_cache_quant="int8",
                                      output_dir=str(tmp_path / "q")),
                  mcfg, tok, init_params(mcfg, jax.random.PRNGKey(0),
                                         jnp.float32), dataset, reward)
