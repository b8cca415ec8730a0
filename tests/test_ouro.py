"""Ouro (`model_type: ouro`, docs/OURO.md): a looped language model, ONE
stack of layers with four norms each that every token passes several times,
the final norm closing each pass, a cache slot a pass a layer, against the
plain float32 reference of benchmark/harness/reference_ouro.py on seeded
weights. Tiny widths: 2 layers passed 3 times, so that no count stands in
for another; logits, not tokens."""

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

from harness import reference_ouro as ref  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.core.model import (  # noqa: E402
    decode_step, init_kv_cache, init_paged_kv_cache, prefill,
)

with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
    FILE = json.load(f)
with open(os.path.join(BENCH, "tests", "rehearsal", "configs",
                       "tiny-ouro.json")) as f:
    HF = {**json.load(f), "vocab_size": 128}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
V = HF["vocab_size"]
CFG = ModelConfig.from_hf_config(HF)
L, T = CFG.num_hidden_layers, CFG.loop_passes
TOL = 1e-4      # float32 on both sides under "highest": the order of sums
EOS, PAD = 1, 0
CONTROLS = {"two_passes": {"passes": 2}, "no_pass_norm": {"pass_norm": False},
            "no_attn_norm": {"attn_norm": False},
            "no_mlp_norm": {"mlp_norm": False}}


def lay_weights(cfg, key=0):
    """Seeded weights with everything the controls need to matter: kernels at
    1 / sqrt(fan-in), norm weights that are not ones, an embedding of RMS 1,
    a gate with a bias."""
    p = init_params(cfg, jax.random.PRNGKey(key), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 5), 16))
    for name, leaf in p["layers"].items():
        if isinstance(leaf, dict):
            n, fan = leaf["kernel"].shape[:2]
            leaf["kernel"] = leaf["kernel"] * (n / fan) ** 0.5
        else:
            p["layers"][name] = jnp.exp(0.3 * jax.random.normal(next(keys),
                                                                leaf.shape))
    p["norm"] = jnp.exp(0.3 * jax.random.normal(next(keys), p["norm"].shape))
    p["embed_tokens"] = 50.0 * p["embed_tokens"]
    p["lm_head"] = (5.0 * p["lm_head"]).at[:, jnp.asarray([EOS, PAD])].set(0)
    p["early_exit_gate"]["bias"] = jnp.asarray([-0.3], jnp.float32)
    return p


@pytest.fixture(scope="module")
def params():
    return lay_weights(CFG)


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(0)
    x = rng.integers(3, V, (3, 20)).astype(np.int32)
    x[0, :6] = PAD      # left-padded rows of unequal length beside a full one
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
    cfg = ModelConfig.from_hf_config(FILE)
    assert (cfg.loop_passes, cfg.cache_layers, cfg.num_hidden_layers) == (4, 192, 48)
    assert cfg.branch_norms and not cfg.attention_bias
    assert cfg.model_type == "ouro"
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.actual_head_dim, cfg.vocab_size) == (
                2048, 5632, 16, 16, 128, 49152)
    assert cfg.rope_theta == 1e6 and not cfg.tie_word_embeddings
    assert cfg.attention_pattern is None and cfg.max_position_embeddings == 65536
    assert dataclasses.replace(cfg, attention_impl="auto") == ModelConfig.ouro_2_6b()
    assert (CFG.loop_passes, CFG.cache_layers) == (3, 6)
    assert ModelConfig.qwen2_tiny().cache_layers == 2
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == FILE["source"] and FILE["reduced"] == []
        assert not [k for k, v in row["config"].items() if FILE.get(k, "no") != v]
        assert ModelConfig.from_hf_config(row["config"]) == cfg


@pytest.mark.parametrize("change, what", [
    ({"early_exit_threshold": 0.9}, "early_exit_threshold"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"num_experts": 8}, "expert keys"),
    ({"model_type": "qwen2"}, "total_ut_steps"),
    ({"model_type": "llama", "total_ut_steps": 2}, "total_ut_steps"),
])
def test_from_hf_config_raises_on_what_is_not_built(change, what):
    """A threshold below 1 (rows would leave a forward at different passes)
    and, under ANY model type the generic branch builds, a `total_ut_steps`
    above 1: refused by name, never dropped."""
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config({**HF, **change})
    # one pass under a generic model type is that model, and is built
    assert ModelConfig.from_hf_config(
        {**HF, "model_type": "llama", "total_ut_steps": 1}).loop_passes == 1


@pytest.mark.parametrize("fields", [
    {"num_experts": 8, "num_experts_per_tok": 2},
    {"sliding_window": 8, "sliding_window_layout": (1, 0)},
    {"block_length": 4}, {"loop_passes": 0}])
def test_a_loop_over_another_kind_of_stack_is_refused(fields):
    with pytest.raises((NotImplementedError, ValueError), match="loop_passes"):
        dataclasses.replace(CFG, **fields)


def test_hf_names_round_trip(params):
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )
    sd = hf_state_dict_from_params(CFG, params)
    for name in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2",
                 "self_attn.q_proj", "mlp.down_proj"):
        assert f"model.layers.1.{name}.weight" in sd
    assert sd["model.early_exit_gate.weight"].shape == (1, CFG.hidden_size)
    assert sd["model.early_exit_gate.bias"].shape == (1,)
    assert not [k for k in sd if k.endswith(".bias") and "gate" not in k]
    np.testing.assert_array_equal(
        np.asarray(sd["model.layers.1.input_layernorm_2.weight"]),
        np.asarray(params["layers"]["attn_branch_norm"][1]))
    back = params_from_hf_state_dict(CFG, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- the model

def test_uncached_forward_is_the_reference(params, ids, reference_logits):
    real = np.asarray(ids != PAD)
    got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, reference_logits["sound"], real) < TOL


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_model_without_one_mechanism_fails_the_comparison(
        control, params, ids, reference_logits):
    """The reference with one mechanism left out (one pass fewer, no norm
    between the passes, either branch norm) sits FAR from the program."""
    real = np.asarray(ids != PAD)
    got = padded_forward_logits(params, CFG, ids, PAD)
    assert far(got, reference_logits[control], real) > 0.05


def test_one_pass_is_the_unlooped_branch_norms_model(params, ids):
    """`loop_passes = 1` stages no loop: the plain path, whose head applies
    the final norm once, which is what one closed pass is."""
    one = dataclasses.replace(CFG, loop_passes=1)
    real = np.asarray(ids != PAD)
    got = padded_forward_logits(params, one, ids, PAD)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, HF, ids, PAD, passes=1)
    assert far(got, want, real) < TOL
    text = str(jax.make_jaxpr(lambda p: padded_forward_logits(p, one, ids, PAD))(
        params))
    assert "pass" not in text


def _prefill_and_decode(params, ids, cfg=CFG, split=12, paged=False):
    """Logits of the last prompt position and of every later one, through
    the cache, and the cache."""
    B, S = ids.shape
    mask = ids != PAD
    pos = jnp.cumsum(mask, 1) - mask.astype(jnp.int32)
    kw = {}
    if paged:
        P = 4
        nb = -(-S // P)
        kw = dict(page_table=jnp.arange(B * nb, dtype=jnp.int32).reshape(nb, B).T,
                  page_size=P)
        caches = init_paged_kv_cache(cfg, B * nb, P, jnp.float32)
        lg, caches = prefill(params, cfg, ids[:, :split], mask[:, :split],
                             caches, logical_len=nb * P, **kw)
        width = nb * P
    else:
        caches = init_kv_cache(cfg, B, S, jnp.float32)
        lg, caches = prefill(params, cfg, ids[:, :split], mask[:, :split], caches)
        width = S
    out = [lg]
    km = jnp.zeros((B, width), bool).at[:, :split].set(mask[:, :split])
    for s in range(split, S):
        km = km.at[:, s].set(True)
        lg, caches = decode_step(params, cfg, ids[:, s], pos[:, s],
                                 jnp.full((B,), s, jnp.int32), km, caches, **kw)
        out.append(lg)
    return jnp.stack(out, axis=1), caches


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_prefill_and_decode_through_the_cache_are_the_reference(
        params, ids, reference_logits, paged):
    got, caches = _prefill_and_decode(params, ids, paged=paged)
    want = reference_logits["sound"][:, 11:]
    assert float(np.abs(np.asarray(got) - want).max()) < TOL
    # a slot a pass a layer, and every one of them written
    assert len(caches) == 2 and caches[0].shape[0] == T * L == 6
    assert all(float(jnp.abs(caches[0][i]).sum()) > 0 for i in range(T * L))


def test_slot_t_l_is_written_by_pass_t_alone(params, ids):
    """Pass t's keys and values depend on the passes up to t only: the cache
    of the model with fewer passes IS the first layers of this one's, and the
    later passes' layers hold something else."""
    _, three = _prefill_and_decode(params, ids)
    for passes in (1, 2):
        _, fewer = _prefill_and_decode(
            params, ids, dataclasses.replace(CFG, loop_passes=passes))
        assert fewer[0].shape[0] == passes * L
        for a, b in zip(fewer, three):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b[:passes * L]),
                                       atol=1e-5)
    k = np.asarray(three[0])
    for t in range(1, T):
        assert np.abs(k[t * L:(t + 1) * L] - k[(t - 1) * L:t * L]).max() > 0.05


@pytest.mark.parametrize("fault", ["shared_slot", "decode_reads_pass_1"])
def test_a_cache_that_shares_a_slot_a_layer_is_far_from_the_reference(
        fault, params, ids, reference_logits, monkeypatch):
    """The paper's "last-step reuse" (the passes of a layer share one slot,
    so a decode step's pass t reads what the LAST pass wrote of the earlier
    tokens), and a decode that reads and writes pass 1's slots in every pass
    while the prefill wrote all of them: each must sit FAR from the
    reference, so the sound test cannot pass by accident."""
    sound = M._pass_cache_offset
    if fault == "shared_slot":
        monkeypatch.setattr(M, "_pass_cache_offset", lambda config, t: t * 0)
        got, _ = _prefill_and_decode(params, ids)
    else:
        B = ids.shape[0]
        mask = ids != PAD
        caches = init_kv_cache(CFG, B, ids.shape[1], jnp.float32)
        _, caches = prefill(params, CFG, ids[:, :12], mask[:, :12], caches)
        monkeypatch.setattr(M, "_pass_cache_offset", lambda config, t: t * 0)
        pos = jnp.cumsum(mask, 1) - mask.astype(jnp.int32)
        km = jnp.zeros(ids.shape, bool).at[:, :12].set(mask[:, :12])
        out = []
        for s in range(12, ids.shape[1]):
            km = km.at[:, s].set(True)
            lg, caches = decode_step(params, CFG, ids[:, s], pos[:, s],
                                     jnp.full((B,), s, jnp.int32), km, caches)
            out.append(lg)
        got = jnp.stack(out, axis=1)
    assert sound(CFG, 2) == 2 * L
    want = reference_logits["sound"][:, -got.shape[1]:]
    assert float(np.abs(np.asarray(got) - want).max()) > 0.05


def test_the_gate_on_each_passs_state_gives_the_references_exit_distribution(
        params, ids):
    """No forward here computes the gate (at the published threshold every
    token runs every pass), so the tree's leaf and the states it would read
    are held to the reference's `p(t)` from outside: pass t's closed state is
    the hidden state of the model with t passes."""
    from nanorlhf_tpu.core.model import padded_forward_hidden

    real = np.asarray(ids != PAD)
    gate = params["early_exit_gate"]
    lam = jnp.stack([jax.nn.sigmoid(
        (padded_forward_hidden(params, dataclasses.replace(CFG, loop_passes=t),
                               ids, PAD) @ gate["kernel"])[..., 0]
        + gate["bias"][0]) for t in range(1, T + 1)], axis=-1)
    before = jnp.cumprod(jnp.concatenate(
        [jnp.ones_like(lam[..., :1]), 1.0 - lam[..., :-1]], axis=-1), axis=-1)
    p = jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]], axis=-1)
    with jax.default_matmul_precision("highest"):
        want_p, want_exits = ref.exit_distribution(params, HF, ids, PAD)
        _, want_early = ref.exit_distribution(
            params, {**HF, "early_exit_threshold": 0.5}, ids, PAD)
    assert p.shape == ids.shape + (T,)
    assert far(p, want_p, real) < 1e-5
    np.testing.assert_allclose(np.asarray(p.sum(-1))[real], 1.0, atol=1e-5)
    # the gate says something: no pass takes nothing or everything
    assert 0.01 < float(np.asarray(p)[real].min())
    assert float(np.asarray(p)[real].max()) < 0.95
    # q = 1: a sigmoid is below 1, so every token runs every pass
    reached = np.asarray(jnp.cumsum(p, -1))[..., :-1]
    assert (reached[real] < 1.0).all()
    assert (np.asarray(want_exits)[real] == T).all()
    # below 1 (refused by the config parser) tokens would leave earlier
    early = np.where((reached >= 0.5).any(-1), (reached >= 0.5).argmax(-1),
                     T - 1) + 1
    np.testing.assert_array_equal(early[real], np.asarray(want_early)[real])
    assert (early[real] < T).any()


def test_a_looped_stack_of_any_family_carries_the_gate(ids):
    """The gate's leaf hangs on the feature, not on a model's name: a looped
    config of another family initialises, runs and round-trips its names."""
    from nanorlhf_tpu.core.params import (
        hf_state_dict_from_params, params_from_hf_state_dict,
    )
    cfg = dataclasses.replace(ModelConfig.qwen2_tiny(), loop_passes=2)
    tree = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert tree["early_exit_gate"]["kernel"].shape == (cfg.hidden_size, 1)
    assert "attn_branch_norm" not in tree["layers"]
    assert "early_exit_gate" not in init_params(
        dataclasses.replace(CFG, loop_passes=1), jax.random.PRNGKey(0))
    small = jnp.clip(ids, 0, cfg.vocab_size - 1)
    assert bool(jnp.isfinite(padded_forward_logits(tree, cfg, small, PAD)).all())
    sd = hf_state_dict_from_params(cfg, tree)
    assert "model.early_exit_gate.bias" in sd
    assert "model.layers.0.input_layernorm_2.weight" not in sd
    back = params_from_hf_state_dict(cfg, sd, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(tree)


def test_one_adapter_used_by_every_pass_has_the_references_gradient(params, ids):
    """LoRA: the adapters lie over the LAYERS and every pass uses them; the
    gradient through three uses of one adapter is `jax.grad` of the reference
    with the same adapters (nothing else trains a looped model here: the
    trainers' step is not run on it, docs/OURO.md)."""
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

    lora = init_lora_params(CFG, LoraConfig(r=4, targets=("q_proj", "o_proj",
                                                          "down_proj")),
                            jax.random.PRNGKey(3), jnp.float32)
    assert lora["layers"]["q_proj"]["a"].shape[0] == L      # not T * L
    for name, ab in lora["layers"].items():
        ab["b"] = 0.05 * jax.random.normal(jax.random.PRNGKey(len(name)),
                                           ab["b"].shape)
    mask = ids != PAD

    def loss(logits):
        return jnp.mean(jax.nn.logsumexp(logits, -1) * mask)

    got = jax.grad(lambda lo: loss(padded_forward_logits(
        {**params, "lora": lo}, CFG, ids, PAD, lora_scale=0.5)))(lora)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda lo: loss(ref.logits(
            {**params, "lora": lo}, HF, ids, PAD, lora_scale=0.5)))(lora)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=1e-6)


def test_flops_count_the_stack_once_a_pass(params):
    from nanorlhf_tpu.telemetry.mfu import flops_param_count

    once = flops_param_count(params)
    stack = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params["layers"]))
    assert flops_param_count(params, CFG.loop_passes) == once + (T - 1) * stack


# ------------------------------------------------------------- the session

def session(params, cfg=CFG, **kw):
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.radix import RadixCache

    return DecodeSession(
        params, cfg, **{**dict(
            rows=3, prompt_len=12, max_tokens=14, page_size=4, eos_token_id=EOS,
            pad_token_id=PAD, key=jax.random.PRNGKey(1), per_row=True,
            prefix_cache=RadixCache(headroom=0.0), sync_every=4), **kw})


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


def test_session_rows_cross_page_boundaries_on_the_reference(params):
    """Three rows at once over pages of 4 (a 10-token prompt is three pages,
    14 new tokens cross four more): every served token is the reference's top
    to float32 roundoff (its LOGITS on the served context), and is not the
    top of a model with one pass fewer."""
    sess = session(params)
    prompts, answers = serve(sess, (10, 3, 7), (14, 12, 9))
    assert (sess.config.loop_passes, sess.config.cache_layers) == (3, 6)
    assert sess.state[3][0].shape[0] == 6
    assert sess.kv_bytes_per_token == 6 * 2 * 4 * 16 * 4
    for g in gaps(params, prompts, answers):
        assert g.max() < TOL
    assert max(g.max() for g in gaps(params, prompts, answers, passes=2)) > 0.05
    # a live row's pages reserve its whole budget; fewer hold a token
    assert 0 < sess.global_slots_read < sess.pool_reserved_slots
    assert sess.pool_reserved_slots % (sess.nb * sess.page_size) == 0


def test_radix_keys_pages_not_layers(params):
    """A re-used prefix is a hit on a looped model as on any other: the tree
    keys a row's pages, and a page holds every cache layer's slots."""
    sess = session(params, rows=2, prompt_len=16)
    rng = np.random.default_rng(4)
    shared = rng.integers(3, V, 9)
    prompts = [np.concatenate([shared, rng.integers(3, V, 4)]) for _ in range(2)]
    answers = []
    for r, p in enumerate(prompts):
        toks, mask = np.zeros(16, np.int32), np.zeros(16, bool)
        toks[16 - len(p):], mask[16 - len(p):] = p, True
        sess.admit(r, toks, mask, r, budget=8, temperature=1.0, top_p=1.0,
                   greedy=True)
        for _ in range(20):
            done, _ = sess.step()
            if done.all() and not sess.has_pending():
                break
        answers.append(np.asarray(sess.state[1])[r, :8])
        sess.release(r)
    assert sess.hit_tokens >= 8
    for g in gaps(params, prompts, answers):
        assert g.max() < TOL


def test_engine_serves_the_monolithic_loops_tokens_and_counts(params):
    from nanorlhf_tpu.sampler.sampler import SamplingParams, generate
    from nanorlhf_tpu.serving.engine import ServingEngine

    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, V, n) for n in (11, 5, 12)]
    with ServingEngine(params, CFG, eos_token_id=EOS, pad_token_id=PAD,
                       page_size=4, prompt_len=12, max_new_tokens=12, rows=2,
                       headroom=0.0, sync_every=4) as engine:
        reqs = [engine.submit(p, greedy=True, max_tokens=10)[0] for p in prompts]
        streams = [list(engine.stream(r)) for r in reqs]
        m = engine.metrics()
    ids = np.zeros((3, 12), np.int32)
    for i, p in enumerate(prompts):
        ids[i, 12 - len(p):] = p
    out = generate(params, CFG, jnp.asarray(ids), jnp.asarray(ids != PAD),
                   jax.random.PRNGKey(0),
                   SamplingParams(max_tokens=10, greedy=True),
                   eos_token_id=EOS, pad_token_id=PAD)
    tokens = np.asarray(out)        # [B, max_tokens]: the new tokens
    assert tokens.shape == (3, 10)
    assert [list(map(int, s)) for s in streams] == tokens.tolist()
    assert m["serving/loop_passes_per_token"] == 3
    assert m["serving/cache_layers"] == 6
    assert m["serving/kv_bytes_per_token"] == 6 * 2 * 4 * 16 * 4
    assert 0 < m["serving/pool_live_slots"] < m["serving/pool_reserved_slots"]
    assert "serving/exit_cdf_pass1" not in m    # no served forward runs the gate


# ------------------------------------------------------- refused by name

@pytest.mark.parametrize("what", ["speculation", "int8", "mesh"])
def test_what_is_not_built_for_a_looped_model_is_refused_by_name(what, params):
    from nanorlhf_tpu.sampler.sampler import SamplingParams, compose_check

    if what == "speculation":
        with pytest.raises(NotImplementedError, match="looped model"):
            compose_check(SamplingParams(max_tokens=4, greedy=True, spec_k=2),
                          config=CFG)
        with pytest.raises(NotImplementedError, match="looped model"):
            session(params, spec_k=2, greedy=True, per_row=False,
                    prefix_cache=None)
    elif what == "int8":
        q8 = dataclasses.replace(CFG, kv_cache_quant="int8")
        with pytest.raises(NotImplementedError, match="looped model"):
            compose_check(SamplingParams(max_tokens=4), config=q8)
        with pytest.raises(NotImplementedError, match="looped model"):
            init_kv_cache(q8, 1, 8)
        with pytest.raises(NotImplementedError, match="looped model"):
            init_paged_kv_cache(q8, 4, 4)
    else:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1), ("data", "tensor"))
        meshed = dataclasses.replace(CFG, spmd_mesh=mesh,
                                     spmd_batch_axes=("data",))
        with pytest.raises(NotImplementedError, match="looped model"):
            compose_check(SamplingParams(max_tokens=4), config=meshed)
        with pytest.raises(NotImplementedError, match="looped model"):
            session(params, cfg=meshed)
