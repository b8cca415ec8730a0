"""`core/model._run_layers`, the one function that scans the layers, against
the layers run one by one (`loop_layers`: no scan, no carry, no index that is
not a Python number), for the six model kinds, without a cache and over a
contiguous and a paged one; and the form of the bf16 attention read
(`core/model.attention_form`), a rule a case."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import ModelConfig, init_params, padded_forward_logits
from nanorlhf_tpu.core import model as M
from nanorlhf_tpu.core.model import (
    decode_step, decode_verify, init_kv_cache, init_paged_kv_cache, prefill,
)
from tests.test_smallthinker import V, _two_periods, _two_rows

PAD, EOS = 0, 3


def loop_layers(config, params, x, cos, sin, views, kv_caches=None,
                lora_scale=1.0, remat=False, attn_fn=None,
                layer_transform=None, cached_aux=False):
    """What `core/model._run_layers` computes, with no scan, no carry and
    no index that is not a Python number: the layers one by one through
    `_layer_body`, each on its leaves sliced statically out of the stacks.
    Of its kind's cache a layer sees ONLY its own one-layer stacks
    `c[l:l + 1]`, as layer 0 of them, and the stacks that leave are those
    laid end to end: a `_layer_body`, `_cache_write` or `_layer_slab` that
    read or wrote another layer's slab under the scan shows against this.
    The expert kernels are addressed in place where the runner addresses
    them in place. A looped model (docs/OURO.md) goes through its stack
    `loop_passes` times, the final norm after each, and a pass's layers see
    the one-layer stacks that FOLLOW the pass before's: a slot a pass a
    layer, laid end to end."""
    cached = kv_caches is not None
    plain = config.attention_pattern is None
    groups = ()
    if cached:
        groups = (tuple(kv_caches),) if plain else tuple(kv_caches)
    written = [[] for _ in groups]  # each group's one-layer stacks, in order
    seen, aux = [0, 0, 0], None     # layers of each cache group so far
    for _ in range(config.loop_passes):
        for tree, lora, start, count in M._layer_stacks(params):
            tree = dict(tree)
            experts = None
            if cached or not plain or M.use_expert_kernel(config):
                experts = tree.pop("experts", None)
            own = [0, 0]    # this stack's attention layers and conv layers so far
            # (the leaves of the kind that keeps a state and no pages)
            stateful = ("lightning" if config.linear_layers
                        else "ssm" if config.mamba_layers else "conv")
            split = (config.conv_layers + config.linear_layers
                     + config.mamba_layers)
            auxes = []
            for at, kind in enumerate(config.layer_kinds[start:start + count]):
                g = M._kind_group(kind)
                layer_params = {}
                for name, leaf in tree.items():
                    mine = name == stateful or name in M._ATTENTION_LEAVES
                    if split and mine:
                        if (name == stateful) == (g == 2):
                            layer_params[name] = jax.tree.map(
                                lambda a: a[own[int(g == 2)]], leaf)
                    else:
                        layer_params[name] = jax.tree.map(lambda a: a[at], leaf)
                own_cache = None
                if cached:
                    own_cache = tuple(c[seen[g]:seen[g] + 1] for c in groups[g])
                x, cache, layer_aux = M._layer_body(
                    config, x, M.LayerLeaves(
                        layer_params, jax.tree.map(lambda a: a[at], lora),
                        experts, at),
                    0, kind, views[g], own_cache, cos, sin, lora_scale, attn_fn)
                if cached:
                    written[g].append(cache)
                auxes.append(layer_aux)
                seen[g] += 1
                own[int(g == 2)] += 1
            if not cached or cached_aux:
                stacked = jax.tree.map(lambda *a: jnp.stack(a), *auxes)
                aux = aux if stacked is None else stacked
        if config.loop_passes > 1:
            x = M.rms_norm(x, params["norm"], config.rms_norm_eps)
    if not cached:
        return x, None, aux
    # (a group none of whose layers ran, a model's empty stacks, stays)
    caches = tuple(
        tuple(jnp.concatenate(cs, axis=0) for cs in zip(*layers))
        if layers else group for layers, group in zip(written, groups))
    if plain:
        (caches,) = caches
    return x, caches, aux


def _assert_same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# a model of one kind, exact and int8: prefill, decode steps, a verify
# --------------------------------------------------------------------- #

def _qwen(quant):
    return dataclasses.replace(ModelConfig.qwen2_tiny(vocab_size=128),
                               kv_cache_quant=quant)


def _forward_chain(config, params, layout, per_row):
    """prefill → three decode_steps → one decode_verify of four candidates;
    returns every logits array and the final caches."""
    B, Tp, steps, K1, P = 2, 4, 3, 4, 4
    T_max = Tp + steps + K1 + 3
    ids = jnp.asarray([[PAD, 5, 6, 7], [9, 10, 11, 12]], jnp.int32)
    mask = ids != PAD
    kw = {}
    if layout == "paged":
        nb = -(-T_max // P)
        # rows interleave their pages, so a wrong table lookup shows
        table = jnp.arange(B * nb, dtype=jnp.int32).reshape(nb, B).T
        caches = M.init_paged_kv_cache(config, B * nb, P, jnp.float32)
        kw = dict(page_table=table, page_size=P)
        logits, caches = M.prefill(params, config, ids, mask, caches,
                                   logical_len=T_max, **kw)
    else:
        caches = M.init_kv_cache(config, B, T_max, jnp.float32)
        logits, caches = M.prefill(params, config, ids, mask, caches)
    got = [logits]
    plen = jnp.sum(mask, axis=1).astype(jnp.int32)
    # per-row: row 1 sits two slots deeper than row 0 (rows of a session
    # advance at different rates); the skipped slots stay invisible
    ahead = jnp.asarray([0, 2] if per_row else [0, 0], jnp.int32)
    key_mask = jnp.zeros((B, T_max), bool).at[:, :Tp].set(mask)
    rows = jnp.arange(B)
    toks = jnp.asarray([[20, 21, 22], [30, 31, 32]], jnp.int32)
    for i in range(steps):
        slot = Tp + i + ahead
        key_mask = key_mask.at[rows, slot].set(True)
        logits, caches = M.decode_step(
            params, config, toks[:, i], plen + i,
            slot if per_row else Tp + i, key_mask, caches, **kw)
        got.append(logits)
    cand = jnp.asarray([[40, 41, 42, 43], [50, 51, 52, 53]], jnp.int32)
    fill = Tp + steps + ahead
    positions = (plen + steps)[:, None] + jnp.arange(K1)[None, :]
    logits, caches = M.decode_verify(params, config, cand, positions, fill,
                                     key_mask, caches, **kw)
    got.append(logits)
    return got, caches


def _one_kind_chain(monkeypatch, layout, quant, per_row):
    """ISSUE 26. Bit for bit with both sides run op by op
    (`jax.disable_jit`: the scan then steps its body in Python, carry and
    layer index as in the compiled loop), because only then do both sides
    run the same executables. A scan body compiled as one computation
    differs from the same layer run op by op (or jitted alone) in the last
    bit on XLA:CPU (1.2e-7 on these logits: other multiply-adds are
    contracted), whatever the cache does; the compiled path is held to that
    roundoff below, on the exact cache."""
    config = _qwen(quant)
    params = init_params(_qwen("none"), jax.random.PRNGKey(7), jnp.float32)
    with jax.disable_jit():
        got, got_caches = _forward_chain(config, params, layout, per_row)
    compiled, compiled_caches = _forward_chain(config, params, layout, per_row)
    monkeypatch.setattr(M, "_run_layers", loop_layers)
    with jax.disable_jit():
        want, want_caches = _forward_chain(config, params, layout, per_row)
    _assert_same(got, want)
    assert len(got_caches) == (4 if quant == "int8" else 2)
    _assert_same(got_caches, want_caches)
    # something was written, in every layer
    k = np.asarray(got_caches[0])
    assert all(np.abs(k[l]).sum() > 0 for l in range(k.shape[0]))
    if quant == "none":
        # an int8 cache turns a last-bit difference into a whole step now
        # and then, so only the exact cache is compared across compilations
        for a, b in zip(compiled + list(compiled_caches),
                        want + list(want_caches)):
            b = np.asarray(b)
            np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                       atol=1e-5 * np.abs(b).max())


# --------------------------------------------------------------------- #
# a pattern model at two periods: how a layer gets its leaves
# --------------------------------------------------------------------- #

def _adapters(params):
    """LoRA leaves on q and o of every stack with attention leaves, over the
    STACK's layers (LFM2's attention leaves themselves lie over its attention
    layers only), with a `b` that is not zero, so that they count."""
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    out = {}
    for stack in ("dense_layers", "layers"):
        tree = params.get(stack, {})
        if "q_proj" not in tree:
            continue
        L = tree["input_layernorm"].shape[0]
        out[stack] = {}
        for name in ("q_proj", "o_proj"):
            d_in, d_out = tree[name]["kernel"].shape[1:]
            out[stack][name] = {
                "a": jax.random.normal(next(keys), (L, d_in, 4)) / 2,
                "b": jax.random.normal(next(keys), (L, 4, d_out)) * 0.05}
    return out


def _scans(jaxpr):
    """Every `scan` equation of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _scans(sub)
    return found


def _scanned(eqn):
    """The shapes of a scan's xs."""
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    return [v.aval.shape for v in eqn.invars[skip:]]


def _two_period_pattern(monkeypatch, model, lora):
    """ISSUE 43. With a cache (a prefill, then three decode steps, paged)
    the period scan's xs are the period's index alone and every layer takes
    its leaves, LoRA's and a kind's own too, from the whole stacks at its own
    index (`core/model.leaves_in_place`): logits and caches BITWISE those of
    the layers run one by one on statically sliced leaves (`loop_layers`).
    Without a cache the stacks are still the scan's xs, `[n / p, p, ...]`,
    and the gradient is the loop's."""
    cfg = _two_periods(model)
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    *_, start, n = M._layer_stacks(params)[-1]
    assert n == 2 * len(cfg.stack_pattern(start, n))    # two trips
    assert M.leaves_in_place(cfg, cached=True)
    assert not M.leaves_in_place(cfg, cached=False)
    assert not M.leaves_in_place(cfg, cached=True, layer_transform=lambda *a: a)
    if lora:
        params = {**params, "lora": _adapters(params)}
        assert set(params["lora"]) == ({"layers"} if model != "trinity"
                                       else {"dense_layers", "layers"})
    B, T, P, T_max = 2, 12, 4, 16
    ids, valid, pos, tabs, pages = _two_rows(cfg)

    fill = functools.partial(
        prefill, page_table=tabs, page_size=P, logical_len=T_max)
    step = functools.partial(decode_step, page_table=tabs, page_size=P)
    prompt = (ids[:, :T], jnp.asarray(valid[:, :T]))

    def pool():
        return init_paged_kv_cache(
            cfg, pages, P, jnp.float32,
            **({"state_rows": B} if cfg.state_layers else {}))

    @jax.disable_jit()
    def served():
        """(the prefill's and each step's logits, the caches after them),
        primitive by primitive: the scan then runs its body a trip at a
        time, and both sides run the same primitives on the same values. (As
        ONE program a side, XLA's CPU backend fuses the elementwise
        operations of a loop's body and of the unrolled layers differently,
        and Trinity's decode step then differs in the last bit, 2e-7.)"""
        lg, caches = fill(params, cfg, *prompt, pool())
        out = [lg]
        km = jnp.zeros((B, T_max), bool).at[:, :T].set(valid[:, :T])
        for t in range(T, T + 3):
            km = km.at[:, t].set(True)
            lg, caches = step(params, cfg, ids[:, t], pos[:, t],
                              jnp.full((B,), t, jnp.int32), km, caches)
            out.append(lg)
        return out, caches

    def scored():
        """The uncached forward's loss, a NEW function a call: jit and
        make_jaxpr keep a trace by function, and the second trace has to see
        the loop."""
        def loss(p):
            logits = padded_forward_logits(p, cfg, ids, 0)
            return jnp.mean(jax.nn.logsumexp(logits, -1) * valid)
        return loss

    cached = jax.make_jaxpr(fill, static_argnums=1)(
        params, cfg, *prompt, jax.eval_shape(pool))
    uncached = jax.make_jaxpr(scored())(params)
    # the last scan is the expert stack's: two trips, the index its one xs
    assert _scanned(_scans(cached.jaxpr)[-1]) == [(2,)]
    xs = _scanned(_scans(uncached.jaxpr)[-1])
    # every projection kernel is among them, by period (LFM2: its attention
    # layer's one a period, its conv layers' three)
    kernels = [s for s in xs if len(s) == 4 and s[0] == 2]
    assert (2,) in xs and len(kernels) >= 4 + 4 * lora, xs
    got, got_caches = served()
    got_grad = jax.jit(jax.grad(scored()))(params)
    monkeypatch.setattr(M, "_run_layers", loop_layers)
    assert not [e for e in _scans(jax.make_jaxpr(scored())(params).jaxpr)
                if e.params["length"] == 2]
    want, want_caches = served()
    want_grad = jax.jit(jax.grad(scored()))(params)
    _assert_same(got, want)
    _assert_same(got_caches, want_caches)
    moved = 0
    for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
        moved += bool(np.abs(np.asarray(b)).max() > 0)
    assert moved > 10


# --------------------------------------------------------------------- #
# every kind, every cache it has
# --------------------------------------------------------------------- #

_KINDS = {"qwen2": ModelConfig.qwen2_tiny, "olmoe": ModelConfig.olmoe_tiny,
          "axk1": ModelConfig.axk1_tiny,
          "smallthinker": ModelConfig.smallthinker_tiny,
          "lfm2": ModelConfig.lfm2_tiny, "trinity": ModelConfig.trinity_tiny,
          "sala": ModelConfig.minicpm_sala_tiny,
          "ouro": ModelConfig.ouro_tiny,
          "granite_h": ModelConfig.granite_h_tiny}


def _every_kind(monkeypatch, kind, cache):
    """The one runner on each model kind's tiny preset: the uncached
    forward's logits, or a prefill, two decode steps (the second with rows
    nobody listens to) and a verify of three candidates over the cache,
    BITWISE the layers one by one, primitive by primitive."""
    cfg = _KINDS[kind](vocab_size=V)
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    B, T, P, T_max, K1 = 2, 10, 4, 16, 3
    ids, valid, pos, tabs, pages = _two_rows(cfg)
    kw = {}
    if cache == "paged":
        kw = {"page_table": tabs, "page_size": P}

    @jax.disable_jit()
    def run():
        if cache == "uncached":
            return [padded_forward_logits(params, cfg, ids, 0)], None
        if cache == "paged":
            caches = init_paged_kv_cache(
                cfg, pages, P, jnp.float32,
                **({"state_rows": B} if cfg.state_layers else {}))
            lg, caches = prefill(params, cfg, ids[:, :T],
                                 jnp.asarray(valid[:, :T]), caches,
                                 logical_len=T_max, **kw)
        else:
            lg, caches = prefill(params, cfg, ids[:, :T],
                                 jnp.asarray(valid[:, :T]),
                                 init_kv_cache(cfg, B, T_max, jnp.float32))
        out = [lg]
        km = jnp.zeros((B, T_max), bool).at[:, :T].set(valid[:, :T])
        for t, live in ((T, None), (T + 1, jnp.asarray([True, False]))):
            km = km.at[:, t].set(True)
            lg, caches = decode_step(
                params, cfg, ids[:, t], pos[:, t],
                jnp.full((B,), t, jnp.int32), km, caches, live=live, **kw)
            out.append(lg)
        fill = jnp.full((B,), T + 2, jnp.int32)
        lg, caches = decode_verify(
            params, cfg, ids[:, T + 2:T + 2 + K1], pos[:, T + 2:T + 2 + K1],
            fill, km, caches, **kw)
        return out + [lg], caches

    got, got_caches = run()
    monkeypatch.setattr(M, "_run_layers", loop_layers)
    want, want_caches = run()
    _assert_same(got, want)
    _assert_same(got_caches, want_caches)
    if got_caches is not None:      # something was written, in every group
        assert all(np.abs(np.asarray(a)).sum() > 0 or a.shape[0] == 0
                   for a in jax.tree.leaves(got_caches))


_CASES = [
    *(pytest.param(_one_kind_chain, (layout, quant, per_row),
                   id=f"{layout}-{'exact' if quant == 'none' else quant}-"
                      f"{'per_row' if per_row else 'scalar'}")
      for layout in ("contiguous", "paged") for quant in ("none", "int8")
      for per_row in (False, True)),
    *(pytest.param(_two_period_pattern, (model, lora),
                   id=f"{model}-{'lora' if lora else 'base'}")
      for model in ("smallthinker", "lfm2", "trinity")
      for lora in (False, True)),
    *(pytest.param(_every_kind, (kind, cache), id=f"{kind}-{cache}")
      for kind in _KINDS for cache in ("uncached", "contiguous", "paged")),
]


@pytest.mark.parametrize("case, args", _CASES)
def test_the_scanned_layers_are_the_layers_one_by_one(case, args, monkeypatch):
    """`_run_layers` (the scan, the carry, the traced index into the stacks)
    against `loop_layers` substituted for it: see each case's own words."""
    case(monkeypatch, *args)


# --------------------------------------------------------------------- #
# the form of the bf16 attention read, a rule a case
# --------------------------------------------------------------------- #

_ONE = ModelConfig.qwen2_tiny(vocab_size=V)
_PATTERN = ModelConfig.smallthinker_tiny(vocab_size=V, window=8)
_BIG = 4096     # slots: past the contiguous kernels' "auto" threshold

_FORMS = [
    # (id, config, T, what the call sees, form under "pallas", under "xla")
    ("uncached: the flash kernel over the tokens at hand",
     _ONE, 16, dict(cached=False), "flash", "local"),
    ("uncached, one token: nothing for the flash kernel to tile",
     _ONE, 1, dict(cached=False), "local", "local"),
    ("uncached, a pattern model: XLA in blocks of queries (1)",
     _PATTERN, 6, dict(cached=False), "flash", "query_blocks"),
    ("uncached, a window layer past its window: no flash kernel",
     _PATTERN, 16, dict(cached=False, window=8), "query_blocks",
     "query_blocks"),
    ("uncached, a window layer inside its window is a causal one",
     _PATTERN, 8, dict(cached=False, window=8), "flash", "query_blocks"),
    ("verify, pattern, paged: the flash kernel over the pages, or the walk (2)",
     _PATTERN, 4, dict(cached=True, paged=True, verify=True, cache_len=64),
     "paged_flash", "paged_walk"),
    ("verify, pattern, contiguous: the whole cache under the mask (2)",
     _PATTERN, 4, dict(cached=True, verify=True, cache_len=64), "view", "view"),
    ("verify, one kind, paged: the slab-operand k-query kernel (2)",
     _ONE, 4, dict(cached=True, paged=True, verify=True, cache_len=64),
     "paged_verify_slab", "view"),
    ("verify, one kind, contiguous: the k-query kernel",
     _ONE, 4, dict(cached=True, verify=True, cache_len=64), "verify_slab",
     "view"),
    ("verify of one token still goes by the verify bounds",
     _PATTERN, 1, dict(cached=True, paged=True, verify=True, cache_len=64),
     "paged_flash", "paged_walk"),
    ("prefill from slot 0: the flash kernel over the tokens at hand",
     _ONE, 16, dict(cached=True, cache_len=64), "flash", "view"),
    ("prefill, pattern, XLA: the tokens at hand, not the cache (3)",
     _PATTERN, 6, dict(cached=True, paged=True, cache_len=64), "flash",
     "local"),
    ("prefill, a window layer past its window: XLA",
     _PATTERN, 16, dict(cached=True, window=8, cache_len=64), "local", "local"),
    ("decode over pages: the rows' pages read in place",
     _ONE, 1, dict(cached=True, paged=True, decode=True, cache_len=64),
     "paged_decode", "view"),
    ("decode over pages, a window layer: the same kernel, its own bound",
     _PATTERN, 1, dict(cached=True, paged=True, decode=True, window=8,
                       cache_len=64), "paged_decode", "view"),
    ("decode, contiguous: the prefix-bounded kernel",
     _ONE, 1, dict(cached=True, decode=True, cache_len=_BIG), "decode", "view"),
    ("decode, contiguous, one kind under a mesh: shard_map round it (4)",
     _ONE, 1, dict(cached=True, decode=True, mesh=True, cache_len=_BIG),
     "decode", "view"),
    ("decode, contiguous, pattern under a mesh: refused (4)",
     _PATTERN, 1, dict(cached=True, decode=True, mesh=True, cache_len=_BIG),
     "view", "view"),
    ("decode, contiguous, pattern, no mesh: the kernel",
     _PATTERN, 1, dict(cached=True, decode=True, cache_len=_BIG), "decode",
     "view"),
    ("one token and no bounds (a prefill of one): the view",
     _ONE, 1, dict(cached=True, paged=True, cache_len=64), "view", "view"),
]


@pytest.mark.parametrize("config, T, sees, pallas, xla",
                         [pytest.param(*row[1:], id=row[0]) for row in _FORMS])
def test_the_attention_read_takes_the_form_its_rule_names(config, T, sees,
                                                          pallas, xla):
    """`attention_form` under `attention_impl` "pallas" (every kernel's rule
    says yes) and "xla" (none does), and "auto" off the TPU, which is "xla";
    (1)-(4) are the rules that tell a pattern model from one of a single
    kind (ROADMAP, model layer debts)."""
    for impl, want in (("pallas", pallas), ("xla", xla), ("auto", xla)):
        cfg = dataclasses.replace(config, attention_impl=impl)
        assert M.attention_form(cfg, T, **sees) == want, impl


def test_the_kernel_rules_that_bar_a_cache_bar_its_forms():
    """What `use_paged_decode_kernel` refuses, `attention_form` does not
    name: under a mesh the paged reads are XLA's, whatever the impl."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    for config, verify in ((_ONE, "paged_verify_slab"),
                           (_PATTERN, "paged_walk")):
        cfg = dataclasses.replace(config, attention_impl="pallas",
                                  spmd_mesh=mesh)
        sees = dict(cached=True, paged=True, cache_len=64)
        assert M.attention_form(cfg, 1, decode=True, **sees) == "view"
        assert M.attention_form(cfg, 4, verify=True, **sees) == verify


# --------------------------------------------------------------------- #
# the layout of the one-jit rollout's own cache, a term a case (ISSUE 52)
# --------------------------------------------------------------------- #

def _wide(config, **changes):
    """`config` with heads of 128 lanes, what the paged kernels take."""
    return dataclasses.replace(config, head_dim=128, **changes)


_LAYOUTS = [
    # (id, config, the backend, pages of)
    ("a TPU under auto: pages of a read block, read in place",
     _wide(_ONE), "tpu", 128),
    ("pallas, anywhere: the same", _wide(_ONE, attention_impl="pallas"),
     "cpu", 128),
    ("an expert model of one kind of layer: the same",
     _wide(ModelConfig.olmoe_tiny(vocab_size=V)), "tpu", 128),
    ("xla on a TPU: the gathered view would lose to the extents",
     _wide(_ONE, attention_impl="xla"), "tpu", 0),
    ("auto off the TPU is xla", _wide(_ONE), "cpu", 0),
    ("under a mesh GSPMD refuses the kernel", _wide(_ONE), "mesh", 0),
    ("an int8 cache has a read of its own",
     _wide(_ONE, kv_cache_quant="int8"), "tpu", 0),
    ("a latent cache (MLA) reads through XLA",
     ModelConfig.axk1_tiny(vocab_size=V), "tpu", 0),
    ("a pattern model rolls out on the contiguous cache",
     _wide(_PATTERN), "tpu", 0),
    ("a model that keeps a state: the same",
     _wide(ModelConfig.falcon_h1_tiny(vocab_size=V)), "tpu", 0),
    ("lightning and sparse-attention layers: the same",
     _wide(ModelConfig.minicpm_sala_tiny(vocab_size=V)), "tpu", 0),
    ("a model that generates by blocks has no rollout",
     _wide(ModelConfig.sdar_tiny(vocab_size=V)), "tpu", 0),
    ("heads of 64 lanes: the kernels take whole rows of 128",
     dataclasses.replace(_ONE, head_dim=64), "tpu", 0),
    ("heads of 16, under pallas too", dataclasses.replace(
        _ONE, attention_impl="pallas"), "cpu", 0),
]


@pytest.mark.parametrize("config, backend, pages",
                         [pytest.param(*row[1:], id=row[0])
                          for row in _LAYOUTS])
def test_the_rollouts_own_cache_takes_the_layout_its_rule_names(
        config, backend, pages, monkeypatch):
    """`decode_loop_page_size`: pages exactly where a decode step over the
    identity table is `attention_form`'s `"paged_decode"` AND the kernels
    take the pool's geometry; and the loop, its extents and what is
    reported of it all go by it (`sampler._loop_page_size`)."""
    from jax.sharding import Mesh

    from nanorlhf_tpu.sampler import sampler as S
    from nanorlhf_tpu.sampler.sampler import SamplingParams

    if backend == "mesh":
        config = dataclasses.replace(config, spmd_mesh=Mesh(
            np.asarray(jax.devices()[:2]), ("data",)))
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "cpu" if backend == "cpu" else "tpu")
    assert M.decode_loop_page_size(config) == pages
    if pages:
        assert M.attention_form(config, 1, cached=True, paged=True,
                                decode=True, cache_len=768) == "paged_decode"
    assert S._loop_page_size(config, 0) == pages
    assert S._loop_page_size(config, 16) == 16      # the caller's stands
    if config.block_generation:
        return
    sampling = SamplingParams(max_tokens=512)
    assert S.kv_in_place(config, sampling, 64) == int(pages > 0)
    loops = S._read_loops(config, 256, 512)
    # one loop over pages, and wherever the contiguous read bounds itself
    assert (loops == [(768, 512)]) == (
        pages > 0 or config.kv_cache_quant == "int8"
        or bool(config.kv_lora_rank)
        or M.use_decode_kernel(config.attention_impl, 768))
    for other in (dict(spec_k=2), dict(page_size=128, decode_rows=8)):
        assert S.kv_in_place(config, dataclasses.replace(
            sampling, **other), 64) == 0
