"""The stacked KV cache is the layer scan's carry (core/model.py::_run_layers).

Two things are pinned here:

- the numbers: `prefill`, a chain of `decode_step` and a `decode_verify`
  through the scanned path equal, bit for bit on CPU, an unrolled reference
  in which neither the scan nor the carry exists: a Python loop over layers
  that hands `_layer_body` each layer's own one-layer stack `c[l:l+1]`;
- the mechanism: in the compiled decode loops no instruction copies a
  stacked cache array and no `dynamic-update-slice` writes a whole layer slab
  into one. With the cache as the scan's xs/ys (before ISSUE 26) XLA put two
  whole-stack copies into every decode step.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.core import model as M
from nanorlhf_tpu.sampler.paged import session as S
from nanorlhf_tpu.sampler.sampler import generate_tokens

PAD, EOS = 0, 3


def _config(quant):
    return dataclasses.replace(ModelConfig.qwen2_tiny(vocab_size=128),
                               kv_cache_quant=quant)


@pytest.fixture(scope="module")
def params():
    return init_params(_config("none"), jax.random.PRNGKey(7), jnp.float32)


# --------------------------------------------------------------------- #
# the numbers
# --------------------------------------------------------------------- #

def _unrolled_run_layers(config, params, x, cos, sin, mask, kv_caches=None,
                         cache_index=0, lora_scale=1.0, decode_bounds=None,
                         verify_bounds=None, paged=None):
    """`_run_layers`' cached branch without the scan and without the carry:
    layer l sees only its own one-layer stack, as layer 0 of it."""
    outs = []
    for l in range(kv_caches[0].shape[0]):
        x, new, _ = M._layer_body(
            config, x, jax.tree.map(lambda p: p[l], params["layers"]),
            cos, sin, mask, tuple(c[l:l + 1] for c in kv_caches), cache_index,
            None, lora_scale, decode_bounds=decode_bounds,
            verify_bounds=verify_bounds, paged=paged, layer=0,
        )
        outs.append(new)
    return x, tuple(jnp.concatenate(cs, axis=0) for cs in zip(*outs)), None


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


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("quant", ["none", "int8"], ids=["exact", "int8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_scanned_carry_matches_unrolled_layers(params, monkeypatch, layout,
                                               quant, per_row):
    """Bit for bit with both sides run op by op (`jax.disable_jit`: the scan
    then steps its body in Python, carry and layer index as in the compiled
    loop), because only then do both sides run the same executables. A scan
    body compiled as one computation differs from the same layer run op by op
    (or jitted alone) in the last bit on XLA:CPU (1.2e-7 on these logits:
    other multiply-adds are contracted), whatever the cache does; the
    compiled path is held to that roundoff below, on the exact cache."""
    config = _config(quant)
    with jax.disable_jit():
        got, got_caches = _forward_chain(config, params, layout, per_row)
    compiled, compiled_caches = _forward_chain(config, params, layout, per_row)
    monkeypatch.setattr(M, "_run_layers", _unrolled_run_layers)
    with jax.disable_jit():
        want, want_caches = _forward_chain(config, params, layout, per_row)
    for i, (a, b) in enumerate(zip(got, want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"logits of forward {i}")
    assert len(got_caches) == (4 if quant == "int8" else 2)
    for a, b in zip(got_caches, want_caches):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
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
# the mechanism, on compiled CPU HLO
# --------------------------------------------------------------------- #

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(
    r"^(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}


def _computations(hlo):
    """HLO text -> {computation: [(name, result type, opcode, operands and
    attributes as text)]}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        line = line.strip()
        head = _COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None:
            instr = _INSTR.match(line)
            if instr:
                cur.append(instr.groups())
    return comps


def _reachable(comps, root):
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name not in seen and name in comps:
            seen.add(name)
            for _, _, _, rest in comps[name]:
                todo.extend(_CALLEE.findall(rest))
    return seen


def _shapes(text):
    return [(d, tuple(int(n) for n in dims.split(",") if n))
            for d, dims in _SHAPE.findall(text)]


def hlo_stacks(caches):
    """The stacked cache arrays as HLO writes their types."""
    return {(_HLO_DTYPE[jnp.dtype(c.dtype).name], tuple(c.shape))
            for c in caches}


def decode_loop_reach(hlo):
    """(computations, sorted names of those a decode loop reaches, fusions
    included). A decode loop is a `while` whose body holds the layer scan's
    `while`."""
    comps = _computations(hlo)
    assert comps, "no computation parsed from the HLO text"
    bodies = {re.search(r"body=%?([\w.\-]+)", rest).group(1)
              for instrs in comps.values() for _, _, op, rest in instrs
              if op == "while"}
    loops = [b for b in bodies
             if any(op == "while" for _, _, op, _ in comps[b])]
    assert loops, "no decode loop (a while around the layer scan) found"
    return comps, sorted(set().union(*(_reachable(comps, b) for b in loops)))


def decode_loop_offences(hlo, stacks, slabs_too=False):
    """What the decode loops of a compiled program do to whole cache stacks.

    A decode loop is a `while` whose body holds the layer scan's `while`.
    Returns (offences, writes): every `copy` that produces a stack and every
    `dynamic-update-slice` into a stack whose update is as large as a layer
    slab, found in anything the loop bodies reach (fusions included); with
    `slabs_too` also every instruction outside a fusion whose result is a
    layer slab (a slab set down in memory on its way to attention); and the
    number of `dynamic-update-slice`s into a stack or a reshaped view of
    one, so a caller can tell that the parser saw the writes at all."""
    comps, reached = decode_loop_reach(hlo)
    sizes = {(d, int(np.prod(shape))): int(np.prod(shape[1:]))
             for d, shape in stacks}
    slabs = {(d, slab) for (d, _), slab in sizes.items()}
    fused = {callee for instrs in comps.values()
             for _, _, op, rest in instrs if op == "fusion"
             for callee in re.findall(r"calls=%?([\w.\-]+)", rest)}
    offences, writes = [], 0
    for name in reached:
        types = {instr: result for instr, result, _, _ in comps[name]}
        for _, result, op, rest in comps[name]:
            if result.startswith("("):
                continue
            dtype, shape = _shapes(result)[0]
            if op == "copy" and (dtype, shape) in stacks:
                offences.append(f"{name}: {result} copy")
            if (slabs_too and name not in fused
                    and op not in ("parameter", "get-tuple-element")
                    and (dtype, int(np.prod(shape))) in slabs):
                offences.append(f"{name}: {result} {op}, a layer slab")
            slab = sizes.get((dtype, int(np.prod(shape))))
            if op == "dynamic-update-slice" and slab is not None:
                writes += 1
                # second operand; its type is printed inline or with it
                operand = rest.split(", ")[1].split()
                update = _shapes(types.get(operand[-1].lstrip("%"),
                                           operand[0]))[0][1]
                if int(np.prod(update)) >= slab:
                    offences.append(f"{name}: {result} dynamic-update-slice "
                                    f"of {list(update)}")
    return offences, writes


def _generate_hlo(params, quant, page_size):
    config = _config(quant)
    ids = jnp.asarray([[PAD, 5, 6, 7, 8, 9], [9, 10, 11, 12, 13, 14],
                       [PAD, PAD, 5, 6, 7, 8]], jnp.int32)
    B, Tp, max_tokens, fanout = 3, 6, 10, 2
    T_max = Tp + max_tokens
    lowered = generate_tokens.lower(
        params, config, ids, ids != PAD, jax.random.PRNGKey(0),
        max_tokens=max_tokens, eos_token_id=EOS, pad_token_id=PAD,
        temperature=0.9, capture_logprobs=True, prompt_fanout=fanout,
        page_size=page_size)
    if page_size:
        cache = M.init_paged_kv_cache(
            config, B * fanout * -(-T_max // page_size), page_size,
            jnp.float32)
    else:
        cache = M.init_kv_cache(config, B * fanout, T_max, jnp.float32)
    return lowered.compile().as_text(), cache


def _chunk_hlo(params):
    """The session's decode chunk (`_chunk_loop` under `_decode_chunk`):
    per-row slots, a live block table, rows at different depths."""
    config = _config("none")
    R, Tp, max_tokens, P = 3, 6, 10, 4
    T_max = Tp + max_tokens
    nb = -(-T_max // P)
    cache = M.init_paged_kv_cache(config, R * nb + 2, P, jnp.float32)
    state = (jnp.int32(0), jnp.zeros((R, max_tokens), jnp.int32),
             jnp.zeros((R, max_tokens), jnp.float32), cache,
             jnp.zeros((R, T_max), bool).at[:, :Tp].set(True),
             jnp.zeros((R,), bool), jnp.full((R,), 5, jnp.int32),
             jnp.asarray([1, 2, 3], jnp.int32), jnp.full((R,), Tp, jnp.int32),
             jax.random.PRNGKey(0))
    table = jnp.arange(R * nb, dtype=jnp.int32).reshape(R, nb)
    lowered = S._decode_chunk.lower(
        params, config, state, table, Tp=Tp, max_tokens=max_tokens,
        page_size=P, sync_every=4, eos_token_id=EOS, pad_token_id=PAD,
        temperature=0.9, top_p=0.95, greedy=False, lora_scale=1.0, top_k=64,
        capture_logprobs=True, approx_top_k=True)
    return lowered.compile().as_text(), cache


@pytest.mark.parametrize("loop", ["generate-contiguous", "generate-paged",
                                  "generate-int8", "session-chunk"])
def test_decode_loop_moves_no_whole_cache(params, loop):
    if loop == "session-chunk":
        hlo, cache = _chunk_hlo(params)
    else:
        hlo, cache = _generate_hlo(
            params, "int8" if loop == "generate-int8" else "none",
            4 if loop == "generate-paged" else 0)
    # XLA:CPU has no bf16 dynamic-update-slice: it widens the whole array to
    # f32 and back around one, in any program, so the int8 cache's bf16 scale
    # stacks are out of this backend's reach; its s8 value stacks are held
    # (tests/test_chip_compile.py holds all four to the TPU compiler)
    stacks = {s for s in hlo_stacks(cache) if s[0] != "bf16"}
    offences, writes = decode_loop_offences(hlo, stacks)
    assert not offences, "\n".join(offences)
    if loop in ("generate-contiguous", "generate-int8"):
        # the scalar-slot write is a dynamic-update-slice (per-row and paged
        # writes lower to scatters): the parser has seen K's and V's
        assert writes >= 2
