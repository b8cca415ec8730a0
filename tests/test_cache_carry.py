"""The stacked KV cache is the layer scan's carry (core/model.py::_run_layers).

The mechanism is pinned here: in the compiled decode loops no instruction
copies a stacked cache array and no `dynamic-update-slice` writes a whole
layer slab into one. With the cache as the scan's xs/ys (before ISSUE 26) XLA
put two whole-stack copies into every decode step. The numbers (the scanned
path against the layers one by one, bit for bit) are
tests/test_layer_runner.py's.
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
