"""The chip's compiler on the main path's kernels, and chip_smoke's gate.

Every Pallas kernel `attention_impl="auto"` / `fused_logprob_impl="auto"` can
select is lowered NON-interpreted and compiled for a described (not attached)
`v5e:2x2` device at Qwen2.5-1.5B geometry — what interpret mode on the CPU
cannot show: a block the Mosaic tiling rule refuses, a kernel over its VMEM
budget. A compile that passes is not a chip run; `chip_smoke.py` is that.
`ops/attention._interpret_default()` asks the backend, which is the CPU here,
so the cases steer it with its own `NANORLHF_PALLAS_INTERPRET=0` switch.
Skipped where the TPU compiler cannot describe the topology. The persistent
compile cache is off around the compiles: an entry written for a described
device cannot be read back without one, and the next run would warn.
"""

import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Qwen2.5-1.5B: 12 Q / 2 KV heads of 128, hidden 1536, vocab 151936 (tied)
H, KV, HD, D, V = 12, 2, 128, 1536, 151936
PAGE = 128


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # only the compiler is used, no chip: pytest-xdist workers may each load
    # libtpu, which otherwise admits one process per machine (its lockfile)
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return list(topo.devices)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """interpret=False through the kernels' own switch; persistent cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("NANORLHF_PALLAS_INTERPRET", "0")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _flash(B=4, T=2048):
    from nanorlhf_tpu.ops.attention import flash_attention

    args = [((B, H, T, HD), jnp.bfloat16), ((B, KV, T, HD), jnp.bfloat16),
            ((B, KV, T, HD), jnp.bfloat16), ((B, T), jnp.bool_)]
    return flash_attention, args


def _flash_bwd():
    fn, args = _flash()

    def grads(q, k, v, valid):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v, valid).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return grads, args


def _decode(B=32, T=2048):
    from nanorlhf_tpu.ops.decode_attention import decode_attention

    return decode_attention, [
        ((B, H, HD), jnp.bfloat16), ((B, KV, T, HD), jnp.bfloat16),
        ((B, KV, T, HD), jnp.bfloat16), ((B,), jnp.int32), ((B,), jnp.int32)]


def _decode_q8(B=32, T=2048):
    from nanorlhf_tpu.ops.decode_attention import decode_attention_q8

    return decode_attention_q8, [
        ((B, H, HD), jnp.bfloat16),
        ((B, KV, T, HD), jnp.int8), ((B, KV, 8, T), jnp.bfloat16),
        ((B, KV, T, HD), jnp.int8), ((B, KV, 8, T), jnp.bfloat16),
        ((B,), jnp.int32), ((B,), jnp.int32)]


def _verify(B=32, T=2048, Tq=4):
    from nanorlhf_tpu.ops.decode_attention import decode_verify_attention

    return decode_verify_attention, [
        ((B, H, Tq, HD), jnp.bfloat16), ((B, KV, T, HD), jnp.bfloat16),
        ((B, KV, T, HD), jnp.bfloat16), ((B,), jnp.int32), ((B,), jnp.int32)]


def _paged_decode(B=64, nb=12, L=28, N=807, H=H, KV=KV):
    """The in-place read at the serving cell's shape: 64 rows of 12 blocks
    over the whole stack of 28 x 807 pages, the layer a scalar. (The other
    served geometries, whose buffer slots, query tiles and batched products
    differ: Trinity's 8 KV heads and items of two pages, SDAR's block read
    with 32 query rows a KV head in two tiles, OLMoE's 16 heads a page.)"""
    from nanorlhf_tpu.ops.decode_attention import (
        paged_decode_attention, paged_decode_plan, paged_pages_per_item,
    )

    def fn(q, k_pool, v_pool, layer, table, start, filled, live):
        plan = paged_decode_plan(
            table, start, filled, page_size=PAGE, num_pages=N,
            pages_per_item=paged_pages_per_item(k_pool), live=live)
        return paged_decode_attention(q, k_pool, v_pool, layer, plan)

    return fn, [
        ((B, H, HD), jnp.bfloat16), ((L, N, KV, PAGE, HD), jnp.bfloat16),
        ((L, N, KV, PAGE, HD), jnp.bfloat16), ((), jnp.int32),
        ((B, nb), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.bool_)]


def _paged_decode_q8(B=32, nb=16):
    from nanorlhf_tpu.ops.decode_attention import paged_decode_attention_q8

    N = B * nb
    return paged_decode_attention_q8, [
        ((B, H, HD), jnp.bfloat16),
        ((N, KV, PAGE, HD), jnp.int8), ((N, KV, 8, PAGE), jnp.bfloat16),
        ((N, KV, PAGE, HD), jnp.int8), ((N, KV, 8, PAGE), jnp.bfloat16),
        ((B, nb), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32)]


def _paged_verify(B=32, nb=16, Tq=4):
    from nanorlhf_tpu.ops.decode_attention import paged_decode_verify_attention

    N = B * nb
    return paged_decode_verify_attention, [
        ((B, H, Tq, HD), jnp.bfloat16), ((N, KV, PAGE, HD), jnp.bfloat16),
        ((N, KV, PAGE, HD), jnp.bfloat16), ((B, nb), jnp.int32),
        ((B,), jnp.int32), ((B,), jnp.int32)]


def _fused(R=2048):
    """Tied layout: the [V, D] embedding leaf, `transposed=True`."""
    from nanorlhf_tpu.ops.fused_logprob import fused_logprob

    def fn(h, w, labels):
        return fused_logprob(h, w, labels, 0.9, impl="pallas",
                             with_entropy=True, transposed=True)

    return fn, [((R, D), jnp.bfloat16), ((V, D), jnp.bfloat16),
                ((R,), jnp.int32)]


def _fused_bwd():
    """The op's backward is a lax chunk scan; the Pallas forward it
    differentiates through stays in the program (its output is the loss)."""
    fn, args = _fused()

    def grads(h, w, labels):
        return jax.grad(lambda h, w: (fn(h, w, labels)[0] ** 2).sum(),
                        argnums=(0, 1))(h, w)

    return grads, args


CASES = {"flash_fwd": _flash, "flash_bwd": _flash_bwd, "decode": _decode,
         "decode_q8": _decode_q8, "verify": _verify,
         "paged_decode": _paged_decode,
         "paged_decode_trinity": functools.partial(
             _paged_decode, B=32, nb=72, L=4, N=1344, H=48, KV=8),
         "paged_decode_sdar_block": functools.partial(
             _paged_decode, B=64, nb=25, L=7, N=1625, H=128, KV=4),
         "paged_decode_olmoe": functools.partial(
             _paged_decode, B=64, nb=6, L=16, N=400, H=16, KV=16),
         "paged_decode_q8": _paged_decode_q8,
         "paged_verify": _paged_verify, "fused_fwd": _fused,
         "fused_bwd": _fused_bwd}


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(case, v5e, compiled_kernels):
    fn, args = CASES[case]()
    one_chip = SingleDeviceSharding(v5e[0])
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fused_kernel_is_not_partitionable_so_auto_takes_lax_under_a_mesh(
        v5e, compiled_kernels):
    """Why trainer.fused_logprob_impl routes "auto" to the lax scan on a
    multi-device mesh: the TPU compiler refuses a Mosaic kernel it would have
    to partition. Interpret mode and the CPU backend never show this."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig
    from nanorlhf_tpu.trainer import RLConfig
    from nanorlhf_tpu.trainer.trainer import fused_logprob_impl

    mesh = Mesh(np.asarray(v5e).reshape(2, 2), ("fsdp", "tensor"))
    fn, args = _fused(R=256)
    shardings = [P("fsdp", None), P("tensor", "fsdp"), P("fsdp")]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, sp))
             for (shape, dtype), sp in zip(args, shardings)]
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(fn).lower(*specs)

    cfg, mcfg = RLConfig(), ModelConfig.qwen2_tiny()
    assert fused_logprob_impl(cfg, mcfg) == "auto"
    on_mesh = dataclasses.replace(mcfg, spmd_mesh=mesh)
    assert fused_logprob_impl(cfg, on_mesh) == "lax"
    cfg.fused_logprob_impl = "pallas"   # an explicit choice is passed through
    assert fused_logprob_impl(cfg, on_mesh) == "pallas"


def _shapes_on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _decode_loop(case, one_chip, layers=2):
    """(lowered program, stacked cache shapes) of a decode loop at
    Qwen2.5-1.5B widths, two layers deep: the benchmark's rollout (16 prompts
    x 4 samples, 256 + 512 slots: since ISSUE 52 over 64 x 6 pages of 128
    wherever `core/model.decode_loop_page_size` says so, which with
    `default_backend` answered as `tpu` is here), the same under
    `attention_impl="xla"` (`rollout_xla`: the contiguous cache and its
    extents on a TPU too) and over an int8 cache, and the
    serving session's chunk (64 rows, 807 pages of 128); `rollout_olmoe` is
    the rollout at OLMoE-1B-7B's widths (64 experts, 8 a token).
    `serving_admission` is the session's largest suffix prefill (1024
    tokens) over the same pool."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.sampler.sampler import generate_tokens

    cfg = ModelConfig(
        vocab_size=V, hidden_size=D, intermediate_size=8960,
        num_hidden_layers=layers, num_attention_heads=H,
        num_key_value_heads=KV,
        attention_impl="xla" if case == "rollout_xla" else "auto",
        kv_cache_quant="int8" if case == "rollout_int8" else "none")
    if case == "rollout_olmoe":
        cfg = dataclasses.replace(ModelConfig.olmoe_1b_7b(), num_hidden_layers=2)
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)),
        one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    if case in ("serving_chunk", "serving_admission"):
        R, Tp, new, pages = 64, 1024, 512, 807
        cache = jax.eval_shape(
            lambda: M.init_paged_kv_cache(cfg, pages, PAGE, jnp.bfloat16))
    if case == "serving_admission":
        from nanorlhf_tpu.serving.radix import suffix_logits

        lowered = suffix_logits.lower(
            params, cfg, spec((1, Tp), jnp.int32), spec((1, Tp), jnp.int32),
            spec((1,), jnp.int32), spec((), jnp.int32),
            spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip),
            spec(((Tp + new) // PAGE,), jnp.int32), page_size=PAGE,
            lora_scale=1.0)
        return lowered, cache
    if case == "serving_chunk":
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        lowered = session._serving_chunk.lower(
            params, cfg, state, spec((R, (Tp + new) // PAGE), jnp.int32),
            spec((R,), jnp.float32), spec((R,), jnp.float32),
            spec((R,), jnp.bool_), spec((R,), jnp.int32), Tp=Tp,
            max_tokens=new, page_size=PAGE, sync_every=4, eos_token_id=3,
            pad_token_id=0, temperature=1.0, top_p=1.0, greedy=False,
            lora_scale=1.0, top_k=64, capture_logprobs=False,
            approx_top_k=True)
        return lowered, cache
    B, fanout, Tp, new = 16, 4, 256, 512
    lowered = generate_tokens.lower(
        params, cfg, spec((B, Tp), jnp.int32), spec((B, Tp), jnp.bool_), key,
        max_tokens=new, eos_token_id=3, pad_token_id=0, temperature=0.9,
        capture_logprobs=True, prompt_fanout=fanout)
    page = M.decode_loop_page_size(cfg)
    cache = jax.eval_shape(
        lambda: M.init_paged_kv_cache(
            cfg, B * fanout * (Tp + new) // page, page, jnp.bfloat16) if page
        else M.init_kv_cache(cfg, B * fanout, Tp + new, jnp.bfloat16))
    return lowered, cache


@pytest.mark.parametrize("case", ["rollout", "rollout_xla", "rollout_int8",
                                  "serving_chunk"])
def test_decode_loop_carries_the_cache_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """What tests/test_cache_carry.py holds XLA:CPU to, asked of the chip's
    compiler at the benchmark's shapes: inside the decode loop no copy of a
    cache stack and no slab written back into one, and on the XLA attention
    path no layer slab set down in memory between the stack and the
    attention matmuls either (both reads fuse; the first compile of ISSUE 26
    sliced and relaid V per layer, 18 % of the step). The model asks
    `jax.default_backend()` for its TPU choices, which is the CPU here, so
    the test answers for it; the int8 case then takes the q8 Pallas kernel,
    whose operands are slabs by construction. The serving chunk reads its
    pages in place (ISSUE 28): the kernel is in the loop, and nothing the
    loop reaches, fused or not, has the shape of the gathered view's pieces
    (the rows' 64 x 12 = 768 pages gathered, their transpose into row
    order) or of a layer's slab of the pool. So does the rollout since
    ISSUE 52, over its own 64 x 6 pages under the identity table (`rollout`);
    the contiguous cache and its three extents (ISSUE 33) are what a TPU
    runs under `"xla"` (`rollout_xla`) and under a mesh."""
    from test_cache_carry import (
        _CALLEE, _shapes, decode_loop_offences, decode_loop_reach, hlo_stacks,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, cache = _decode_loop(case, SingleDeviceSharding(v5e[0]))
    hlo = lowered.compile().as_text()
    offences, _ = decode_loop_offences(
        hlo, hlo_stacks(cache), slabs_too=case != "rollout_int8")
    assert not offences, "\n".join(offences)
    if case == "rollout":
        # ISSUE 52: ONE decode loop over the page pool; the in-place read's
        # kernel is in it; the write is a slice of each leaf where it lies
        # (`_identity_slot_write`: through a view, not the live-row kernel,
        # no scatter); no copy of a pool leaf anywhere in the program, the
        # prefill's fan-out included; and nothing the loop reaches has the
        # shape of a gathered view (the rows' 384 pages, their transpose
        # into rows of 768 slots) or of a layer's slab of the pool
        assert hlo_stacks(cache) == {("bf16", (2, 384, KV, PAGE, HD))}
        comps, reached = decode_loop_reach(hlo)
        assert _decode_loops(comps) == 1
        reads = [name for name in reached
                 for instr, _, op, rest in comps[name]
                 if op == "custom-call" and instr.startswith("attn.read")
                 and "tpu_custom_call" in rest]
        assert len(reads) == 1, reads
        offences, writes = decode_loop_offences(hlo, hlo_stacks(cache),
                                                slabs_too=True)
        assert not offences and writes >= 2, (offences, writes)
        assert _live_row_write_calls(hlo) == 0
        assert not any(op == "scatter" for name in reached
                       for _, _, op, _ in comps[name])
        view = {(384, KV, PAGE, HD), (64, KV, 6, PAGE, HD),
                (64, 6, KV, PAGE, HD), (64, KV, 768, HD)}
        made = [f"{name}: {result} {op}" for name in reached
                for _, result, op, _ in comps[name]
                if not result.startswith("(")
                and _shapes(result) and _shapes(result)[0][1] in view]
        assert not made, "\n".join(made)
        copies = [f"{name}: {result}" for name, instrs in comps.items()
                  for _, result, op, _ in instrs if op.startswith("copy")
                  and _shapes(result)[:1] == list(hlo_stacks(cache))]
        assert not copies, "\n".join(copies)
        # ISSUE 60: 64 rows take their 64 candidates of `approx_max_k`'s
        # 9,600 by selection, so the program sorts nothing wider than 64
        # (the aggregation's `sort f32[64,9600]` was 0.39 ms a step)
        sorted_ = [_shapes(result) for instrs in comps.values()
                   for _, result, op, _ in instrs if op == "sort"]
        assert sorted_ and all(d <= 64 for shapes in sorted_
                               for _, dims in shapes for d in dims), sorted_
    if case == "rollout_xla":
        # ISSUE 33: three decode loops, one an extent of the cache read; each
        # loop's QK fusion reads its own extent of the stack in place (no
        # slab of any extent set down on the way: as a static slice BEHIND
        # the layer's dynamic one the whole slab was), and no copy of the
        # stack anywhere in the program, between the loops included
        comps, reached = decode_loop_reach(hlo)
        fused = {callee for instrs in comps.values()
                 for _, _, op, rest in instrs if op == "fusion"
                 for callee in _CALLEE.findall(rest)}
        scores, slabs = set(), []
        for name in reached:
            for _, result, op, _ in comps[name]:
                if result.startswith("(") or name in fused:
                    continue
                shape = _shapes(result)[0][1] if _shapes(result) else ()
                if op == "fusion" and shape[:3] == (64, KV, H // KV):
                    scores.add(shape[3])
                if (shape[:2] == (64, KV) and shape[3:] == (HD,)
                        and shape[2] >= 128):
                    slabs.append(f"{name}: {result} {op}")
        # (PV's result has the same leading axes, HD wide)
        assert scores - {HD} == {512, 640, 768}, scores
        assert not slabs, "\n".join(slabs)
        copies = [f"{name}: {result}" for name, instrs in comps.items()
                  for _, result, op, _ in instrs if op == "copy"
                  and _shapes(result)[0] in hlo_stacks(cache)]
        assert not copies, "\n".join(copies)
    if case == "serving_chunk":
        comps, reached = decode_loop_reach(hlo)
        assert any(op == "custom-call" and "tpu_custom_call" in rest
                   for name in reached for _, _, op, rest in comps[name])
        view = {(768, KV, PAGE, HD), (64, KV, 12, PAGE, HD),
                (807, KV, PAGE, HD)}
        made = [f"{name}: {result} {op}" for name in reached
                for _, result, op, _ in comps[name]
                if not result.startswith("(")
                and _shapes(result) and _shapes(result)[0][1] in view]
        assert not made, "\n".join(made)


@pytest.mark.parametrize("case", ["serving_chunk", "serving_admission"])
def test_session_program_writes_the_page_pool_where_it_lies_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 30, asked of the chip's compiler at the `serve-1.5b-chat` cell's
    shapes (Qwen2.5-1.5B whole, 64 rows, 807 pages of 128: each pool leaf
    `bf16[28,807,2,128,128]`, 1.48 GB): the session's chunk and its largest
    admission forward DONATE the pool (`utils/donation.jit_donating`, decided
    from the described devices the arguments name), so the compiled module
    aliases both pool leaves from its parameters to its results and no `copy`
    of a leaf is left anywhere in it. Without the donation each call kept its
    argument alive and built the result beside it: one copy of each leaf per
    call, 21 % of the device's busy time in that cell (ledger, PR 29)."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, cache = _decode_loop(case, SingleDeviceSharding(v5e[0]),
                                  layers=28)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    (pool,) = hlo_stacks(cache)
    assert pool == ("bf16", (28, 807, 2, 128, 128))
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo.splitlines()[0])}
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo, re.M).group(1)
    leaves = {int(rest.split(")")[0]) for _, result, op, rest in comps[entry]
              if op == "parameter" and _shapes(result)[:1] == [pool]}
    assert len(leaves) == 2 and leaves <= aliased, (leaves, aliased)
    pool_bytes = 2 * 2 * int(np.prod(pool[1]))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and pool in _shapes(result)]
    assert not copies, "\n".join(copies)
    # ISSUE 41: what writes into a leaf is the one thing that produces an
    # array of its size. The chunk's layer scan holds ONE call of the
    # live-row kernel (ops/paged_cache_write: K and V, outputs aliased to
    # its pool operands, named by its scope) where it held two scatters of
    # 64 x 2 rows run over a `[5784576, 128]` view of the leaf; the
    # admission's 1,024 tokens go as two scatters of whole pages into the
    # leaf itself, under `attn.write`
    writes = _leaf_sized_results(comps, [pool])
    assert not re.findall(r"bf16\[5784576,128\]", hlo)
    if case == "serving_chunk":
        assert writes == ["custom-call"], writes
        assert _live_row_write_calls(hlo) == 1
    else:
        assert writes == ["scatter"] * 2, writes
        assert hlo.count("/attn.write/scatter") >= 2


def test_moe_decode_step_is_a_grouped_matmul_over_the_stack_in_place_on_v5e(
        v5e, compiled_kernels, monkeypatch):
    """The rollout at OLMoE's widths (the `grpo-olmoe-r512` cell's shapes,
    two layers), asked of the chip's compiler: the three expert matmuls of
    each layer are the grouped-matmul kernel `auto` takes on a TPU (`%gmm`,
    megablox's, compiled here with its tiles at these widths), not a dense
    product over all 64 experts for every row; no layer's
    `[64, 2048, 1024]` slice of an expert stack is copied out for it (a
    custom call's operands are buffers: sliced by the layer scan, each
    kernel was copied in every layer of every step, three times the bytes
    the step has to move; ops/moe.py); and the KV cache stack is carried in
    place as in the dense rollout."""
    import re

    from test_cache_carry import decode_loop_offences, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, cache = _decode_loop("rollout_olmoe", SingleDeviceSharding(v5e[0]))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    offences, writes = decode_loop_offences(hlo, hlo_stacks(cache),
                                            slabs_too=True)
    assert not offences and writes >= 2, (offences, writes)
    # ISSUE 52: its cache is 64 x 6 pages of 16 heads, read in place by the
    # kernel inside ONE decode loop and written by slice; no copy of a leaf
    from test_cache_carry import _shapes, decode_loop_reach

    assert hlo_stacks(cache) == {("bf16", (2, 384, 16, PAGE, HD))}
    comps, reached = decode_loop_reach(hlo)
    reads = [name for name in reached for instr, _, op, _ in comps[name]
             if op == "custom-call" and instr.startswith("attn.read")]
    assert len(reads) == 1, reads      # one layer scan: one loop (an expert
    assert _live_row_write_calls(hlo) == 0      # layer has loops of its own)
    copies = [f"{name}: {result}" for name, instrs in comps.items()
              for _, result, op, _ in instrs if op.startswith("copy")
              and _shapes(result)[:1] == list(hlo_stacks(cache))]
    assert not copies, "\n".join(copies)
    # prefill + decode loop bodies, three kernels each, under one layer scan
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 6
    expert_shapes = r"bf16\[(?:\d+,)?64,(?:2048,1024|1024,2048)\]"
    made = [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(r"= " + expert_shapes, ln)
            and not re.search(r" (parameter|get-tuple-element|bitcast)\(", ln)]
    assert not made, "an expert stack or slab is produced, not read:\n" + "\n".join(made)
    # the cost analysis counts a loop's body once: one layer of the prefill
    # (16 x 256 tokens) and of a decode step (64 rows), 8 experts a token.
    # The whole program is 1.45 x that (attention, head); a dense product
    # over all 64 experts would be 8 x the routed part
    flops = compiled.cost_analysis()["flops"]
    routed = (16 * 256 + 64) * 8 * 3 * 2 * 2048 * 1024
    assert routed < flops < 2.5 * routed, (flops, routed)


def _decode_loops(comps) -> int:
    """Decode loops of a compiled module: `while`s whose body holds the
    layer scan's `while` (tests/test_cache_carry.decode_loop_reach)."""
    import re

    bodies = {re.search(r"body=%?([\w.\-]+)", rest).group(1)
              for instrs in comps.values() for _, _, op, rest in instrs
              if op == "while"}
    return sum(any(op == "while" for _, _, op, _ in comps[b]) for b in bodies)


def _live_row_write_calls(hlo: str) -> int:
    """Calls of ops/paged_cache_write in a compiled module: custom calls
    named by their scope, `%attn.write*`, that return a K and a V leaf
    aliased to their operands."""
    import re

    return len(re.findall(
        r"%attn\.write[\w.]* = \(bf16\[[\d,]+\]\S*, bf16\[[\d,]+\]\S*\) "
        r"custom-call\(.*output_to_operand_aliasing=", hlo))


def _unfused_results(comps):
    """`(computation, name, result type, opcode, rest)` of every instruction
    of a compiled module that stands OUTSIDE the fused computations and makes
    something: parameters, bitcasts, tuple reads and control flow apart."""
    import re

    fused = {re.search(r"calls=%?([\w.\-]+)", rest).group(1)
             for instrs in comps.values() for _, _, op, rest in instrs
             if op == "fusion"}
    for name, instrs in comps.items():
        if name in fused:
            continue
        for instr, result, op, rest in instrs:
            if op not in ("parameter", "bitcast", "get-tuple-element", "while",
                          "tuple", "conditional", "call"):
                yield name, instr, result, op, rest


def _leaf_sized_results(comps, pools) -> list:
    """The opcode (a fusion's: its root's) of every instruction of a compiled
    module that produces ONE array with as many elements as a pool leaf,
    parameters, bitcasts and tuple reads apart; a call that returns several
    such arrays (the live-row kernel's K and V) counts once. What is in the
    list MOVES a leaf's worth of bytes or writes into a leaf in place."""
    import re

    from test_cache_carry import _shapes

    sizes = {int(np.prod(shape)) for _, shape in pools}
    roots = {name: instrs[-1][2] for name, instrs in comps.items()}
    made = []
    for _, _, result, op, rest in _unfused_results(comps):
        if not any(int(np.prod(s[1])) in sizes for s in _shapes(result)):
            continue
        if op == "fusion":
            op = roots[re.search(r"calls=%?([\w.\-]+)", rest).group(1)]
        made.append(op)
    return made


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_chunk"])
def test_axk1_session_programs_keep_the_latent_pool_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 31, asked of the chip's compiler at the `serve-axk1-docqa`
    cell's shapes (A.X-K1's published widths as one chip of sixteen holds
    them, the dense layer + two expert layers; 32 rows, 2,720 pages of 128):
    the session's decode chunk and its 1,024-token KV-only prefill chunk
    alias BOTH leaves of the latent pool (`c_kv` `bf16[3,2720,1,128,512]`,
    `k_rope` `bf16[3,2720,1,64,128]`, two rotary keys a row) from their
    parameters to their results, and no `copy` of a leaf is left in the
    module. As ONE 576-wide array the pool was relaid whole on the way in
    and on the way out of every program (core/mla.py). The expert matmuls
    are the grouped-matmul kernel over the 12 held experts' stack."""
    import dataclasses
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(v5e[0])
    cfg = dataclasses.replace(ModelConfig.axk1(), num_hidden_layers=3,
                              vocab_size=20480, experts_held=12)
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)
    assert params["layers"]["experts"]["up_proj"]["kernel"].shape == (
        2, 12, 7168, 2048)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, pages, chunk = 32, 8192, 512, 2720, 1024
    nb = (Tp + new) // PAGE
    cache = jax.eval_shape(
        lambda: M.init_paged_kv_cache(cfg, pages, PAGE, jnp.bfloat16))
    pools = hlo_stacks(cache)
    assert set(pools) == {("bf16", (3, pages, 1, PAGE, 512)),
                          ("bf16", (3, pages, 1, PAGE // 2, 128))}
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        lowered = session._serving_chunk.lower(
            params, cfg, state, spec((R, nb), jnp.int32),
            spec((R,), jnp.float32), spec((R,), jnp.float32),
            spec((R,), jnp.bool_), spec((R,), jnp.int32), Tp=Tp,
            max_tokens=new, page_size=PAGE, sync_every=4, eos_token_id=1,
            pad_token_id=0, temperature=1.0, top_p=1.0, greedy=False,
            lora_scale=1.0, top_k=64, capture_logprobs=False,
            approx_top_k=True)
    else:
        lowered = session._prefill_chunk_fwd.lower(
            params, cfg, spec((1, chunk), jnp.int32), spec((1, chunk), jnp.int32),
            spec((1,), jnp.int32), spec((1, Tp + new), jnp.bool_),
            _shapes_on(cache, one_chip), spec((nb,), jnp.int32),
            page_size=PAGE, lora_scale=1.0)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo.splitlines()[0])}
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo, re.M).group(1)
    leaves = {int(rest.split(")")[0]) for _, result, op, rest in comps[entry]
              if op == "parameter" and _shapes(result)[:1]
              and _shapes(result)[0] in pools}
    assert len(leaves) == 2 and leaves <= aliased, (leaves, aliased)
    pool_bytes = 2 * sum(int(np.prod(shape)) for _, shape in pools)
    assert pool_bytes == 3 * pages * PAGE * 576 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(pools)]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _smallthinker_session_program(case, v5e, layers=4):
    """The `serve-smallthinker-longshort` cell's decode chunk, KV-only
    prefill piece or suffix forward, lowered for a described v5e at
    SmallThinker's published widths and `layers` layers (periods of four):
    `(compiled, cache shapes)`."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.sampler.paged.pages import ring_blocks

    one_chip = SingleDeviceSharding(v5e[0])
    layout = (0, 1, 1, 1) * (layers // 4)
    cfg = dataclasses.replace(
        ModelConfig.smallthinker_21b(), num_hidden_layers=layers,
        sliding_window_layout=layout, rope_layout=layout)
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = 32, 14336, 2048, 1024
    nb = (Tp + new) // PAGE
    ring = ring_blocks(cfg.sliding_window, PAGE, chunk)
    pages = (R * nb + nb, R * ring)
    assert (nb, ring, pages) == (128, 42, (4224, 1344))
    cache = jax.eval_shape(
        lambda: M.init_paged_kv_cache(cfg, pages, PAGE, jnp.bfloat16))
    tables = (spec((R, nb), jnp.int32),) * 2
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        lowered = session._serving_chunk.lower(
            params, cfg, state, tables, spec((R,), jnp.float32),
            spec((R,), jnp.float32), spec((R,), jnp.bool_),
            spec((R,), jnp.int32), Tp=Tp, max_tokens=new, page_size=PAGE,
            sync_every=4, eos_token_id=1, pad_token_id=0, temperature=1.0,
            top_p=1.0, greedy=False, lora_scale=1.0, top_k=64,
            capture_logprobs=False, approx_top_k=True)
    elif case == "suffix":
        from nanorlhf_tpu.serving.radix import suffix_logits

        lowered = suffix_logits.lower(
            params, cfg, spec((1, chunk), jnp.int32), spec((1, chunk), jnp.int32),
            spec((1,), jnp.int32), spec((), jnp.int32),
            spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip),
            (spec((nb,), jnp.int32),) * 2, page_size=PAGE, lora_scale=1.0)
    else:
        lowered = session._prefill_chunk_fwd.lower(
            params, cfg, spec((1, chunk), jnp.int32), spec((1, chunk), jnp.int32),
            spec((1,), jnp.int32), spec((1, Tp + new), jnp.bool_),
            _shapes_on(cache, one_chip), (spec((nb,), jnp.int32),) * 2,
            page_size=PAGE, lora_scale=1.0)
    return lowered.compile(), cache


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_chunk", "suffix"])
def test_smallthinker_session_programs_keep_both_pools_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 34, asked of the chip's compiler at the
    `serve-smallthinker-longshort` cell's shapes (SmallThinker's published
    widths, one period of four layers; 32 rows of 16,384 slots, pages of
    128, a ring of 42 window pages a row): the session's decode chunk and
    its 1,024-token KV-only prefill chunk alias all FOUR leaves of the page
    pool of two kinds (global `bf16[1,4224,4,128,128]` x (k, v), window
    `bf16[3,1344,4,128,128]` x (k, v)) from their parameters to their
    results and leave no `copy` of a leaf in the module. The decode chunk
    reads them through the in-place kernel once a layer, named by its
    kind's scope (`%attn.global*`, `%attn.window*`: what
    benchmark/harness/attn_trace.py finds in the device trace); the expert
    matmuls are the grouped-matmul kernel. The prefill chunk (ISSUE 37)
    reads them through the flash kernel over the pages in place, once a
    layer whose output something reads, under the inner scope `attn.paged_flash`, whose custom call that
    reader's pattern does NOT take for a decode read; no float32
    `[.., 1024, 1024]` score array is left in the module (XLA's walk wrote
    63). ISSUE 41: whatever produces an array the size of a pool leaf is the
    new tokens' write and nothing else, never a gather, a transpose or a
    copy of one: in the piece and in the 1,024-token suffix forward the
    scatter of whole pages into the leaf ITSELF, under `attn.write` (as a
    scatter of 4,096 rows the compiler ran it over a `[rows, 128]` view of
    the leaf and dropped its `op_name`: sixteen a piece at ~0.24 ms, PERF.md
    PR 37), and in the decode chunk the live-row kernel's call
    (ops/paged_cache_write, `%attn.write*`), whose outputs alias its pool
    operands."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache = _smallthinker_session_program(case, v5e)
    pools = hlo_stacks(jax.tree.leaves(cache))
    assert set(pools) == {("bf16", (1, 4224, 4, PAGE, 128)),
                          ("bf16", (3, 1344, 4, PAGE, 128))}
    hlo = compiled.as_text()
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo.splitlines()[0])}
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo, re.M).group(1)
    leaves = {int(rest.split(")")[0]) for _, result, op, rest in comps[entry]
              if op == "parameter" and _shapes(result)[:1]
              and _shapes(result)[0] in pools}
    assert len(leaves) == 4 and leaves <= aliased, (leaves, aliased)
    writes = _leaf_sized_results(comps, pools)
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(pools)]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 3
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    from harness.attn_trace import KERNEL

    calls = [line.strip() for line in hlo.splitlines()
             if re.match(r"\s*%(attn\.|paged_prefill)[\w.]* = \S+ custom-call\(",
                         line)]
    decode_reads = [KERNEL.match(line).group(1) for line in calls
                    if KERNEL.match(line)]
    if case == "decode_chunk":
        assert decode_reads.count("global") == 1
        assert decode_reads.count("window") == 3 and len(calls) == 4, calls
        # four layers' K and V go through four calls of the live-row kernel
        assert sorted(writes) == ["custom-call"] * 4, writes
        assert _live_row_write_calls(hlo) == 4
    else:
        # K and V of every layer that writes, each one scatter of whole pages
        # into the 5-D leaf, named
        assert set(writes) == {"scatter"} and len(writes) == 8, writes
        assert hlo.count("/attn.write/scatter") >= 8
        assert not re.findall(r"bf16\[(?:4128768|4325376),128\]", hlo)
    if case == "prefill_chunk":
        assert not decode_reads, decode_reads
        # (a KV-only piece returns the pools alone: the LAST layer's read
        # feeds nothing and is not in the module)
        # and a kernel call is named by its jitted wrapper (by its scope,
        # `%attn.paged_flash*`, should the wrapper lose its jit)
        assert len(calls) == 3 and all(
            line.startswith("%paged_prefill_attention") for line in calls), calls
        scopes = re.findall(
            r'custom-call\(.*op_name="([^"]*attn\.paged_flash[^"]*/pallas_call)"', hlo)
        assert sum("attn.global/attn.paged_flash" in s for s in scopes) == 1
        assert sum("attn.window/attn.paged_flash" in s for s in scopes) == 2
        assert not re.findall(r"f32\[[\d,]*1024,1024\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("T", [1, 16, 37, 512, 1024])
@pytest.mark.parametrize("window", [0, 4096])
def test_paged_prefill_kernel_compiles_at_every_suffix_width_on_v5e(
        window, T, v5e, compiled_kernels):
    """ISSUE 37: the flash read over the pages in place, alone, at the
    `serve-smallthinker-longshort` cell's pools and at the widths an
    admission forward can have: the piece (1,024), a power-of-two suffix
    bucket down to one token, and a bucket cut to the slots a row has left
    (`radix.bucket_len` returns any number then: 37). Mosaic must take the
    query block's `[7, bq, 128] -> [7 * bq, 128]` fold at each (bq is whole
    sublane tiles of bf16) and the scores must fit its VMEM budget."""
    from nanorlhf_tpu.ops.paged_prefill_attention import paged_prefill_attention

    one_chip = SingleDeviceSharding(v5e[0])
    L, N = (6, 1344) if window else (2, 4224)
    pool = ((L, N, 4, PAGE, 128), jnp.bfloat16)
    args = [((1, 28, T, 128), jnp.bfloat16), pool, pool, ((), jnp.int32),
            ((1, 128), jnp.int32), ((1,), jnp.int32), ((1,), jnp.int32)]
    compiled = jax.jit(
        lambda *a: paged_prefill_attention(*a, window)).lower(
        *(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
          for shape, dtype in args)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the pools are read where they lie: nothing their size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_chip_smoke_refuses_a_cpu_backend():
    """No accelerator → non-zero exit before any phase, and no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert '"platform": "cpu"' in out.stdout  # it said what it found


def _lfm2_session_program(case, v5e, layers=6):
    """The `serve-lfm2-chat` cell's decode chunk, KV-only prefill piece or
    closing suffix forward, lowered for a described v5e at LFM2-24B-A2B's
    published widths and the first `layers` layers: `(compiled, cache
    shapes, config)`."""
    import dataclasses

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.serving import radix

    one_chip = SingleDeviceSharding(v5e[0])
    full = ModelConfig.lfm2_24b()
    cfg = dataclasses.replace(full, num_hidden_layers=layers,
                              layer_types=full.layer_types[:layers])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = 64, 4096, 1024, 1024
    nb = (Tp + new) // PAGE
    pages = (R * nb, R)     # no window layer: the ring's one page a row
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, pages, PAGE, jnp.bfloat16, state_rows=R))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        tables = (spec((R, nb), jnp.int32),) * 2 + (spec((R, 1), jnp.int32),)
        lowered = session._serving_chunk.lower(
            params, cfg, state, tables, spec((R,), jnp.float32),
            spec((R,), jnp.float32), spec((R,), jnp.bool_),
            spec((R,), jnp.int32), Tp=Tp, max_tokens=new, page_size=PAGE,
            sync_every=4, eos_token_id=1, pad_token_id=0, temperature=1.0,
            top_p=1.0, greedy=False, lora_scale=1.0, top_k=64,
            capture_logprobs=False, approx_top_k=True)
    else:
        row = (spec((nb,), jnp.int32),) * 2 + (spec((1,), jnp.int32),)
        args = (params, cfg, spec((1, chunk), jnp.int32),
                spec((1, chunk), jnp.int32), spec((1,), jnp.int32))
        tail = (spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip), row)
        if case == "prefill_piece":
            lowered = session._prefill_chunk_fwd.lower(
                *args, *tail, page_size=PAGE, lora_scale=1.0)
        else:
            lowered = radix.suffix_logits.lower(
                *args, spec((), jnp.int32), *tail, page_size=PAGE,
                lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_piece", "suffix"])
def test_lfm2_session_programs_keep_pages_and_state_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 38, asked of the chip's compiler at the `serve-lfm2-chat` cell's
    shapes (LFM2-24B-A2B's published widths, the two dense conv layers and
    one period [a, c, c, c]; 64 rows of 5,120 slots, pages of 128): the
    session's decode chunk, its 1,024-token KV-only prefill piece and its
    closing suffix forward alias the attention layers' pool (heads of 64 in
    pairs: `bf16[1,2560,4,128,128]` x (k, v)) AND the conv state
    (`bf16[5,2,64,2048]`) from their parameters to their results; no module
    holds a `copy` of a pool leaf and the decode chunk none of the state;
    the T = 1 read is the in-place kernel (`%attn.global*`), the T > 1 read
    the flash kernel over the pages
    (`%paged_prefill_attention*`), neither `_paged_view`'s gather; the
    expert matmuls are the grouped-matmul kernel."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _lfm2_session_program(case, v5e)
    hlo = compiled.as_text()
    kept = hlo_stacks([leaf for leaf in jax.tree.leaves(cache) if leaf.size])
    assert set(kept) == {("bf16", (1, 2560, 4, PAGE, 128)),
                         ("bf16", (5, 2, 64, 2048))}
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo.splitlines()[0])}
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo, re.M).group(1)
    leaves = {int(rest.split(")")[0]) for _, result, op, rest in comps[entry]
              if op == "parameter" and _shapes(result)[:1]
              and _shapes(result)[0] in kept}
    assert len(leaves) == 3 and leaves <= aliased, (leaves, aliased)
    # the decode chunk copies neither; an admission forward (one row) may
    # relay the STATE at its ends (the compiler wants the row's K - 1 values
    # in one tile there: 2.6 MB each way, ~10 us beside a forward that reads
    # every expert), never the pool
    held = kept if case == "decode_chunk" else {
        k for k in kept if k[1][-1] == 128}
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(held)]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 3
    calls = [line.strip() for line in hlo.splitlines()
             if re.match(r"\s*%(attn\.|paged_prefill)[\w.]* = \S+ custom-call\(",
                         line)]
    if case == "decode_chunk":
        assert len(calls) == 1 and calls[0].startswith("%attn.global"), calls
    elif case == "suffix":
        assert len(calls) == 1 and calls[0].startswith(
            "%paged_prefill_attention"), calls
    else:   # a KV-only piece: its one attention layer is not the last layer
        assert len(calls) == 1, calls
    # no gathered view of the row's pages: [.., 5120, 128] by slot
    assert not re.findall(r"bf16\[\d+,4,5120,128\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _set_down(comps, shapes, layer_elements) -> list:
    """What a compiled module sets down of its stacked kernels: every
    instruction outside the fused computations whose result holds a bf16
    array of one of `shapes` (with or without a leading 1 or 2), and every
    one named `*.remat*` whose result holds an array of more than
    `layer_elements` elements."""
    from test_cache_carry import _shapes

    made = []
    for name, instr, result, op, _ in _unfused_results(comps):
        for dtype, dims in _shapes(result):
            while dims[:1] in ((1,), (2,)) and dims not in shapes:
                dims = dims[1:]
            if (dtype == "bf16" and dims in shapes) or (
                    ".remat" in instr and int(np.prod(dims)) > layer_elements):
                made.append(f"{name}: {instr} {op} {result}")
                break
    return made


@pytest.mark.parametrize("model, case", [
    ("smallthinker", "decode_chunk"), ("smallthinker", "prefill_chunk"),
    ("lfm2", "decode_chunk"), ("lfm2", "prefill_piece")])
def test_pattern_session_programs_set_down_no_period_of_kernels_on_v5e(
        model, case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 43, asked of the chip's compiler at the cells' DEPTH, two
    periods of the pattern (`serve-smallthinker-longshort`: 8 layers;
    `serve-lfm2-chat`: the two dense conv layers and two periods [a, c, c,
    c]), where the layer scan has two trips and a period's slice is dynamic
    (at one period, as the cases above lower it, the scan has one trip and
    the compiler reads the stacks with static slices): outside its fused
    computations the decode chunk and the prefill piece produce NO array
    shaped like a period of a projection stack, and nothing named `*.remat`
    larger than one layer's kernel. The cached scan hands a layer its
    kernels by index into the whole stacks (`core/model.leaves_in_place`,
    `_at`), so each slice has one user, the layer's matmul. As scanned xs a
    period's slice had `p` users, fused into none and was set down: at
    SmallThinker's widths `%dynamic-slice_bitcast_fusion.24` and its twin
    `.24.remat` (`bf16[4,3584,2560]`), `.20` (`bf16[4,2560,3584]`), `.22`,
    `.23` (`bf16[4,2560,512]`) and `%copy-done.1 bf16[4,3584,2560]`, 0.585 ms
    of a 5.65 ms decode step on the chip (PERF.md PR 43); at LFM2's the conv
    layers' `bf16[3,2048,6144]` and `bf16[3,2048,2048]`, each twice, and the
    dense stack whole, `bf16[2,1,11776,2048]`, a trip of ITS scan. What was
    still set down after that, a LAYER's q / k / v kernel relaid on its way
    into fast memory and the relaid stacks, is the next test's."""
    from test_cache_carry import _computations

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if model == "smallthinker":
        compiled, _ = _smallthinker_session_program(case, v5e, layers=8)
        shapes = {(4, 3584, 2560), (4, 2560, 3584), (4, 2560, 512)}
        layer_elements = 2560 * 3584
    else:
        compiled, _, cfg = _lfm2_session_program(case, v5e, layers=10)
        assert cfg.stack_pattern(2, 8) == ((False, True),) + ("conv",) * 3
        shapes = {(3, 2048, 6144), (3, 2048, 2048), (2, 1, 11776, 2048)}
        layer_elements = 11776 * 2048
    made = _set_down(_computations(compiled.as_text()), shapes, layer_elements)
    assert not made, "\n".join(made)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _kernels_moved(comps, widths) -> list:
    """What a compiled module does to its q / k / v projection kernels
    besides reading them: every instruction outside the fused computations
    whose result holds a bf16 array `[D, width]` or `[layers, D, width]` of
    `widths` (`(D, width)` pairs: one layer's kernel, a period's, a whole
    stack), unless it is a PREFETCH, the array as it is stored (row-major)
    brought into fast memory (`S(1)`), which is the kernel's one read; and
    whatever of those shapes is named `*.remat*` (made a second time). A
    `-start`'s result names its source beside its target: its `-done` is
    judged."""
    import re

    array = re.compile(r"bf16\[([\d,]+)\]\{([\d,]*)(?::([^}]*))?\}")
    moved = []
    for name, instr, result, op, _ in _unfused_results(comps):
        if op.endswith("-start"):
            continue
        for dims, order, tiling in array.findall(result):
            dims = tuple(int(n) for n in dims.split(","))
            if len(dims) not in (2, 3) or dims[-2:] not in widths:
                continue
            stored = order == ",".join(
                str(n) for n in reversed(range(len(dims))))
            if ".remat" in instr or not (stored and "S(1)" in tiling):
                moved.append(f"{name}: {instr} {op} {result}")
                break
    return moved


@pytest.mark.parametrize("model, case", [
    ("smallthinker", "decode_chunk"), ("smallthinker", "prefill_chunk"),
    ("lfm2", "decode_chunk"), ("lfm2", "prefill_piece"),
    ("trinity", "decode_chunk")])
def test_pattern_session_programs_read_qkv_kernels_where_they_lie_on_v5e(
        model, case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 44, asked of the chip's compiler at the cells' depth (the
    programs of the test above, and `serve-trinity-reason`'s decode chunk at
    its own cut, a one-layer dense stack and one period of four): outside
    its fused computations a program produces no bf16 array shaped like ONE
    layer's q / k / v projection kernel or like a whole projection stack in
    another layout than the stored one or outside fast memory, and nothing
    of those shapes named `*.remat*`. `core/model._attention` fences the
    projections' results from the head split where the layer took its
    kernels at a traced index of the stacks (`optimization_barrier`), so the
    reshape `[B, T, H, hd]` cannot reach back into a kernel's layout. Without
    the fence the compiler wanted the kernels contraction-minor (`{1,2,0}`):
    SmallThinker's decode chunk relaid the stacks once a call (`%copy.175
    bf16[8,2560,3584]`, `%copy.174/.177 bf16[8,2560,512]`), set each layer's
    relaid slice down twice a step (`%constant_dynamic-slice_fusion.15` and
    `.15.remat bf16[1,2560,3584]`, `.16/.17` and their `.remat`s
    `bf16[1,2560,512]`) and fetched the k / v stacks again every period
    (`%copy-done.7/.9 bf16[8,2560,512]`); Trinity's did the same to its dense
    layer (`%fusion.1124` and `.1124.remat bf16[1,3072,6144]`, `.1126/.1131`
    with `.remat` and `.remat2`) and to its period (`%copy.484
    bf16[4,3072,6144]`): temporaries 233 -> 14 MB and 349 -> 11 MB. What
    stays is a prefetch of a kernel as it is stored (`%copy-done.9
    bf16[1,3072,6144]{2,1,0:..S(1)}`): the kernel's read."""
    from test_cache_carry import _computations

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if model == "smallthinker":
        compiled, _ = _smallthinker_session_program(case, v5e, layers=8)
        widths = {(2560, 3584), (2560, 512)}
    elif model == "lfm2":   # (the conv layers' `out_proj` is as wide as q's)
        compiled, _, _ = _lfm2_session_program(case, v5e, layers=10)
        widths = {(2048, 2048), (2048, 512)}
    else:                   # (q's kernel and the gate's have one shape)
        compiled, _, _ = _trinity_session_program(case, v5e)
        widths = {(3072, 6144), (3072, 1024)}
    moved = _kernels_moved(_computations(compiled.as_text()), widths)
    assert not moved, "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def _trinity_session_program(case, v5e):
    """The `serve-trinity-reason` cell's decode chunk, KV-only prefill piece
    or closing admission forward, lowered for a described v5e at the
    configuration file's own cut (Trinity-Large-Preview's published widths,
    one dense window layer and one period of expert layers, 32 of 256
    experts held, an eighth of the vocabulary) and the cell's engine sizes:
    `(compiled, cache shapes, config)`."""
    import json
    import os

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.sampler.paged.pages import ring_blocks
    from nanorlhf_tpu.serving import radix

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "trinity-large-ep8-l5.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "reason-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = (eng["rows"], eng["prompt_len"], eng["max_new_tokens"],
                         eng["prefill_chunk"])
    nb = (Tp + new) // PAGE
    ring = ring_blocks(cfg.sliding_window, PAGE, chunk)
    pages = (R * nb + nb, R * ring)     # one row's spare pages (headroom 0)
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, pages, PAGE, jnp.bfloat16))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        lowered = session._serving_chunk.lower(
            params, cfg, state, (spec((R, nb), jnp.int32),) * 2,
            spec((R,), jnp.float32), spec((R,), jnp.float32),
            spec((R,), jnp.bool_), spec((R,), jnp.int32), Tp=Tp,
            max_tokens=new, page_size=PAGE, sync_every=eng["sync_every"],
            eos_token_id=1, pad_token_id=0, temperature=1.0, top_p=1.0,
            greedy=False, lora_scale=1.0, top_k=64, capture_logprobs=False,
            approx_top_k=True)
    else:
        args = (params, cfg, spec((1, chunk), jnp.int32),
                spec((1, chunk), jnp.int32), spec((1,), jnp.int32))
        tail = (spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip),
                (spec((nb,), jnp.int32),) * 2)
        if case == "prefill_piece":
            lowered = session._prefill_chunk_fwd.lower(
                *args, *tail, page_size=PAGE, lora_scale=1.0)
        else:
            lowered = radix.suffix_logits.lower(
                *args, spec((), jnp.int32), *tail, page_size=PAGE,
                lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_piece", "admission"])
def test_trinity_session_programs_fit_the_chip_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 42, asked of the chip's compiler at the `serve-trinity-reason`
    cell's own shapes (the configuration file's cut: 8.64 GB of bf16 weights,
    32 rows of 9,216 slots, pages of 128, a global pool of 2,376 pages x 1
    layer and a window pool of 1,344 x 4): the session's decode chunk, its
    1,024-token KV-only prefill piece and its closing admission forward each
    FIT 16 GB (arguments + temporaries + results less what they alias), alias
    both pools from their parameters to their results, hold no `copy` of a
    pool leaf, read the pages through the two in-place kernels
    (`%attn.global*` / `%attn.window*` at T = 1, `%paged_prefill_attention*`
    at T > 1) and run the experts through the grouped-matmul kernel. A later
    change that grows a buffer fails here, on the CPU."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _trinity_session_program(case, v5e)
    hlo = compiled.as_text()
    kept = hlo_stacks(jax.tree.leaves(cache))
    assert set(kept) == {("bf16", (1, 2376, 8, PAGE, 128)),
                         ("bf16", (4, 1344, 8, PAGE, 128))}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 12.5e9 < m.argument_size_in_bytes < 13.0e9     # weights + pools
    assert m.alias_size_in_bytes > 4.0e9                  # both pools donated
    assert peak < 15.5e9, (case, peak, m.temp_size_in_bytes)
    assert m.temp_size_in_bytes < 1.5e9, (case, m.temp_size_in_bytes)
    comps = _computations(hlo)
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(kept)]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 3
    calls = [line.strip().split(" ")[0] for line in hlo.splitlines()
             if re.match(r"\s*%(attn\.|paged_prefill)[\w.]* = \S+ custom-call\(",
                         line)]
    if case == "decode_chunk":
        assert {c.split(".")[1] for c in calls} == {"global", "window"}, calls
    else:
        assert calls and all(c.startswith("%paged_prefill_attention")
                             for c in calls), calls
    # no gathered view of a row's pages: [.., 9216, 128] by slot
    assert not re.findall(r"bf16\[\d+,8,9216,128\]", hlo)


def _sdar_session_program(case, v5e):
    """The `serve-sdar-blockgen` cell's block chunk or its KV-only prefill
    piece, lowered for a described v5e at the configuration file's own cut
    (SDAR-30B-A3B-Chat's published widths, 7 layers, all 128 experts, the
    whole vocabulary) and the cell's engine sizes: `(compiled, cache
    shapes, config)`."""
    import json
    import os

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "sdar-30b-a3b-l7.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "blockgen-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = (eng["rows"], eng["prompt_len"], eng["max_new_tokens"],
                         eng["prefill_chunk"])
    B = cfg.block_length
    T_max = Tp + new + B        # a row's last block may pass its budget
    nb = -(-T_max // PAGE)
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, R * nb + nb, PAGE, jnp.bfloat16))     # one row's spare pages
    if case == "block_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        rows = lambda dt: spec((R,), dt)    # noqa: E731
        state = (spec((), jnp.int32), spec((R, new + B), jnp.int32),
                 spec((R, new + B), jnp.int32), _shapes_on(cache, one_chip),
                 spec((R, T_max), jnp.bool_), rows(jnp.bool_), rows(jnp.int32),
                 rows(jnp.int32), rows(jnp.int32), key,
                 spec((R, B), jnp.int32), spec((R, B), jnp.bool_),
                 rows(jnp.int32), rows(jnp.int32), spec((5,), jnp.int32))
        lowered = session._block_chunk.lower(
            params, cfg, state, spec((R, nb), jnp.int32), rows(jnp.float32),
            rows(jnp.float32), rows(jnp.bool_), rows(jnp.int32),
            rows(jnp.int32), rows(jnp.int32), Tp=Tp, page_size=PAGE,
            sync_every=eng["sync_every"], eos_token_id=1, lora_scale=1.0,
            top_k=64, approx_top_k=True)
    else:
        lowered = session._prefill_chunk_fwd.lower(
            params, cfg, spec((1, chunk), jnp.int32),
            spec((1, chunk), jnp.int32), spec((1,), jnp.int32),
            spec((1, T_max), jnp.bool_), _shapes_on(cache, one_chip),
            spec((nb,), jnp.int32), page_size=PAGE, lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", ["block_chunk", "prefill_piece"])
def test_sdar_session_programs_fit_the_chip_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 46, asked of the chip's compiler at the `serve-sdar-blockgen`
    cell's own shapes (9.97 GB of bf16 weights, 64 rows of 3,076 slots, pages
    of 128, a pool of 1,625 pages x 7 layers): the session's block chunk and
    its 1,024-token KV-only prefill piece each FIT 16 GB, alias the pool from
    their parameters to their results, hold no `copy` of a pool leaf and run
    the experts through the grouped-matmul kernel; the block chunk reads the
    pages through the in-place decode kernel under the name `attn.block`,
    and neither gathers a row's pages into a view by slot."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _sdar_session_program(case, v5e)
    hlo = compiled.as_text()
    kept = hlo_stacks(jax.tree.leaves(cache))
    assert set(kept) == {("bf16", (7, 1625, 4, PAGE, 128))}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 12.2e9 < m.argument_size_in_bytes < 13.1e9     # weights + pool
    assert m.alias_size_in_bytes > 2.9e9                  # the pool donated
    assert peak < 14.0e9, (case, peak, m.temp_size_in_bytes)
    assert m.temp_size_in_bytes < 0.7e9, (case, m.temp_size_in_bytes)
    comps = _computations(hlo)
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(kept)]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r"%gmm[\w.]* = bf16\[\d+,\d+\]\S* custom-call\(", hlo)) >= 3
    calls = [line.strip().split(" ")[0] for line in hlo.splitlines()
             if re.match(r"\s*%attn\.[\w.]* = \S+ custom-call\(", line)]
    if case == "block_chunk":
        assert calls and all(c.startswith("%attn.block") for c in calls), calls
        # ISSUE 60: no branch of the sampler relays the logits (its pick's
        # arrays lie rows-minor from 128 rows on; the candidates are pinned
        # row-major so that the 78 MB of logits are not)
        relaid = [f"{name}: {result}" for name, instrs in comps.items()
                  for _, result, op, _ in instrs if op.startswith("copy")
                  and any(dims[-1:] == (cfg.vocab_size,)
                          for _, dims in _shapes(result))]
        assert not relaid, "\n".join(relaid)
    else:       # the piece's read is XLA's walk of the key blocks
        assert not calls
    assert not re.findall(r"bf16\[\d+,4,3200,128\]", hlo)


def _falcon_h1_session_program(case, v5e):
    """The `serve-falcon-h1-assist` cell's decode chunk, its KV-only prefill
    piece or its closing suffix forward, lowered for a described v5e at the
    configuration file's own cut (Falcon-H1-34B-Instruct's published widths,
    5 layers, the whole vocabulary) and the cell's engine sizes: `(compiled,
    cache shapes, config)`."""
    import json
    import os

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.serving import radix

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "falcon-h1-34b-l5.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "assist-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = (eng["rows"], eng["prompt_len"], eng["max_new_tokens"],
                         eng["prefill_chunk"])
    nb = (Tp + new) // PAGE
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, (R * nb, R), PAGE, jnp.bfloat16, state_rows=R))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        tables = (spec((R, nb), jnp.int32),) * 2 + (spec((R, 1), jnp.int32),)
        lowered = session._serving_chunk.lower(
            params, cfg, state, tables, spec((R,), jnp.float32),
            spec((R,), jnp.float32), spec((R,), jnp.bool_),
            spec((R,), jnp.int32), Tp=Tp, max_tokens=new, page_size=PAGE,
            sync_every=eng["sync_every"], eos_token_id=1, pad_token_id=0,
            temperature=1.0, top_p=1.0, greedy=False, lora_scale=1.0, top_k=64,
            capture_logprobs=False, approx_top_k=True)
    else:
        row = (spec((nb,), jnp.int32),) * 2 + (spec((1,), jnp.int32),)
        args = (params, cfg, spec((1, chunk), jnp.int32),
                spec((1, chunk), jnp.int32), spec((1,), jnp.int32))
        tail = (spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip), row)
        if case == "prefill_piece":
            lowered = session._prefill_chunk_fwd.lower(
                *args, *tail, page_size=PAGE, lora_scale=1.0)
        else:
            lowered = radix.suffix_logits.lower(
                *args, spec((), jnp.int32), *tail, page_size=PAGE,
                lora_scale=1.0)
    return lowered.compile(), cache, cfg


def _granite_h_session_program(case, v5e):
    """The `serve-granite-h-ragchat` cell's decode chunk, its KV-only prefill
    piece or its closing suffix forward, lowered for a described v5e at the
    configuration file's own cut (granite-4.0-h-small's published widths, ten
    layers, 36 of 72 experts, half of the vocabulary) and the cell's engine
    sizes: `(compiled, cache shapes, config)`."""
    import json
    import os

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.serving import radix

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs",
                           "granite-4.0-h-small-ep2-l10.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "ragchat-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = (eng["rows"], eng["prompt_len"], eng["max_new_tokens"],
                         eng["prefill_chunk"])
    if case == "suffix_bucket":     # a closing forward of a few tokens
        chunk = 8
    nb = (Tp + new) // PAGE
    # (the engine's pool at headroom 0: every row's pages and one row's spare)
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, (R * nb + nb, R), PAGE, jnp.bfloat16, state_rows=R))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        tables = (spec((R, nb), jnp.int32),) * 2 + (spec((R, 1), jnp.int32),)
        lowered = session._serving_chunk.lower(
            params, cfg, state, tables, spec((R,), jnp.float32),
            spec((R,), jnp.float32), spec((R,), jnp.bool_),
            spec((R,), jnp.int32), Tp=Tp, max_tokens=new, page_size=PAGE,
            sync_every=eng["sync_every"], eos_token_id=1, pad_token_id=0,
            temperature=1.0, top_p=1.0, greedy=False, lora_scale=1.0, top_k=64,
            capture_logprobs=False, approx_top_k=True)
    else:
        row = (spec((nb,), jnp.int32),) * 2 + (spec((1,), jnp.int32),)
        args = (params, cfg, spec((1, chunk), jnp.int32),
                spec((1, chunk), jnp.int32), spec((1,), jnp.int32))
        tail = (spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip), row)
        if case == "prefill_piece":
            lowered = session._prefill_chunk_fwd.lower(
                *args, *tail, page_size=PAGE, lora_scale=1.0)
        else:
            lowered = radix.suffix_logits.lower(
                *args, spec((), jnp.int32), *tail, page_size=PAGE,
                lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", [
    "decode_chunk", "suffix_bucket",
    # (35-40 s of compile each: outside the tier-1 run, whose whole has 1,470 s)
    pytest.param("prefill_piece", marks=pytest.mark.slow),
    pytest.param("suffix", marks=pytest.mark.slow)])
def test_granite_h_session_programs_fit_the_chip_with_pages_and_state_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 59, asked of the chip's compiler at the `serve-granite-h-ragchat`
    cell's own shapes (9.51 GB of bf16 weights; 48 rows of 8,960 slots, pages
    of 128: a pool of 3,430 pages x ONE attention layer, 1.80 GB; a state of
    48 rows x NINE mixer layers, the tail `bf16[9,3,48,8448]` and the
    recurrent state `f32[9,48,128,64,128]`, 1.83 GB): the session's decode
    chunk, its 1,024-token KV-only prefill piece and its closing suffix
    forward each stay under 15 GB and alias the pool AND both state leaves
    from their parameters to their results; no module holds a `copy` of a
    pool leaf or of the recurrent state; the T = 1 read is the in-place
    kernel (`%attn.global*`, one layer of ten), a piece's the paged prefill
    kernel, and the decode step's pass over the state is
    `ops/ssm.ssm_update_in_place`'s call at (H, P, G, N) = (128, 64, 1, 128)
    (`%attn.ssm.update*`, which takes the whole stack and returns it: nine a
    step); no kernel of the mixer's input projection (`bf16[9,4096,16640]`,
    130 whole lane tiles, `dt`'s 128 columns a leaf of their own as
    Falcon-H1's are) is relaid: every `copy` of a weight is a prefetch in
    the stored layout. `suffix_bucket`: the closing forward of an 8-token
    bucket, ONE chunk of the scan, where the compiler relaid the whole
    state heads-inside and back (two copies of 1.8 GB, 1.86 GB of
    temporaries) until `_ssm_operator` cut a row's state out of the stack
    seen as `[L, rows, H P, N]`."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _granite_h_session_program(case, v5e)
    assert (cfg.ssm_layers, cfg.page_layers, cfg.experts_held) == (9, 1, 36)
    hlo = compiled.as_text()
    kept = hlo_stacks([leaf for leaf in jax.tree.leaves(cache) if leaf.size])
    S = ("f32", (9, 48, 128, 64, 128))
    assert set(kept) == {("bf16", (1, 3430, 8, PAGE, 128)),
                         ("bf16", (9, 3, 48, 8448)), S}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 13.0e9 < m.argument_size_in_bytes < 13.3e9   # weights, pool, state
    assert m.alias_size_in_bytes > 3.6e9                # pool and state donated
    assert peak < 15.0e9, (case, peak, m.temp_size_in_bytes)
    assert m.temp_size_in_bytes < (
        0.1e9 if case in ("decode_chunk", "suffix_bucket") else 0.6e9)
    comps = _computations(hlo)
    everything = [i for instrs in comps.values() for i in instrs]
    # (the state also as the operator sees it when it cuts a row out)
    held = {k for k in kept if k[1] != (9, 3, 48, 8448)} | {
        ("f32", (9, 48, 8192, 128))}
    copies = [f"{name}: {result} {op}" for name, result, op, _ in everything
              if op.startswith("copy") and set(_shapes(result)) & held]
    assert not copies, "\n".join(copies)
    # a weight's copy is its prefetch, in the layout it is stored in
    relaid = [line.strip()[:160] for line in hlo.splitlines()
              if re.match(r"\s*%copy[\w.\-]* = bf16\[\d+,(4096|8192),", line)
              and not re.search(r"\{2,1,0:", line)]
    assert not relaid, "\n".join(relaid)
    calls = [name for name, _, op, _ in everything if op == "custom-call"]
    reads = [c for c in calls if c.startswith(("attn.global", "attn.window",
                                               "paged_prefill"))]
    updates = [result for name, result, op, _ in everything
               if op == "custom-call" and name.startswith("attn.ssm.update")]
    if case == "decode_chunk":
        assert reads and all(c.startswith("attn.global") for c in reads), reads
        assert len(updates) == 9 and all(S in _shapes(r) for r in updates)
    else:
        assert reads and all(c.startswith("paged_prefill") for c in reads)
        assert not updates      # a piece scans; only a step updates in place
    assert sum(c.startswith("gmm") for c in calls) >= 27
    # no gathered view of the row's pages: [.., 8960, 128] by slot
    assert not re.findall(r"bf16\[\d+,8,8960,128\]", hlo)


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_piece", "suffix"])
def test_falcon_h1_session_programs_fit_the_chip_with_pages_and_state_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 49, asked of the chip's compiler at the `serve-falcon-h1-assist`
    cell's own shapes (9.65 GB of bf16 weights, 48 rows of 5,120 slots, pages
    of 128: a pool of 1,920 pages x 5 layers, 2.52 GB; a state of 48 rows x
    5 layers, the tail `bf16[5,3,48,5120]` and the recurrent state
    `f32[5,48,32,128,256]`, 1.01 GB): the session's decode chunk, its
    1,024-token KV-only prefill piece and its closing suffix forward each
    FIT 16 GB and alias the pool AND both state leaves from their parameters
    to their results; no module holds a `copy` of a pool leaf or of the
    recurrent state (a second copy of it is 1 GB); the T = 1 read is the
    in-place kernel (`%attn.global*`)."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _falcon_h1_session_program(case, v5e)
    hlo = compiled.as_text()
    kept = hlo_stacks([leaf for leaf in jax.tree.leaves(cache) if leaf.size])
    assert set(kept) == {("bf16", (5, 1920, 4, PAGE, 128)),
                         ("bf16", (5, 3, 48, 5120)),
                         ("f32", (5, 48, 32, 128, 256))}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    # weights, pool, state (a KV-only piece takes no head: 2.67 GB less)
    head = 2.67e9 if case == "prefill_piece" else 0.0
    assert 13.0e9 < m.argument_size_in_bytes + head < 13.4e9
    assert m.alias_size_in_bytes > 3.5e9                # pool and state donated
    assert peak < 15.0e9, (case, peak, m.temp_size_in_bytes)
    comps = _computations(hlo)
    held = {k for k in kept if k[1] != (5, 3, 48, 5120)}
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & held]
    assert not copies, "\n".join(copies)
    calls = [line.strip().split(" ")[0] for line in hlo.splitlines()
             if re.match(r"\s*%(attn\.|paged_prefill)[\w.]* = \S+ custom-call\(",
                         line)]
    if case == "decode_chunk":
        assert calls and all(c.startswith("%attn.global") for c in calls), calls
    # no gathered view of the row's pages: [.., 5120, 128] by slot
    assert not re.findall(r"bf16\[\d+,4,5120,128\]", hlo)


def test_falcon_h1_decode_chunk_passes_over_the_state_in_one_kernel_on_v5e(
        v5e, compiled_kernels, monkeypatch):
    """ISSUE 50, asked of the chip's compiler at the `serve-falcon-h1-assist`
    cell's own shapes: the decode chunk's pass over the recurrent state is
    `ops/ssm.ssm_update_in_place`'s call, named by its scope
    (`%attn.ssm.update*`), which takes the whole `f32[5,48,32,128,256]` and
    returns it; no fusion reads or writes that stack or a layer's
    `f32[48,32,128,256]` of it (XLA's read for `y` and its in-place
    read-and-write of all 48 rows were two such: PERF.md, PR 49), nothing
    copies it, and the program's temporaries stay PR 49's (0.6 GB)."""
    import re

    from test_cache_carry import _CALLEE, _computations, _shapes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, _, _ = _falcon_h1_session_program("decode_chunk", v5e)
    comps = _computations(compiled.as_text())
    S = {("f32", (5, 48, 32, 128, 256)), ("f32", (48, 32, 128, 256))}
    everything = [i for instrs in comps.values() for i in instrs]
    kernels = [result for name, result, op, _ in everything
               if op == "custom-call" and name.startswith("attn.ssm.update")]
    assert kernels and all(set(_shapes(r)) & S for r in kernels), kernels
    fused = {callee for _, _, op, rest in everything if op == "fusion"
             for callee in _CALLEE.findall(rest)}
    touching = [f"{comp}: {name} = {result} {op}" for comp in fused
                for name, result, op, _ in comps.get(comp, ())
                if set(_shapes(result)) & S]
    assert fused and not touching, "\n".join(touching)
    copies = [f"{name} = {result} {op}" for name, result, op, _ in everything
              if op.startswith("copy") and set(_shapes(result)) & S]
    assert not copies, "\n".join(copies)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def _sala_session_program(case, v5e):
    """The `serve-sala-docchat` cell's decode chunk, its KV-only prefill piece
    or its closing suffix forward, lowered for a described v5e at the
    configuration file's own cut (MiniCPM-SALA's published widths, 8 layers,
    the whole vocabulary) and the cell's engine sizes: `(compiled, cache
    shapes, config)`."""
    import json
    import os

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.serving import radix

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "minicpm-sala-l8.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "docchat-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new, chunk = (eng["rows"], eng["prompt_len"], eng["max_new_tokens"],
                         eng["prefill_chunk"])
    nb = (Tp + new) // PAGE
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, (R * nb, R), PAGE, jnp.bfloat16, state_rows=R))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        tables = (spec((R, nb), jnp.int32),) * 2 + (spec((R, 1), jnp.int32),)
        lowered = session._serving_chunk.lower(
            params, cfg, state, tables, spec((R,), jnp.float32),
            spec((R,), jnp.float32), spec((R,), jnp.bool_),
            spec((R,), jnp.int32), Tp=Tp, max_tokens=new, page_size=PAGE,
            sync_every=eng["sync_every"], eos_token_id=1, pad_token_id=0,
            temperature=1.0, top_p=1.0, greedy=False, lora_scale=1.0, top_k=64,
            capture_logprobs=False, approx_top_k=True)
    else:
        row = (spec((nb,), jnp.int32),) * 2 + (spec((1,), jnp.int32),)
        args = (params, cfg, spec((1, chunk), jnp.int32),
                spec((1, chunk), jnp.int32), spec((1,), jnp.int32))
        tail = (spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip), row,
                spec((1,), jnp.int32))      # (the prompt's length: call_keys)
        if case == "prefill_piece":
            lowered = session._prefill_chunk_fwd.lower(
                *args, *tail, page_size=PAGE, lora_scale=1.0)
        else:
            lowered = radix.suffix_logits.lower(
                *args, spec((), jnp.int32), *tail, page_size=PAGE,
                lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", ["decode_chunk", "prefill_piece", "suffix"])
def test_sala_session_programs_fit_the_chip_with_pages_keys_and_state_in_place_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 53, asked of the chip's compiler at the `serve-sala-docchat`
    cell's own shapes (5.64 GB of bf16 weights, 32 rows of 66,560 slots,
    pages of 128: a pool of 16,640 pages x 2 sparse layers, 4.36 GB, their
    compressed keys `bf16[2,16640,16,128]`, 0.14 GB, and a state of 32 rows x
    6 lightning layers, `f32[6,32,32,128,128]`, 0.40 GB): the session's
    decode chunk, its 1,024-token KV-only prefill piece and its closing
    suffix forward each FIT 16 GB and alias the pool, the compressed keys
    AND the state from their parameters to their results; no module holds a
    `copy` of any of them (the compressed keys with their heads on an axis
    of their own were relaid whole at both ends of a decode chunk); the T =
    1 read is ops/sparse_attention.py's kernel (`%attn.read*`: a step's
    work is its selection's, not `rows x table width`), the state's pass
    `%attn.linear.update*`; a piece's dense branch is the paged flash
    kernel. The decode chunk's selection is a loop over the rows that select
    (ISSUE 54); a piece's and a suffix forward's take their top blocks
    without a sort of the 1,040 scores (ISSUE 58)."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _sala_session_program(case, v5e)
    hlo = compiled.as_text()
    kept = hlo_stacks([leaf for leaf in jax.tree.leaves(cache) if leaf.size])
    assert set(kept) == {("bf16", (2, 16640, 2, PAGE, 128)),
                         ("bf16", (2, 16640, 16, 128)),
                         ("f32", (6, 32, 32, 128, 128))}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    head = 0.6e9 if case == "prefill_piece" else 0.0    # a piece takes no head
    assert 10.4e9 < m.argument_size_in_bytes + head < 10.7e9
    assert m.alias_size_in_bytes > 4.8e9        # pool, keys and state donated
    assert peak < 12.0e9, (case, peak, m.temp_size_in_bytes)
    comps = _computations(hlo)
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and set(_shapes(result)) & set(kept)]
    assert not copies, "\n".join(copies)
    calls = {re.sub(r"[.\d]+$", "", c) for c in re.findall(
        r"%([\w.]+) = [^=\n]* custom-call\(", hlo)}
    select = [line for line in hlo.split("\n") if "attn.select" in line]
    if case == "decode_chunk":
        assert {"attn.read", "attn.write", "attn.linear.update"} <= calls, calls
        # no gathered view of a row's pages: [.., 66560, 128] by slot
        assert not re.findall(r"bf16\[\d+,2,66560,128\]", hlo)
        # ISSUE 54: the selection runs a row at a time over the rows that
        # select (`sala.select_needed`): `attn.select` names a sort of ONE
        # row's 1,040 block scores and a gather of its 521 pages of
        # compressed keys; nothing ranks or gathers all 32 resident rows',
        # and the loop's operands (the 0.14 GB stack among them) cost no
        # temporaries beyond PR 53's 0.75 GB (and no `copy`, above)
        # (two queries a trip: under `sala._PICK_QUERIES`, where the chip
        # timed `lax.top_k`'s sort ahead of `top_blocks`, ISSUE 58)
        assert any(" sort(" in line and "f32[1,2,1,1040]" in line
                   for line in select)
        assert any("bf16[521,16,128]" in line for line in select)
        assert not re.findall(r"f32\[32,2,1,1040\]|bf16\[16640,16,128\]", hlo)
        assert m.temp_size_in_bytes <= 0.75e9, m.temp_size_in_bytes
    else:
        assert "paged_prefill_attention" in calls, calls
        # ISSUE 58: a block of 256 queries takes its top 64 of 1,040 block
        # scores by `sala.top_blocks`: `attn.select` sorts the 64 pairs it
        # took and nothing 1,040 wide, within the temporaries the sort had
        sorted_ = [line.split(" sort(")[0] for line in select
                   if " sort(" in line]
        assert sorted_ and all("[1,2,256,64]" in r and ",1040]" not in r
                               for r in sorted_), sorted_
        assert m.temp_size_in_bytes <= 0.7e9, m.temp_size_in_bytes


def _ouro_session_program(case, v5e):
    """The `serve-ouro-tutor` cell's decode chunk or its largest admission
    forward (a 256-token prompt, one piece), lowered for a described v5e at
    the configuration file's own sizes (Ouro-2.6B whole: 48 layers passed 4
    times, 192 cache layers) and the cell's engine sizes: `(compiled, cache
    shapes, config)`."""
    import json

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.serving import radix

    bench = os.path.join(REPO, "benchmark")
    with open(os.path.join(bench, "configs", "ouro-2.6b.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    with open(os.path.join(bench, "traffic", "tutor-steady.json")) as f:
        eng = json.load(f)["engine"]
    one_chip = SingleDeviceSharding(v5e[0])
    params = _shapes_on(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, Tp, new = eng["rows"], eng["prompt_len"], eng["max_new_tokens"]
    nb = (Tp + new) // PAGE
    # (one row's spare pages: the radix pool's at headroom 0)
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, R * nb + nb, PAGE, jnp.bfloat16))
    if case == "decode_chunk":
        key = _shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
        state = (spec((), jnp.int32), spec((R, new), jnp.int32),
                 spec((R, new), jnp.float32), _shapes_on(cache, one_chip),
                 spec((R, Tp + new), jnp.bool_), spec((R,), jnp.bool_),
                 spec((R,), jnp.int32), spec((R,), jnp.int32),
                 spec((R,), jnp.int32), key)
        lowered = session._serving_chunk.lower(
            params, cfg, state, spec((R, nb), jnp.int32),
            spec((R,), jnp.float32), spec((R,), jnp.float32),
            spec((R,), jnp.bool_), spec((R,), jnp.int32), Tp=Tp,
            max_tokens=new, page_size=PAGE, sync_every=eng["sync_every"],
            eos_token_id=1, pad_token_id=0, temperature=1.0, top_p=1.0,
            greedy=False, lora_scale=1.0, top_k=64, capture_logprobs=False,
            approx_top_k=True)
    else:
        lowered = radix.suffix_logits.lower(
            params, cfg, spec((1, Tp), jnp.int32), spec((1, Tp), jnp.int32),
            spec((1,), jnp.int32), spec((), jnp.int32),
            spec((1, Tp + new), jnp.bool_), _shapes_on(cache, one_chip),
            spec((nb,), jnp.int32), page_size=PAGE, lora_scale=1.0)
    return lowered.compile(), cache, cfg


@pytest.mark.parametrize("case", ["decode_chunk", "admission"])
def test_ouro_session_programs_carry_the_pool_through_both_loops_on_v5e(
        case, v5e, compiled_kernels, monkeypatch):
    """ISSUE 55, asked of the chip's compiler at the `serve-ouro-tutor`
    cell's own shapes (5.34 GB of bf16 weights; 8 rows of 640 slots, pages of
    128 and one row's spare, each pool leaf `bf16[192,45,16,128,128]`, 4.53
    GB): the pool rides
    TWO nested loop carries now (the passes around the layers, and in the
    chunk the decode steps around both). The session's decode chunk and its
    256-token admission forward each alias both pool leaves from their
    parameters to their results, hold no `copy` of a leaf anywhere (neither
    loop's carry sets one down), read the pages through the in-place kernel
    (the chunk; the admission gathers its own row's 5 pages), and FIT: arguments
    + temporaries + results less what they alias under 15 GB."""
    import re

    from test_cache_carry import _computations, _shapes, hlo_stacks

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, cache, cfg = _ouro_session_program(case, v5e)
    assert (cfg.loop_passes, cfg.cache_layers) == (4, 192)
    hlo = compiled.as_text()
    (pool,) = hlo_stacks(cache)
    assert pool == ("bf16", (192, 45, 16, PAGE, 128))
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    pool_bytes = 2 * 2 * int(np.prod(pool[1]))
    assert pool_bytes == 45 * 192 * 2 ** 20 == 9_059_696_640
    assert 14.3e9 < m.argument_size_in_bytes < 14.5e9     # weights + pool
    assert m.alias_size_in_bytes >= pool_bytes            # both leaves donated
    assert peak < 15e9, (case, peak, m.temp_size_in_bytes)
    assert m.temp_size_in_bytes < 1.0e9, (case, m.temp_size_in_bytes)
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo.splitlines()[0])}
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo, re.M).group(1)
    leaves = {int(rest.split(")")[0]) for _, result, op, rest in comps[entry]
              if op == "parameter" and _shapes(result)[:1] == [pool]}
    assert len(leaves) == 2 and leaves <= aliased, (leaves, aliased)
    copies = [f"{name}: {result} {op}" for name, instrs in comps.items()
              for _, result, op, _ in instrs
              if op.startswith("copy") and pool in _shapes(result)]
    assert not copies, "\n".join(copies)
    # a layer's slab of the pool ([45, 16, 128, 128]) is never set down
    slabs = [f"{name}: {result} {op}" for name, instrs in comps.items()
             for _, result, op, _ in instrs
             if not result.startswith("(") and _shapes(result)
             and _shapes(result)[0][1] == pool[1][1:]]
    assert not slabs, "\n".join(slabs)
    calls = [line.strip().split(" ")[0] for line in hlo.splitlines()
             if re.match(r"\s*%(attn\.|paged_prefill)[\w.]* = \S+ custom-call\(",
                         line)]
    if case == "decode_chunk":
        assert any(c.startswith("%attn.read") for c in calls), calls
        assert _live_row_write_calls(hlo) == 1
    else:
        # (a model of one kind reads an admission's pages as
        # `serve-1.5b-chat` does: the row's own 5 pages gathered, never the
        # pool; its 256 tokens go in as whole pages under `attn.write`)
        assert hlo.count("/attn.write/scatter") >= 2
        assert not re.findall(r"bf16\[45,16,640,128\]", hlo)
    # the loop is in the program: the pool rides the passes' carry round the
    # layers' (and in the chunk the decode steps' round both)
    loops = [name for name, instrs in comps.items() for _, result, op, _ in instrs
             if op == "while" and _shapes(result).count(pool) == 2]
    assert len(loops) == (3 if case == "decode_chunk" else 2), loops
