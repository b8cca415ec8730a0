"""Compile a cell's main programs at its real size for a DESCRIBED v5e:2x2,
here, without a chip (on-chip-measurement guide, section 2), and print each
program's bytes per device. Run before a cell's first call to the chip:

    JAX_PLATFORMS=cpu python benchmark/tools/compile_check.py <workload> [...]

What the compiler refuses here (a program that does not fit, a kernel that
cannot be partitioned) costs no chip time. It counts one program at a time,
not what else the process keeps on the device; nothing runs, so this says
nothing about results or times and is never reported as a chip run.

rl cells: the one-jit rollout (`sampler.generate_tokens`, prompts x sample_n
rows) and the policy scorer (`trainer.fused_response_logprobs`).
serve cells: the session's decode chunk (`_serving_chunk`) and the largest
suffix prefill (`radix.suffix_logits`) over the engine's page pool.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("NANORLHF_PALLAS_INTERPRET", "0")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from harness import cell as cells, model  # noqa: E402


def report(name, lowered):
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    hlo = compiled.as_text()
    row = {"program": name,
           "argument_gb": m.argument_size_in_bytes / 1e9,
           "output_gb": m.output_size_in_bytes / 1e9,
           "alias_gb": m.alias_size_in_bytes / 1e9,
           "temp_gb": m.temp_size_in_bytes / 1e9,
           "pallas_calls": hlo.count("tpu_custom_call"),
           "collectives": {k: hlo.count(k + "(") + hlo.count(k + "-start(")
                           for k in ("all-gather", "all-reduce",
                                     "reduce-scatter", "all-to-all",
                                     "collective-permute")}}
    row["live_gb"] = (row["argument_gb"] + row["output_gb"] - row["alias_gb"]
                      + row["temp_gb"])
    print(json.dumps(row), flush=True)


def abstract_params(mcfg, cell, mesh, lora: bool):
    from nanorlhf_tpu.core import init_params
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params
    from nanorlhf_tpu.parallel import param_sharding_rules

    dtype = model.dtype_of(cell.config)
    spec = cell.config["assumed"]["lora"]

    def build(key):
        p = init_params(mcfg, key, dtype)
        if lora:
            p["lora"] = init_lora_params(
                mcfg, LoraConfig(r=spec["r"], alpha=spec["alpha"]), key, dtype)
        return p

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    rules = param_sharding_rules(shapes)
    return jax.tree.map(lambda s, r: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, r)), shapes, rules)


def check_rl(cell, devices):
    from nanorlhf_tpu.sampler.sampler import generate_tokens
    from nanorlhf_tpu.trainer import RLConfig
    from nanorlhf_tpu.trainer.trainer import fused_response_logprobs

    mix, m = cell.traffic, cell.config["mesh"]
    mesh = Mesh(np.asarray(devices[: cell.chips]).reshape(
        m["data"], m["fsdp"], m["tensor"], 1), ("data", "fsdp", "tensor", "sp"))
    mcfg = model.model_config(cell.config)
    if cell.chips > 1:
        mcfg = dataclasses.replace(mcfg, spmd_mesh=mesh,
                                   spmd_batch_axes=("data", "fsdp"),
                                   spmd_head_axis="tensor")
    params = abstract_params(mcfg, cell, mesh, lora=True)
    scale = cell.config["assumed"]["lora"]["alpha"] / cell.config["assumed"]["lora"]["r"]
    batch = NamedSharding(mesh, P(("data", "fsdp"), None))
    ctx, resp = mix["prompt_len_max"], mix["response_length"]
    ids = jax.ShapeDtypeStruct((mix["prompts"], ctx), jnp.int32, sharding=batch)
    mask = jax.ShapeDtypeStruct((mix["prompts"], ctx), jnp.bool_, sharding=batch)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
    report(f"{cell.name}: rollout generate_tokens", generate_tokens.lower(
        params, mcfg, ids, mask, key, max_tokens=resp, eos_token_id=1,
        pad_token_id=0, temperature=mix["temperature"], top_p=0.95,
        lora_scale=scale, top_k=64, prompt_fanout=mix["sample_n"]))
    cfg = RLConfig(temperature=mix["temperature"])
    rows = min(mix["prompts"], 8)
    qr = jax.ShapeDtypeStruct((rows, ctx + resp), jnp.int32, sharding=batch)
    report(f"{cell.name}: policy scorer, {rows} rows", jax.jit(
        lambda p, x: fused_response_logprobs(
            p, mcfg, x, x[:, ctx:], 0, ctx, cfg, lora_scale=scale)).lower(params, qr))


def check_serve(cell, devices):
    from nanorlhf_tpu.sampler.paged import session as sess
    from nanorlhf_tpu.sampler.paged.pages import blocks_per_row
    from nanorlhf_tpu.serving.radix import RadixCache, suffix_logits

    e = cell.traffic["engine"]
    mesh = Mesh(np.asarray(devices[:1]).reshape(1, 1, 1, 1),
                ("data", "fsdp", "tensor", "sp"))
    one = NamedSharding(mesh, P())
    mcfg = model.model_config(cell.config)
    params = abstract_params(mcfg, cell, mesh, lora=False)
    R, Tp, new, page = e["rows"], e["prompt_len"], e["max_new_tokens"], e["page_size"]
    T_max = Tp + new
    nb = blocks_per_row(T_max, page)
    pages = R * nb + RadixCache(headroom=e["headroom"]).extra_pages(R, nb)
    from nanorlhf_tpu.core.model import init_paged_kv_cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
        lambda: init_paged_kv_cache(mcfg, pages, page, jnp.bfloat16)))
    pool_gb = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(caches)) / 1e9
    print(json.dumps({"pages": pages, "pool_gb": pool_gb}), flush=True)
    state = (sds((), jnp.int32), sds((R, new), jnp.int32), sds((R, new), jnp.float32),
             caches, sds((R, T_max), jnp.bool_), sds((R,), jnp.bool_),
             sds((R,), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.int32),
             sds((2,), jnp.uint32))
    statics = dict(Tp=Tp, max_tokens=new, page_size=page,
                   sync_every=e["sync_every"], eos_token_id=1, pad_token_id=0,
                   temperature=1.0, top_p=0.95, greedy=False, lora_scale=1.0,
                   top_k=64, capture_logprobs=False, approx_top_k=True)
    report(f"{cell.name}: _serving_chunk, {R} rows", sess._serving_chunk.lower(
        params, mcfg, state, sds((R, nb), jnp.int32), sds((R,), jnp.float32),
        sds((R,), jnp.float32), sds((R,), jnp.bool_), sds((R,), jnp.int32),
        **statics))
    report(f"{cell.name}: suffix_logits, {Tp} tokens", suffix_logits.lower(
        params, mcfg, sds((1, Tp), jnp.int32), sds((1, Tp), jnp.int32),
        sds((1,), jnp.int32), sds((), jnp.int32), sds((1, T_max), jnp.bool_),
        caches, sds((nb,), jnp.int32), page_size=page, lora_scale=1.0))


def main(argv) -> int:
    from jax.experimental import topologies

    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    bench_file = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
    for name in argv:
        cell = cells.load_cell(bench_file, name)
        {"rl": check_rl, "serve": check_serve}[cell.kind](cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
