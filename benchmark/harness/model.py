"""Model build: `ModelConfig` from the configuration file's widths, weights
random from the seed, on the device, sharded at birth.

`entrypoints/common.resolve_model` can build only a preset chosen by a
substring of a path; a configuration here is a file, so the harness does what
`resolve_model` does for its offline model (one jitted `init_params` with
`out_shardings` from `param_sharding_rules`), from the file's widths, and
hands the result to the unchanged `RLTrainer` / `ServingEngine`.
"""

from __future__ import annotations

import dataclasses
from functools import partial


def dtype_of(config: dict):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["assumed"]["dtype"]]


def model_config(config: dict, attention_impl: str = "auto"):
    """The program's `ModelConfig` from the file's top-level keys (the
    published `config.json` keys, as the file holds them)."""
    from nanorlhf_tpu.core import ModelConfig

    mcfg = ModelConfig.from_hf_config(config)
    return dataclasses.replace(mcfg, attention_impl=attention_impl)


def mesh_of(config: dict, n_devices: int):
    """The configuration's mesh (`{"data":1,"fsdp":2,"tensor":2}`) over this
    machine's devices; one chip is a 1x1x1 mesh."""
    import jax

    from nanorlhf_tpu.parallel import MeshConfig, make_mesh

    spec = config.get("mesh") or {"data": 1, "fsdp": 1, "tensor": 1}
    mesh_cfg = MeshConfig(data=spec["data"], fsdp=spec["fsdp"],
                          tensor=spec["tensor"])
    return mesh_cfg, make_mesh(mesh_cfg, devices=jax.devices()[:n_devices])


def init_weights(mcfg, seed: int, dtype, mesh=None):
    """One jitted call from the seed, in the type the weights are served in;
    under a mesh, straight into the trainer's sharding."""
    import jax
    from jax.sharding import NamedSharding

    from nanorlhf_tpu.core import init_params
    from nanorlhf_tpu.parallel import param_sharding_rules

    init = partial(init_params, mcfg, dtype=dtype)
    key = jax.random.PRNGKey(seed)
    shardings = None if mesh is None else jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_sharding_rules(jax.eval_shape(init, key)))
    return jax.jit(init, out_shardings=shardings)(key)
