"""Test harness: simulate an 8-device TPU mesh on CPU.

Must set XLA flags before jax initializes its backend, hence module-level env
mutation in conftest (pytest imports this before any test module).
"""

import os

# Force CPU even if the environment pins another platform: unit/sharding
# tests must run on the virtual 8-device CPU mesh, on a chip machine too.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Belt and suspenders: jax.config beats the env var, so anything that set
# jax_platforms through it at interpreter startup is forced back here too.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

# Persistent XLA compilation cache: jit compiles dominate suite wall time on
# small hosts; repeat runs (CI / driver rounds) reuse executables from disk.
# Same directory rule as launchers, bench and tools (utils/compile_cache.py).
# NOTE: cache-deserialized CPU executables with DONATED buffers abort the
# process on this jaxlib — which is why the trainer gates buffer donation
# off on the CPU backend (utils/donation.donate_argnums_on_accel); without that
# gate this cache would have to stay off for the whole suite.
from nanorlhf_tpu.utils.compile_cache import (  # noqa: E402
    enable_compilation_cache,
)

enable_compilation_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
