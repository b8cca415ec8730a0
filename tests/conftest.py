"""Test harness: simulate an 8-device TPU mesh on CPU.

Must set XLA flags before jax initializes its backend, hence module-level env
mutation in conftest (pytest imports this before any test module).
"""

import gc
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


# A test worker keeps every executable it has compiled, each mapped into the
# process: the suite's 73 files leave ~340,000 memory maps between them, six
# workers 27,000-58,000 each as `--dist load` deals the tests, against
# `vm.max_map_count` = 65,530, and a worker that reaches that dies in
# whichever test it is running (PR 41: three whole runs lost a worker in
# tests/test_smallthinker.py, one in tests/test_sp_score.py, which holds
# 2,583 alone). `jax.clear_caches()` lets the executables go and their maps
# with them (24,273 -> 669 after tests/test_lfm2.py). Between modules, so that
# no test's warmed-up programs go under it; the largest module adds 30,510
# (tests/test_axk1.py), so a worker stays under 24,000 + 30,510.
_MAPS_HIGH = 24_000


@pytest.fixture(scope="module", autouse=True)
def _let_executables_go_between_modules():
    yield
    try:
        with open("/proc/self/maps", "rb") as f:
            held = f.read().count(b"\n")
    except OSError:     # no procfs here: nothing to read, nothing to fear
        return
    if held > _MAPS_HIGH:
        jax.clear_caches()
        gc.collect()
