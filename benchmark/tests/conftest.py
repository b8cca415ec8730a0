"""The benchmark's own tests (CPU, outside tier-1's `tests/`):

    python -m pytest benchmark/tests -q

Forces the CPU and four virtual devices before jax initialises, and puts the
benchmark's directory and the repo's root on the path, as `run.py` does.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("NANORLHF_CACHE_DIR", "0")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
