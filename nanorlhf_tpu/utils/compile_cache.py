"""Persistent XLA compilation cache shared by launchers, chip_smoke,
benchmark/run.py, tools and tests.

A cold process compiles every jitted program it runs (the r1 bucket menu is
several context × response shapes plus sp variants); jax's persistent cache
turns a later process's compiles into disk loads — but only if every
entrypoint enables it and they all agree on one directory.

Placement, in this order:

- `NANORLHF_CACHE_DIR=0`: this module enables nothing and returns None.
- `JAX_COMPILATION_CACHE_DIR` set: jax reads that variable itself; the cache
  lives there and nothing here sets another directory, writes a marker file
  into it, or deletes anything under it. Whoever placed the directory owns it.
- otherwise: `<repo>/.jax_cache` (covered by `.gitignore`). One fixed path:
  jax's cache key already separates backends, jax versions and compile
  options, so nothing about the host, the process or the time goes into it.

jax skips an entry it cannot read (with a warning) and compiles again, so a
process killed mid-write costs one recompile, not the directory.
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")),
    ".jax_cache",
)

# set by the first successful enable_compilation_cache(): later calls return
# it without touching jax.config (conftest, launchers, benchmark/run.py and tools
# all call enable; re-pointing a live jax cache mid-process is not supported)
_enabled_dir: str | None = None


def enable_compilation_cache() -> str | None:
    """Turn on jax's persistent compilation cache and return its directory
    (None when `NANORLHF_CACHE_DIR=0`). Placement is the module docstring's.

    Once-only per process; safe before or after backend init (the config
    only has to be set before the first compile)."""
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    if os.environ.get("NANORLHF_CACHE_DIR") == "0":
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # persist even sub-second compiles: a run's worth of small jits (reward
    # shaping, metric reductions) adds up in a process that starts cold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _enabled_dir = path
    return path
