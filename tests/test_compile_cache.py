"""Where the persistent compile cache goes, and what the program may do there.

`enable_compilation_cache()` is once-only per process: the first call sets
the directory, every later call (launchers, chip_smoke, bench, tools all call
it) returns it without touching `jax.config` again. Placement: off under
`NANORLHF_CACHE_DIR=0`; `JAX_COMPILATION_CACHE_DIR` where that is set — the
program then sets no directory of its own, plants no marker file and deletes
nothing there, stale pid files of killed runs included; `<repo>/.jax_cache`
otherwise.

conftest enables the real cache for the suite, so the in-process tests
monkeypatch `jax.config.update`; the externally placed directory is driven in
a child process, where jax reads the variable at import as it does for users.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import nanorlhf_tpu.utils.compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    """A fresh, un-latched module whose jax.config writes are recorded
    instead of applied."""
    seen = []
    monkeypatch.setattr(cc, "_enabled_dir", None)
    monkeypatch.delenv("NANORLHF_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_enable_latches_then_noops(updates):
    d = cc.enable_compilation_cache()
    assert d is not None and cc._enabled_dir == d
    first = list(updates)
    assert ("jax_compilation_cache_dir", d) in first
    # repeat call: same dir back, jax.config untouched
    assert cc.enable_compilation_cache() == d
    assert updates == first


def test_disabled_env_does_not_latch(updates, monkeypatch):
    monkeypatch.setenv("NANORLHF_CACHE_DIR", "0")
    assert cc.enable_compilation_cache() is None
    assert cc._enabled_dir is None  # a later call may still enable
    assert updates == []


def test_default_is_repo_jax_cache_exactly(updates):
    assert cc.enable_compilation_cache() == os.path.join(REPO, ".jax_cache")
    assert cc.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_external_dir_sets_no_directory_in_code(updates, monkeypatch, tmp_path):
    d = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert cc.enable_compilation_cache() == d
    assert all(name != "jax_compilation_cache_dir" for name, _ in updates)
    assert not os.path.exists(d)  # jax makes it on first write, not we


_CHILD = """
import json, os, jax, jax.numpy as jnp
from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache
d = enable_compilation_cache()
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.fixture(scope="module")
def external(tmp_path_factory):
    """One child process with the cache placed from outside, in a directory
    that already holds a killed run's pid file and an older entry."""
    parent = tmp_path_factory.mktemp("cache_parent")
    d = parent / "placed"
    d.mkdir()
    (d / ".suite_in_progress.999999").write_text("999999")
    (d / "jit_old-0123-cache").write_bytes(b"older entry")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(d), "PYTHONPATH": REPO}
    env.pop("NANORLHF_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=str(parent),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), d, parent


def test_external_dir_is_used_and_gets_only_jax_entries(external):
    report, d, parent = external
    assert report == {"dir": str(d), "config": str(d)}
    names = set(os.listdir(d)) - {".suite_in_progress.999999",
                                  "jit_old-0123-cache"}
    assert names, "the child's compile was not cached in the placed dir"
    # jax's own entries only: no sentinel, lock or marker in or beside it
    assert all(n.endswith(("-cache", "-atime")) for n in names), names
    assert os.listdir(parent) == ["placed"]


def test_stale_pid_file_in_external_dir_wipes_nothing(external):
    _, d, _ = external
    assert (d / ".suite_in_progress.999999").read_text() == "999999"
    assert (d / "jit_old-0123-cache").read_bytes() == b"older entry"
