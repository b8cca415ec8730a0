"""Buffer donation: the one rule for when it is on, and a jit that applies
the rule to the devices its donated argument lives on.

The trainer's update donates the parameters and the optimiser state; the
decode session's programs donate the KV page pool they are handed
(docs/PAGED_CACHE.md "Who owns the pool"). Both ask the same rule.
"""

from __future__ import annotations

from functools import update_wrapper

import jax


def donate_argnums_on_accel(*nums: int, platform: str | None = None) -> tuple:
    """Buffer donation argnums, gated off on the CPU backend.

    On accelerators donation lets XLA reuse the params/opt-state HBM across
    the update — essential at scale. On the CPU backend it buys nothing
    (host RAM, test-sized models) and is LETHAL in combination with the
    persistent compilation cache on current jaxlib: an executable
    deserialized from the cache with donated buffers segfaults/aborts the
    process a few optimizer steps in (deterministically reproduced via
    repeated train/resume cycles — fresh or warm cache alike; with donation
    off, the same sequence passes). Launchers enable the cache for every
    backend, so this protects CPU demo runs as well as the test suite.

    `platform` is where the program will run when the caller knows it
    (`platform_of` its arguments); the default backend otherwise."""
    if platform is None:
        platform = jax.default_backend()
    return nums if platform != "cpu" else ()


def platform_of(tree) -> str:
    """The platform of the devices that hold `tree`'s first leaf, or that a
    `ShapeDtypeStruct` there says will hold it (a program lowered for a
    described TPU from a CPU process); the default backend where the leaf
    names no device."""
    leaves = jax.tree.leaves(tree)
    sharding = getattr(leaves[0], "sharding", None) if leaves else None
    if sharding is None:
        return jax.default_backend()
    return next(iter(sharding.device_set)).platform


class jit_donating:
    """`jax.jit(fn, **jit_kw)` that donates positional argument `donate`
    wherever `donate_argnums_on_accel` allows it on that argument's devices.

    The donated argument is consumed by every call on an accelerator: the
    caller replaces its reference with the call's result in the same
    statement and keeps no other. Pass the argument by position. The two
    programs (donating, not) are built on first use, so importing a module
    that defines one touches no backend."""

    def __init__(self, fn, *, donate: int, **jit_kw):
        self._fn, self._donate, self._jit_kw = fn, donate, jit_kw
        self._programs: dict = {}
        update_wrapper(self, fn)

    def _argnums(self, donated_arg) -> tuple:
        return donate_argnums_on_accel(
            self._donate, platform=platform_of(donated_arg))

    def donates(self, donated_arg) -> bool:
        """Whether a call given `donated_arg` consumes it."""
        return bool(self._argnums(donated_arg))

    def _program(self, args):
        nums = self._argnums(args[self._donate])
        if nums not in self._programs:
            self._programs[nums] = jax.jit(
                self._fn, donate_argnums=nums, **self._jit_kw)
        return self._programs[nums]

    def __call__(self, *args, **kwargs):
        return self._program(args)(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self._program(args).lower(*args, **kwargs)
