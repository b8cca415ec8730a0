"""Analytic model-FLOPs / MFU / throughput accounting + recompile counter.

The ROADMAP's "fast as the hardware allows" is unverifiable from
episodes/sec alone — MFU (achieved model FLOPs / peak chip FLOPs) is the
hardware-normalized number. The accounting is ANALYTIC, the standard
transformer napkin model behind the trainer's per-update `perf/*` metrics
(the benchmark's `mfu` counts operations itself, attention included:
`benchmark/harness/ops_bytes.py`):

    fwd FLOPs per token ≈ 2 · n_params        (one MAC per weight)
    bwd ≈ 2 × fwd  →  train tokens cost 3 · fwd

per update:

    flops = (decode + prefill + score_tokens) · 2N  +  train_tokens · 6N
    MFU   = flops / wall_seconds / (peak_flops_per_chip · n_devices)

Deliberate approximations (stable across PRs, so the series is
comparable): attention FLOPs (quadratic term) and the PPO value model are
not counted — at production sequence lengths on the 1.5B policy the 2N
weight term dominates; decode is counted at the full configured
response_length (the toy/real reward loops nearly always run it out).

The recompile counter hangs a `jax.monitoring` duration listener on
XLA's backend-compile event: a silent retrace (a shape that escaped the
bucket menu, a donation change) shows up as a `perf/recompiles` step
instead of an unexplained 40 s stall.

Importable without jax (a parent process that must never touch the
backend can read the tables): jax is only imported inside `recompile_counter()` /
`flops_param_count()`.
"""

from __future__ import annotations

import threading

from nanorlhf_tpu.analysis.lockorder import make_lock
from typing import Optional

# peak dense bf16 FLOPs/s per chip by device kind (public figures;
# substring match on jax Device.device_kind).
PEAK_FLOPS_PER_CHIP = {
    "v6": 918e12,       # Trillium / v6e
    "v5p": 459e12,
    "v5": 197e12,       # v5e / "TPU v5 lite"
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}
CPU_PEAK_FLOPS = 1e12   # nominal; CPU MFU is not meaningful, only finite


def peak_flops_per_chip(device_kind: str, backend: str) -> tuple[float, bool]:
    """(peak_flops, known): peak dense bf16 FLOPs/s for one chip. A TPU
    kind that is not in the table is an error, not a default: a utilization
    against a guessed peak reads like a measurement. Non-TPU backends get
    the nominal CPU constant (flagged known=False) so MFU stays a finite
    series in tests."""
    if backend != "tpu":
        return CPU_PEAK_FLOPS, False
    kind = (device_kind or "").lower().replace(" ", "")
    for k, v in PEAK_FLOPS_PER_CHIP.items():
        if k in kind:
            return v, True
    raise ValueError(
        f"no peak FLOP/s for TPU device_kind {device_kind!r}: add it to "
        "PEAK_FLOPS_PER_CHIP (telemetry/mfu.py) with its source")


def flops_param_count(params: dict, loop_passes: int = 1) -> int:
    """Parameter count for the 2N-per-token FLOPs model: the base policy
    tree without LoRA adapters (adapter FLOPs are a rounding error at
    production ranks, and excluding them keeps fused/LoRA configs on the
    same denominator as full fine-tuning). A looped model's token passes the
    layer stacks `loop_passes` times (docs/OURO.md), so their parameters
    count that often and the embedding and the head once."""
    import jax
    import numpy as np

    return sum(
        int(np.prod(x.shape))
        * (loop_passes if k in ("layers", "dense_layers") else 1)
        for k, v in params.items() if k != "lora"
        for x in jax.tree.leaves(v)
    )


def update_flops(n_params: int, *, decode_tokens: float = 0.0,
                 prefill_tokens: float = 0.0, score_tokens: float = 0.0,
                 train_tokens: float = 0.0) -> float:
    """Model FLOPs for one RL update under the napkin model (module
    docstring): forward-only tokens at 2N, trained tokens at 3·2N."""
    fwd = 2.0 * float(n_params)
    return (decode_tokens + prefill_tokens + score_tokens) * fwd \
        + train_tokens * 3.0 * fwd


# ---------------------------------------------------------------------- #
# recompile counter (jax.monitoring)
# ---------------------------------------------------------------------- #

# jax emits this duration event once per program that reaches the backend:
# an in-memory jit cache hit does not fire it; a persistent-cache hit does
# (the event wraps `compile_or_get_cached`, jax 0.9), with the load time as
# its duration. So the COUNT is "programs new to this process" whether the
# cache is warm or cold, and only the SECONDS tell the two apart (measured
# on the chip: 203 programs, 57.7 s cold vs 8.3 s with 193 cache hits).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileCounter:
    """Cumulative backend-compile count + seconds, fed by jax.monitoring.
    Thread-safe: compiles can happen on the producer thread too."""

    def __init__(self):
        self._lock = make_lock("telemetry.mfu.counter")
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, name: str, secs: float, **kw) -> None:
        if name == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += float(secs)


_COUNTER: Optional[RecompileCounter] = None
_COUNTER_LOCK = make_lock("telemetry.mfu.registry")


def recompile_counter() -> RecompileCounter:
    """The process-global recompile counter, installing its jax.monitoring
    listener on first use. Global because the listener registry is global
    (listeners cannot be unregistered individually) — one listener serves
    every trainer in the process, all reading the same cumulative series."""
    global _COUNTER
    with _COUNTER_LOCK:
        if _COUNTER is None:
            counter = RecompileCounter()
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                counter._on_event
            )
            _COUNTER = counter
    return _COUNTER
