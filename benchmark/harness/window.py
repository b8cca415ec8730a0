"""What every driver shares: the compile meter, the traced part of a window,
percentiles, and the run's result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil

import numpy as np


class Meter:
    """Programs that reached the backend and the seconds that took
    (`telemetry/mfu.RecompileCounter`: compiled, or loaded from the
    persistent cache; count = programs new to the process, only the seconds
    tell warm from cold), persistent-cache requests and hits. The arithmetic
    of `chip_smoke.Meter`."""

    def __init__(self):
        import jax

        from nanorlhf_tpu.telemetry.mfu import recompile_counter

        self._compiles = recompile_counter()
        self._cache = {"/jax/compilation_cache/compile_requests_use_cache": 0,
                       "/jax/compilation_cache/cache_hits": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name in self._cache:
            self._cache[name] += 1

    def mark(self) -> dict:
        requests, hits = self._cache.values()
        return {"compiles": self._compiles.count,
                "compile_seconds": self._compiles.seconds,
                "cache_requests": requests, "cache_hits": hits}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


class TraceWindow:
    """The profiled part of a traced run: `jax.profiler` around a short
    steady stretch, with the harness's own host annotation over it."""

    def __init__(self, out_dir: str, enabled: bool, inside: str | None = None):
        """`inside`: the harness annotation that is already open when the
        traced part starts (the profiler records only spans that begin while
        it runs), re-opened under the same name for the traced part."""
        self.dir = os.path.join(out_dir, "trace")
        self.enabled = enabled
        self.reduced = None
        self._inside = inside
        self._notes = []

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        names = ["bench.trace_window"] + ([self._inside] if self._inside else [])
        self._notes = [jax.profiler.TraceAnnotation(n) for n in names]
        for note in self._notes:
            note.__enter__()

    def stop(self) -> None:
        if not self.enabled or not self._notes:
            return
        import jax

        for note in reversed(self._notes):
            note.__exit__(None, None, None)
        self._notes = []
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        """After the window: trace file -> numbers (harness/xplane.py)."""
        if not self.enabled:
            return None
        from harness import xplane

        path = xplane.newest_xplane(self.dir)
        if path is None:
            return None
        self.reduced = xplane.reduce_file(path)
        if self.reduced is not None:
            self.reduced["file_bytes"] = os.path.getsize(path)
            if self.reduced["busy_s"] > self.reduced["window_s"]:
                # host and device clocks disagree: take the device's extent
                self.reduced["window_from"] = "device_extent"
                self.reduced["window_s"] = self.reduced["device_extent_s"]
        return self.reduced


def annotate(name: str, **kw):
    """A host span in the profiler's own trace (`bench.*`), written by the
    harness around each call it makes into the program."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)
    except Exception:
        return contextlib.nullcontext()


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


def memory_by_device(n_chips: int, key: str = "peak_bytes_in_use") -> list:
    import jax

    return [int((d.memory_stats() or {}).get(key, 0))
            for d in jax.devices()[:n_chips]]


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()[:n_chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(memory_by_device(n_chips))}


@dataclasses.dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict            # name -> value (unrounded)
    run: dict                   # artefacts the per-layer readers read
    why_not: list = dataclasses.field(default_factory=list)
