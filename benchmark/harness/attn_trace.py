"""Device time of the pattern model's two attention reads, from the
profiler's trace.

`core/model.py` runs them under `jax.named_scope("attn.global" |
"attn.window")`, and a Pallas kernel's custom call takes its scope's name:
the in-place paged decode read of a global layer is `%attn.global.N =
... custom-call(...)` in the TPU trace's op line, a window layer's
`%attn.window.N` (read off the HLO compiled for a described v5e, PR 34). The
XLA ops of the same scopes (a prefill chunk's blocked read) keep their own
names and are not counted here. Self times as harness/xplane.py has them,
means over the device planes. A trace without such a kernel (a program
without the pattern: the parent of PR 34, every other model) gives empty
tables and the reader returns nothing.
"""

from __future__ import annotations

import re

from harness import xplane

KERNEL = re.compile(r"^%attn\.(global|window)[\w.\-]* .*custom-call")


def kernel_seconds_of(data) -> dict:
    """{"global": {"events", "seconds"}, "window": {...}}."""
    out = {k: {"events": 0.0, "seconds": 0.0} for k in ("global", "window")}
    n_planes = 0
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        seen = False
        for events in xplane._plane_ops(plane):
            seen = seen or bool(events)
            selfs = xplane.self_times(events)
            counts: dict = {}
            for name, _, _ in events:
                counts[name] = counts.get(name, 0) + 1
            for name, sec in selfs.items():
                m = KERNEL.match(name)
                if m:
                    out[m.group(1)]["events"] += counts[name]
                    out[m.group(1)]["seconds"] += sec
        n_planes += seen
    n_planes = max(n_planes, 1)
    return {k: {"events": v["events"] / n_planes,
                "seconds": v["seconds"] / n_planes} for k, v in out.items()}


def kernel_seconds(path: str) -> dict:
    from jax.profiler import ProfileData

    return kernel_seconds_of(ProfileData.from_file(path))
