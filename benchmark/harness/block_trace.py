"""Device time of the block read of a model that generates by blocks, from
the profiler's trace (docs/BLOCKDIFF.md).

`core/model.py` runs the read under `jax.named_scope("attn.block")`, and a
Pallas kernel's custom call takes its scope's name: the in-place paged read
of a block forward is `%attn.block.N = ... custom-call(...)` in the TPU
trace's op line, one event a layer a forward (harness/attn_trace.py's way,
whose pattern knows `attn.global` / `attn.window` only). A trace without such
a kernel (the plain form off the TPU, every other model, a parent commit)
gives zeros and the reader returns nothing.
"""

from __future__ import annotations

import re

from harness import xplane

KERNEL = re.compile(r"^%attn\.block[\w.\-]* .*custom-call")


def kernel_seconds_of(data) -> dict:
    """{"events", "seconds"}: means over the device planes."""
    events_n = seconds = 0.0
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
                if KERNEL.match(name):
                    events_n += counts[name]
                    seconds += sec
        n_planes += seen
    n_planes = max(n_planes, 1)
    return {"events": events_n / n_planes, "seconds": seconds / n_planes}


def kernel_seconds(path: str) -> dict:
    from jax.profiler import ProfileData

    return kernel_seconds_of(ProfileData.from_file(path))
