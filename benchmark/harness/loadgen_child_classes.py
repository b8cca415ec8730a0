"""`loadgen_child.py` for a mix of CLASSES: several kinds of request in one
stream (short chat turns beside long documents in one queue).

The child, its clock, its records and its summary are `loadgen_child`'s, by
import; only the schedule differs. A mix's `classes` is a list of
`{"name", "share", "prompt_len", ...}`: every class is drawn by
`trafficgen.serve_requests` from a mix of its own (the file's keys with the
class's laid over them, `rate_rps` = share x the stream's rate, a
`schedule_seed` and a content seed moved by the class's index, so two classes
never share their draws), and the classes' schedules are merged by due
instant and renumbered. So each class keeps what `trafficgen` guarantees a
mix: a fixed count a segment, stratified lengths, an exact greedy share.
A record's `tenant` is -1 - the class's index (no class here has tenants).
"""

from __future__ import annotations

import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import loadgen_child, trafficgen  # noqa: E402

CLASS_SEED_STEP = 1_000_003


def class_requests(mix: dict, seed: int, segments, vocab_size: int,
                   rate_rps: float | None = None) -> list:
    """The merged schedule of `mix["classes"]`, in `serve_requests`' form."""
    rate = float(mix["rate_rps"] if rate_rps is None else rate_rps)
    merged = []
    for c, cls in enumerate(mix["classes"]):
        own = {k: v for k, v in mix.items() if k != "classes"}
        own.update({k: v for k, v in cls.items() if k not in ("name", "share")})
        own["rate_rps"] = rate * float(cls["share"])
        if "schedule_seed" in mix:
            own["schedule_seed"] = int(mix["schedule_seed"]) + c * CLASS_SEED_STEP
        for r in trafficgen.serve_requests(own, int(seed) + c * CLASS_SEED_STEP,
                                           segments, vocab_size):
            merged.append({**r, "tenant": -1 - c})
    merged.sort(key=lambda r: (r["t"], r["tenant"], r["index"]))
    return [{**r, "index": i} for i, r in enumerate(merged)]


def main() -> int:
    loadgen_child.trafficgen = types.SimpleNamespace(
        serve_requests=class_requests, digest=trafficgen.digest)
    return loadgen_child.main()


if __name__ == "__main__":
    sys.exit(main())
