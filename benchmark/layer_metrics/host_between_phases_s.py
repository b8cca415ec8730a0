"""trainer loop: median over the window's updates of `trainer/iteration_s`
minus the sum of the `time/*_s` phases: host seconds an update spends between
its phases (decoding, array copies, metrics, logging)."""

import statistics

from layer_metrics.rollout_share import phase_seconds


def read(run):
    rows = [r for r in run.get("rows") or [] if "trainer/iteration_s" in r]
    if not rows:
        return None
    return statistics.median(r["trainer/iteration_s"]
                             - sum(phase_seconds(r).values()) for r in rows)
