"""serving: the loop's `serving.admit` span over all five of its spans
(`serving/loop_{wait,admit,reap,step,deliver}_s`), over the window, in %."""

from layer_metrics.queue_wait_ms import ratio

LOOP = tuple(f"serving/loop_{p}_s"
             for p in ("wait", "admit", "reap", "step", "deliver"))


def read(run):
    return ratio(run, "serving/loop_admit_s", LOOP, 100.0)
