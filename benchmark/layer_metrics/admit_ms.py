"""serving: the loop's `serving.admit` span (`serving/loop_admit_s`) over the
admissions inside the window (`serving/admitted`), in ms: what one admission
costs every stream that waits for it."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/loop_admit_s", "serving/admitted", 1e3)
