"""serving: the share of the beats that took a decode step whose chunk stood
behind an admission forward or a prefill piece (`serving/beats_loaded` over
`beats_clean` + `beats_loaded`), over the window, in %."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/beats_loaded",
                 ("serving/beats_clean", "serving/beats_loaded"), 100.0)
