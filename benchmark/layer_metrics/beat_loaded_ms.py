"""serving: mean seconds of a beat whose chunk stood behind an admission
forward or a prefill piece, report to report on the loop thread's clock
(`serving/beat_loaded_s` over `serving/beats_loaded`), in ms. Over
`beat_clean_ms` it is what such a forward costs every resident row."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/beat_loaded_s", "serving/beats_loaded", 1e3)
