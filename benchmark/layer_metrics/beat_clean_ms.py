"""serving: mean seconds of a beat that carried nothing but its decode chunk,
report to report on the loop thread's clock (`serving/beat_clean_s` over
`serving/beats_clean`, the session's count of the reports that took a decode
step and stood behind no forward of more than one token), in ms: what a
token costs a resident row, times `sync_every`, while nobody is admitted.
`chunk_ms` is the `step` span over ALL beats, so it lies between this and
`beat_loaded_ms`, the nearer to that one the more beats are loaded."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/beat_clean_s", "serving/beats_clean", 1e3)
