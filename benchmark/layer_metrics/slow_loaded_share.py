"""serving: of the decode seconds of the slow tenth's requests (first token
to last report, `serving/slow_decode_s`), the share spent in beats that
carried another request's admission forward or prefill piece
(`serving/slow_loaded_s`), over the window, in %. Against
`loaded_beat_frac` it says whether the tail is made of loaded beats."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/slow_loaded_s", "serving/slow_decode_s", 100.0)
