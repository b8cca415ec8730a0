"""serving: mean seconds a token after the first, on the loop thread's clock,
of the requests that finished inside the window in the slow tenth: at or
above the engine's running 0.90 quantile of those seconds when they finished
(`serving/slow_tpot_s_sum` over `serving/slow_requests`), in ms. The client's
`tpot_p95_ms` is of these requests, with the way out on top."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/slow_tpot_s_sum", "serving/slow_requests", 1e3)
