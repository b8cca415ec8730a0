"""serving: mean seconds from the loop queueing a request's first token to
the gateway's handler having written and flushed it
(`serving/first_token_lag_s_sum` over `..._count`), streamed requests, in ms."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/first_token_lag_s_sum",
                 "serving/first_token_lag_s_count", 1e3)
