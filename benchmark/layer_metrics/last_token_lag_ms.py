"""serving: mean seconds from the loop queueing a request's last token to
the gateway's handler having written and flushed that token
(`serving/last_token_lag_s_sum` over `..._count`), streamed requests, in ms:
`first_token_lag_ms` for the tokens that make a TPOT. What follows the token
(the row's release, a block engine's commit beats) is not in it."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/last_token_lag_s_sum",
                 "serving/last_token_lag_s_count", 1e3)
