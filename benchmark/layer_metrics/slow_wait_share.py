"""serving: of the decode seconds of the slow tenth's requests
(`serving/slow_decode_s`), the share the loop stood waiting for the device
inside `session.sync` (`serving/slow_wait_s`: for a beat's own report and
for the first tokens of other requests read since the report before it),
over the window, in %. The rest is the loop thread's own work (delivery, an
admission's plan, the dispatches)."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/slow_wait_s", "serving/slow_decode_s", 100.0)
