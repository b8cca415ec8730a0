"""serving: the session's `session.sync` span (`serving/session_sync_s`, the
host waiting for the device) over the loop's `serving.step` span, over the
window, in %. The rest of a beat is host work the device idles through."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/session_sync_s", "serving/loop_step_s", 100.0)
