"""serving: the loop's `serving.step` span (`serving/loop_step_s`) over the
beats inside the window (`serving/loop_beats`), in ms: one decode chunk of
`sync_every` tokens a row, prefill tick, table upload and device wait in."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/loop_step_s", "serving/loop_beats", 1e3)
