"""rollout, the serving step of the model with conv layers: the loop's
`serving.step` span (`serving/loop_step_s`) over the decode steps the
session really took inside the window (`serving/decode_steps`: a chunk stops
early once every row is done, so beats x `sync_every` counts steps nobody
ran), in ms: one decode step of every resident row, with its share of the
beat's prefill piece, table uploads and device wait. Nothing where the
engine keeps no state beside its pages (`serving/state_layers`)."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    if not (run.get("counters") or {}).get("end", {}).get("serving/state_layers"):
        return None
    return ratio(run, "serving/loop_step_s", "serving/decode_steps", 1e3)
