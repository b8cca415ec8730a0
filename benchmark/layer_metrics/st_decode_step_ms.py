"""rollout, the serving step of the pattern model (window layers beside
global ones): the loop's `serving.step` span (`serving/loop_step_s`) over the
decode steps inside the window (`serving/loop_beats` x the mix's
`sync_every`), in ms: one decode step of every resident row, with its share
of the beat's prefill chunk, table uploads and device wait. Nothing where the
engine has no window layers (`serving/window_layers`)."""

from layer_metrics.queue_wait_ms import delta, ratio


def read(run):
    if not (run.get("counters") or {}).get("end", {}).get("serving/window_layers"):
        return None
    if delta(run, "serving/loop_beats") is None:
        return None
    per_beat = ratio(run, "serving/loop_step_s", "serving/loop_beats", 1e3)
    if per_beat is None:
        return None
    return per_beat / int(run["traffic"]["engine"]["sync_every"])
