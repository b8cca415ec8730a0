"""rollout, the serving step of the latent-attention expert model: the
loop's `serving.step` span (`serving/loop_step_s`) over the decode steps
inside the window (`serving/loop_beats` x the mix's `sync_every`), in ms:
one decode step of every resident row, with its share of the beat's prefill
tick, table upload and device wait. Nothing where the engine's pool is not
the latent one (`serving/latent_cache`)."""

from layer_metrics.queue_wait_ms import delta, ratio


def read(run):
    if not (run.get("counters") or {}).get("end", {}).get("serving/latent_cache"):
        return None
    if delta(run, "serving/loop_beats") is None:
        return None
    per_beat = ratio(run, "serving/loop_step_s", "serving/loop_beats", 1e3)
    if per_beat is None:
        return None
    return per_beat / int(run["traffic"]["engine"]["sync_every"])
