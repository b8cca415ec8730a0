"""rollout, the serving step of the model whose mixers stand alone in their
layers (docs/GRANITE_H.md): the loop's `serving.step` span
(`serving/loop_step_s`) over the decode steps the session really took inside
the window (`serving/decode_steps`), in ms: one decode step of every
resident row, with its share of the beat's prefill piece, table uploads and
device wait. Nothing where the configuration is another model's or the
engine keeps no state beside its pages (`serving/state_layers`)."""

from layer_metrics.queue_wait_ms import ratio


def granite_h(run) -> bool:
    return (run.get("config") or {}).get("model_type") == "granitemoehybrid"


def read(run):
    if not granite_h(run) or not (run.get("counters") or {}).get(
            "end", {}).get("serving/state_layers"):
        return None
    return ratio(run, "serving/loop_step_s", "serving/decode_steps", 1e3)
