"""rollout, the serving step of MiniCPM-SALA (docs/SALA.md): the loop's
`serving.step` span (`serving/loop_step_s`) over the decode steps the session
really took inside the window (`serving/decode_steps`), in ms: one decode
step of every resident row, with its share of the beat's prefill piece,
table uploads and device wait. Nothing where the configuration has no
`mixer_types` or the engine keeps no state (`serving/state_layers`)."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    if "mixer_types" not in (run.get("config") or {}) or not (
            run.get("counters") or {}).get("end", {}).get("serving/state_layers"):
        return None
    return ratio(run, "serving/loop_step_s", "serving/decode_steps", 1e3)
