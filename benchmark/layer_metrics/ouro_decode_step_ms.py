"""rollout, the serving step of a looped model (docs/OURO.md): the loop's
`serving.step` span (`serving/loop_step_s`) over the decode steps the session
took inside the window (`serving/decode_steps`), in ms: one decode step of
every resident row, all its passes, with its share of the beat's admission,
table uploads and device wait (`sala_decode_step_ms`'s rule). Nothing where
the configuration has no `total_ut_steps` or the program exports no
`serving/loop_passes_per_token`."""

from layer_metrics.queue_wait_ms import ratio


def looped(run) -> bool:
    """The run is a looped configuration's on a program that loops."""
    end = (run.get("counters") or {}).get("end") or {}
    return ("total_ut_steps" in (run.get("config") or {})
            and bool(end.get("serving/loop_passes_per_token")))


def read(run):
    if not looped(run):
        return None
    return ratio(run, "serving/loop_step_s", "serving/decode_steps", 1e3)
