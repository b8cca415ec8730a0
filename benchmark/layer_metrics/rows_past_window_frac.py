"""serving, the page pool of two kinds: of the live rows of the window's
decode steps, those whose context had passed the attention window, in %
(`serving/rows_past_window` over `serving/live_row_steps`, both summed a step
on the host from the session's record of each live row): whether the window
closed WHILE rows decoded in this run. 0 where every row stayed inside it;
nothing where the program has no such counters or no window layer."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    if not (run.get("counters") or {}).get("end", {}).get("serving/window_layers"):
        return None
    return ratio(run, "serving/rows_past_window", "serving/live_row_steps", 100.0)
