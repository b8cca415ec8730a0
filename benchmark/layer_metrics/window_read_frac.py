"""serving, the page pool of two kinds: slots a decode step's read touches in
a WINDOW layer over the slots it touches in a GLOBAL layer, in %
(`serving/window_slots_read` over `serving/global_slots_read`, both counted
on the host from the session's record of each live row: a global layer reads
a row from its first token, a window layer at most `sliding_window` slots of
it). 100 where no live row is past the window; the lower, the more of the
K/V stream the window spares. Nothing where the program has no such
counters or no window layer."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    if not (run.get("counters") or {}).get("end", {}).get("serving/window_layers"):
        return None
    return ratio(run, "serving/window_slots_read", "serving/global_slots_read",
                 100.0)
