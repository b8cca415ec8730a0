"""serving: of what the live rows hold, the state's share, in %:
`serving/state_live_bytes` (a live row's state, both leaves over every layer
that keeps one, summed over the window's decode steps) over it and
`serving/page_live_bytes` (the page slots inside the live rows' bounds, over
every layer that keeps pages, summed likewise). Where it is high a row's
cost is fixed at admission and the pool barely grows with the context: the
state pass, not the paged read, is what a step pays a row. Nothing where the
program exports no such counters or the rows held nothing."""

from layer_metrics.queue_wait_ms import delta


def read(run):
    state = delta(run, "serving/state_live_bytes")
    pages = delta(run, "serving/page_live_bytes")
    if state is None or pages is None or state + pages <= 0:
        return None
    return 100.0 * state / (state + pages)
