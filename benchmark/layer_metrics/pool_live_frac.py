"""serving: the slots of the page pool that hold a live token over the slots
the live rows' pages reserve (a row claims its whole budget's pages at
admission: `serving/pool_live_slots` over `serving/pool_reserved_slots`, both
summed over the window's decode steps), in %. What a pool that claimed pages
as a row grows would carry more rows in. Nothing where the program exports
no such counters."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/pool_live_slots", "serving/pool_reserved_slots",
                 100.0)
