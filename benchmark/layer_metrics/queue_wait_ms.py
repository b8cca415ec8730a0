"""serving: mean seconds a request waited between `submit()` and the start
of its admission, over the admissions inside the window
(`serving/queue_wait_s_sum` over `serving/queue_wait_s_count`), in ms."""


def delta(run, key):
    """`key`'s gain over the window in `engine.metrics()`, stored whole at
    the window's start and end; None where the program exports no such key."""
    c = run.get("counters")
    if not c or key not in c["start"] or key not in c["end"]:
        return None
    return c["end"][key] - c["start"][key]


def ratio(run, over, under, scale):
    """scale x delta(over) / delta(under); `under` may be a tuple of keys,
    summed. None where a key is missing or nothing was counted."""
    under = (under,) if isinstance(under, str) else under
    top, parts = delta(run, over), [delta(run, k) for k in under]
    if top is None or None in parts or not sum(parts):
        return None
    return scale * top / sum(parts)


def read(run):
    return ratio(run, "serving/queue_wait_s_sum", "serving/queue_wait_s_count",
                 1e3)
