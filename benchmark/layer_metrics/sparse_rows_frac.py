"""serving: of the window's live rows' decode steps
(`serving/live_row_steps`), the share taken by rows past `dense_len`, whose
sparse layers select (`serving/sparse_rows`, docs/SALA.md), end less start,
in %. Nothing where the program exports no such counters or took no step."""


def read(run):
    c = run.get("counters") or {}
    start, end = c.get("start") or {}, c.get("end") or {}
    try:
        rows = end["serving/sparse_rows"] - start["serving/sparse_rows"]
        steps = end["serving/live_row_steps"] - start["serving/live_row_steps"]
    except KeyError:
        return None
    return 100.0 * rows / steps if steps else None
