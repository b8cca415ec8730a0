"""serving: active rows over rows, mean over the harness's polls of
`engine.snapshot()` (twice a second) inside the window, in %."""


def read(run):
    snaps = run.get("snapshots")
    if not snaps:
        return None
    return 100.0 * sum(s["active"] for s in snaps) / (len(snaps) * run["rows_total"])
