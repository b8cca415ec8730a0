"""trainer loop, expert model: median over the window's updates of
`time/logprob_s + time/update_s` (two scoring forwards and the adapter's
minibatches): the compute-bound regime of the same expert op."""

import statistics


def read(run):
    rows = run.get("rows")
    if not rows:
        return None
    return statistics.median(r["time/logprob_s"] + r["time/update_s"] for r in rows)
