"""trainer loop: median over the window's updates of
`time/logprob_s + time/update_s` (scoring and the optimizer's minibatches)."""

import statistics


def read(run):
    rows = run.get("rows")
    if not rows:
        return None
    return statistics.median(r["time/logprob_s"] + r["time/update_s"] for r in rows)
