"""model, expert layer: median over the window's rows of the trainer's
`moe/load_max_over_mean`: tokens of the fullest expert over the mean, the
maximum over layers, on the scored batch. 1 is a perfectly even router; the
grouped matmul's longest group, and on several chips the fullest chip,
grows with it. Nothing where the rows lack the counter."""

import statistics


def read(run):
    values = [r["moe/load_max_over_mean"] for r in run.get("rows") or []
              if "moe/load_max_over_mean" in r]
    return statistics.median(values) if values else None
