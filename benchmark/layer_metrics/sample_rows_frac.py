"""serving: of the rows a decode step has (generation by blocks: the
positions of a forward), those its sampler ran over, in %:
`serving/sample_rows` over `serving/sample_slots`, both gained over the
window (`run["counters"]`). A step gathers the rows that need a token to the
front and samples an eighth, a quarter, a half or all of the rows, whichever
holds them (`sampler/paged/session._over_needed`): 100 % says the traffic
keeps the batch full and the mechanism never engages. Nothing where the
program has no such counters."""

from layer_metrics.queue_wait_ms import ratio


def read(run):
    return ratio(run, "serving/sample_rows", "serving/sample_slots", 100.0)
