"""rollout: 100 x the median over the window's rows of the trainer's
`rollout/attn_read_frac`: the share of the KV cache the rollout's decode
attention read, summed over its steps. Over a contiguous cache that is the
static extent a step reads over all `T_max` slots; over pages read in place
(`rollout/kv_in_place` 1) the pages the kernel copies, each row's own from
the end of its left pad to the step's slot, over the table's. 100 wherever
the read is bounded by nothing. Nothing where the rows lack the counter."""

import statistics


def read(run):
    values = [r["rollout/attn_read_frac"] for r in run.get("rows") or []
              if "rollout/attn_read_frac" in r]
    return 100 * statistics.median(values) if values else None
