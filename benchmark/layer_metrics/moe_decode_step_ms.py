"""rollout, expert model: median `time/rollout_s` over the response length,
in ms: one decode step of the sparse-expert model, the weight-bound regime of
ops/moe.py (the one prefill of the padded prompts is inside the rollout's
seconds: an upper bound by about one part in the response length)."""

import statistics


def read(run):
    rows = run.get("rows")
    if not rows:
        return None
    steps = run["traffic"]["response_length"]
    return 1e3 * statistics.median(r["time/rollout_s"] for r in rows) / steps
