"""kernels: the in-place paged decode read of the pattern model against the
HBM bandwidth, both kinds, from the device trace by the kernel's name
(harness/attn_trace.py: `%attn.global*`, `%attn.window*`, one event a layer
a step): the K and V bytes of the slots inside the bounds that a traced
call read, over the bandwidth of peaks.json, over the kernels' measured self
time, in %. A call's slots are the mean of the steps the program took around
the traced seconds (`serving/global_slots_read` and
`serving/window_slots_read` over `serving/decode_steps`, between the
profiler's start and stop: the counters are read outside the profiler's own
start-up and tear-down, so only their RATIO belongs to the trace), times
2 x kv heads x head_dim x 2 B, times the trace's own count of each kind's
events. The kernel moves whole pages and pays a fixed cost an item, so few
short rows read low. Nothing where the trace has no such kernel or the
program no such counters."""

from harness import ops_bytes_smallthinker as ob


def read(run):
    attn, counters = run.get("attn_trace"), run.get("traced_counters")
    if not attn or not counters or len(counters) != 2:
        return None
    spent = attn["global"]["seconds"] + attn["window"]["seconds"]
    before, after = counters
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        g = after["serving/global_slots_read"] - before["serving/global_slots_read"]
        w = after["serving/window_slots_read"] - before["serving/window_slots_read"]
    except KeyError:
        return None
    if not spent or steps <= 0 or g <= 0:
        return None
    slots = (attn["global"]["events"] * g + attn["window"]["events"] * w) / steps
    least = slots * ob.kv_bytes_per_token_layer(run["config"]) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
