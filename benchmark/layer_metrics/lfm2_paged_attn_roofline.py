"""kernels: the in-place paged decode read of the model with conv layers
(its attention layers' pages: two heads of 64 a 128-lane row) against the HBM
bandwidth, from the device trace by the kernel's name (harness/attn_trace.py:
`%attn.global*`, one event an attention layer a step): the K and V bytes of
the slots inside the bounds that a traced call read, over the bandwidth of
peaks.json, over the kernel's measured self time, in %. A call's slots are
the mean of the steps the program took around the traced seconds
(`serving/global_slots_read` over `serving/decode_steps`, between the
profiler's start and stop: only their RATIO belongs to the trace), times 2 x
kv heads x head_dim x 2 B, times the trace's own count of events. The kernel
moves whole pages and pays a fixed cost an item, so rows of a few hundred
slots read low. Nothing where the trace has no such kernel, the program no
such counters, or the configuration no conv layers."""

from harness import ops_bytes_lfm2 as ob


def read(run):
    attn, counters = run.get("attn_trace"), run.get("traced_counters")
    if (not attn or not counters or len(counters) != 2
            or "conv_L_cache" not in run.get("config", {})):
        return None
    spent = attn["global"]["seconds"]
    before, after = counters
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        g = after["serving/global_slots_read"] - before["serving/global_slots_read"]
    except KeyError:
        return None
    if not spent or steps <= 0 or g <= 0:
        return None
    slots = attn["global"]["events"] * g / steps
    least = slots * ob.kv_bytes_per_token_layer(run["config"]) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
