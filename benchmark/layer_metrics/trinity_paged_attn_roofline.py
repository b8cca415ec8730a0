"""kernels: the in-place paged decode read of the gated window model against
the HBM bandwidth, both kinds, from the device trace by the kernel's name
(harness/attn_trace.py: `%attn.global*`, `%attn.window*`, one event a layer a
step): the K and V bytes of the slots inside the bounds that a traced call
read, a window layer's capped at the window, over the bandwidth of
peaks.json, over the kernels' measured self time, in %
(`st_paged_attn_roofline`'s rule: a call's slots are
`serving/global_slots_read` and `serving/window_slots_read` over
`serving/decode_steps` inside the traced seconds, times 2 x kv heads x
head_dim x 2 B, times the trace's own count of each kind's events). The
paged flash read of a prefill piece (`attn.paged_flash`) is NOT in it: the
program counts no slots a piece (its device time is `prefill_device_ms`'s).
Nothing where the trace has no such kernel or the program no such counters."""

from harness import ops_bytes_trinity as ob
from layer_metrics.trinity_decode_roofline import per_step


def read(run):
    attn, cfg = run.get("attn_trace"), run.get("config", {})
    if not attn or cfg.get("model_type") != "afmoe":
        return None
    spent = attn["global"]["seconds"] + attn["window"]["seconds"]
    g = per_step(run, "serving/global_slots_read")
    w = per_step(run, "serving/window_slots_read")
    if not spent or not g or w is None:
        return None
    slots = attn["global"]["events"] * g + attn["window"]["events"] * w
    least = slots * ob.kv_bytes_per_token_layer(cfg) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
