"""kernels: the in-place paged decode read at this model's shape (4 KV heads
of 128, a group of 5 query heads) against the HBM bandwidth, from the device
trace by the kernel's name (harness/attn_trace.py: `%attn.global*`, one
event a layer a step): the K and V bytes of the slots inside the bounds that
a traced call read, over the bandwidth of peaks.json, over the kernel's
measured self time, in %. A call's slots are the mean of the steps the
program took around the traced seconds (`serving/global_slots_read`, a
layer's, over `serving/decode_steps`, between the profiler's start and
stop: only their RATIO belongs to the trace), times 2 x kv heads x head_dim
x 2 B, times the trace's own count of events. Nothing where the trace has no
such kernel, the program no such counters, or the configuration no
state-space mixer."""

from harness import ops_bytes_falcon_h1 as ob
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    attn = run.get("attn_trace")
    if not attn or "mamba_d_state" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/global_slots_read", "serving/decode_steps")
    spent = attn["global"]["seconds"]
    if not gains or not spent or gains[1] <= 0 or gains[0] <= 0:
        return None
    slots = attn["global"]["events"] * gains[0] / gains[1]
    least = slots * ob.kv_bytes_per_token_layer(run["config"]) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
