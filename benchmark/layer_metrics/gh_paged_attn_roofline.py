"""kernels: the in-place paged decode read of this model's ONE attention
layer in ten (8 KV heads of 128, a group of 4 query heads, no rotary)
against the HBM bandwidth, from the device trace by the kernel's name
(harness/attn_trace.py: `%attn.global*`, one event an attention layer a
step): the K and V bytes of the slots inside the bounds that a traced call
read (harness/ops_bytes_granite_h.paged_read_bytes), over the bandwidth of
peaks.json, over the kernel's measured self time, in %. A call's slots are
the mean of the steps the program took inside the traced seconds
(`serving/global_slots_read`, a layer's, over `serving/decode_steps`), times
the trace's own count of events. Nothing where the trace has no such kernel,
the program no such counters, or the configuration is another model's."""

from harness import ops_bytes_granite_h as ob
from layer_metrics.gh_decode_roofline import per_step
from layer_metrics.gh_decode_step_ms import granite_h


def read(run):
    attn = run.get("attn_trace")
    if not attn or not granite_h(run):
        return None
    slots = per_step(run, "serving/global_slots_read")
    spent = attn["global"]["seconds"]
    if not spent or not slots:
        return None
    least = ob.paged_read_bytes(
        run["config"], slots=attn["global"]["events"] * slots) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
