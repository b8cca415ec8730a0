"""kernels, the gated window model with a chip's share of experts: the bytes
one decode step must move (harness/ops_bytes_trinity.decode_step_bytes: the
dense layer, every expert layer's attention, gate, norms, router and shared
expert, the HELD expert kernels some live row reached, the K and V slots
inside the bounds of each kind of layer, a window layer's capped at the
window, head and f32 logits) over the HBM bandwidth of peaks.json, divided by
the DEVICE's seconds a step (`decode_device_step_ms`: the scope `decode` over
the steps counted in the trace), in %. The counts are the program's own
inside the traced seconds (`InsideTrace`), a step: experts reached
(`serving/held_experts_hit`, counted on the device), slots read a kind
(`serving/global_slots_read`, `serving/window_slots_read`), live rows
(`serving/live_row_steps`), each over `serving/decode_steps`. Nothing where
the run has no scope table, the program no such counters, or the
configuration no gate."""

from harness import ops_bytes_trinity as ob
from layer_metrics import decode_device_step_ms


def per_step(run, key):
    counters = run.get("traced_counters")
    if not counters or len(counters) != 2:
        return None
    before, after = counters
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        return (after[key] - before[key]) / steps if steps > 0 else None
    except KeyError:
        return None


def floor_ms(run):
    cfg = run["config"]
    rows = per_step(run, "serving/live_row_steps")
    hit = per_step(run, "serving/held_experts_hit")
    g = per_step(run, "serving/global_slots_read")
    w = per_step(run, "serving/window_slots_read")
    if None in (rows, hit, g, w):
        return None
    b = ob.decode_step_bytes(cfg, rows=rows,
                             experts_hit=hit / ob.widths(cfg)["Le"],
                             global_slots=g, window_slots=w)
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    if run.get("config", {}).get("model_type") != "afmoe":
        return None
    step_ms = decode_device_step_ms.read(run)
    if not step_ms:
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
