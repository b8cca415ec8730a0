"""kernels, the model whose mixers stand alone in their layers: the bytes one
decode step must move (harness/ops_bytes_granite_h.decode_step_bytes: the
nine mixers' and the one attention layer's weights, every layer's shared
expert, router and norms, the HELD expert kernels some live row reached, the
K and V slots inside the bounds of the attention layer, the LIVE rows' state
read and written once, the head and f32 logits) over the HBM bandwidth of
peaks.json, divided by the DEVICE's seconds a step (`decode_device_step_ms`:
the scope `decode` over the steps counted in the trace), in %. The counts
are the program's own inside the traced seconds (`InsideTrace`), a step:
experts reached (`serving/held_experts_hit`, counted on the device), slots
read (`serving/global_slots_read`), live rows (`serving/live_row_steps`),
each over `serving/decode_steps`. The program passes over the state of the
live rows only, and dispatches to the experts the live rows only. Nothing
where the run has no scope table, the program no such counters, or the
configuration is another model's."""

from harness import ops_bytes_granite_h as ob
from layer_metrics import decode_device_step_ms
from layer_metrics.gh_decode_step_ms import granite_h
from layer_metrics.trinity_decode_roofline import per_step  # noqa: F401


def floor_ms(run):
    cfg = run["config"]
    rows = per_step(run, "serving/live_row_steps")
    hit = per_step(run, "serving/held_experts_hit")
    slots = per_step(run, "serving/global_slots_read")
    if None in (rows, hit, slots):
        return None
    b = ob.decode_step_bytes(cfg, rows=rows, slots=slots,
                             experts_hit=hit / ob.widths(cfg)["L"])
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    if not granite_h(run):
        return None
    step_ms = decode_device_step_ms.read(run)
    if not step_ms:
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
