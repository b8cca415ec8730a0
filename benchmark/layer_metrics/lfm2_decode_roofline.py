"""kernels, the model with conv layers: the bytes one decode step must move
(harness/ops_bytes_lfm2.decode_step_bytes: every operator, dense MLP and
router, the expert kernels some live row reached, the K and V slots inside
the bounds of the attention layers, the live rows' state read and written,
the tied head and f32 logits) over the HBM bandwidth of peaks.json, divided
by the step's time (`lfm2_decode_step_ms`), in %. The counts are the
window's own, a step: experts reached (`serving/held_experts_hit`, counted
on the device), slots read (`serving/global_slots_read`, counted on the
host), each over `serving/decode_steps`; live rows are the mean of the
window's snapshots. The step's time holds its share of the beat's prefill
piece, so the share reads low under long prompts: a step-level share of the
memory roofline, not a kernel's."""

from harness import ops_bytes_lfm2 as ob
from layer_metrics import lfm2_decode_step_ms
from layer_metrics.queue_wait_ms import ratio


def live_rows(run):
    snaps = run.get("snapshots") or []
    return sum(s["active"] for s in snaps) / len(snaps) if snaps else None


def floor_ms(run):
    rows = live_rows(run)
    hit = ratio(run, "serving/held_experts_hit", "serving/decode_steps", 1.0)
    slots = ratio(run, "serving/global_slots_read", "serving/decode_steps", 1.0)
    if not rows or None in (hit, slots):
        return None
    cfg = run["config"]
    b = ob.decode_step_bytes(cfg, rows=rows, slots=slots,
                             experts_hit=hit / ob.widths(cfg)["Le"])
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    step_ms = lfm2_decode_step_ms.read(run)
    if not step_ms or "conv_L_cache" not in run.get("config", {}):
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
