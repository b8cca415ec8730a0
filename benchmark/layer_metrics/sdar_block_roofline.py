"""kernels, generation by blocks: what one block forward must move and
compute (harness/ops_bytes_sdar.block_forward_floor_s: every layer's
attention, norms and router, the expert kernels some live token reached, the
K and V of the pages the live rows' block reads span, the head and the live
positions' f32 logits over the HBM bandwidth; its operations over the bf16
peak; the larger) divided by the DEVICE's seconds a forward
(`decode_device_step_ms`: the scope `decode` over the forwards counted in
the trace), in %: the cell's share of the whole step's peak. The counts are
the program's own inside the traced seconds (`InsideTrace`), a forward: live
rows (`serving/block_forwards`), experts reached (`serving/held_experts_hit`,
counted on the device), pages read (`serving/attn_live_pages`), each over
`serving/decode_steps`. Nothing where the run has no scope table, the
program no such counters, or the configuration is another model's."""

from harness import ops_bytes_sdar as ob
from layer_metrics import decode_device_step_ms


def per_forward(run, key):
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
    rows = per_forward(run, "serving/block_forwards")
    hit = per_forward(run, "serving/held_experts_hit")
    pages = per_forward(run, "serving/attn_live_pages")
    if None in (rows, hit, pages):
        return None
    page = int(run["traffic"]["engine"]["page_size"])
    return 1e3 * ob.block_forward_floor_s(
        cfg, run["peaks"], rows=rows,
        experts_hit=hit / ob.widths(cfg)["Le"], slots=pages * page) / run["chips"]


def read(run):
    if run.get("config", {}).get("model_type") != "sdar_moe":
        return None
    step_ms = decode_device_step_ms.read(run)
    if not step_ms:
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
