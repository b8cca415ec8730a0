"""kernels, generation by blocks: the grouped expert matmul (`gmm`) of
SDAR-MoE's experts (K 2,048 / N 768 and back, 128 groups of 3.1 MB a kernel)
against its roofline, from the device trace of the traced serving part by
the kernel's name: `gmm_roofline`'s rule at this model's shapes
(harness/ops_bytes_sdar.grouped_matmul_floor_s: per call shape, the larger of
operations over the bf16 peak and bytes over the HBM bandwidth) times its
calls, over the kernel's measured self time, in %. A block forward's calls
(every resident row x block_length tokens x 8 assignments) are held to the
LIVE rows' tokens and to the kernels THOSE reached in the traced seconds, as
the program counted them on the device (`serving/held_experts_hit` over
`serving/decode_steps` and the layers): a floor that charged more kernels
than a call read would pass 100 %. A prefill piece's calls take a uniform
router's expectation. Nothing where the trace has no such kernel or the
program no such counter."""

from harness import ops_bytes_sdar as ob
from layer_metrics.sdar_block_roofline import per_forward


def read(run):
    moe, cfg = run.get("moe_trace"), run.get("config", {})
    if not moe or not moe.get("kernel") or cfg.get("model_type") != "sdar_moe":
        return None
    hit = per_forward(run, "serving/held_experts_hit")
    live = per_forward(run, "serving/block_forwards")
    if hit is None or not live:
        return None
    w = ob.widths(cfg)
    reached = hit / w["Le"]
    # a block forward's call: every resident row x B tokens x k assignments,
    # which the kernel's caller pads to whole row tiles of 128
    forward_m = int(run["traffic"]["engine"]["rows"]) * w["B"] * w["k"]
    step = lambda c: c["m"] in (forward_m, -(-forward_m // 128) * 128)  # noqa: E731
    least = sum(c["events"] * ob.grouped_matmul_floor_s(
        cfg, run["peaks"], m=c["m"], k=c["k"], n=c["n"],
        tokens=live * w["B"] if step(c) else None,
        kernels=reached if step(c) else None)
        for c in moe["kernel"])
    spent = sum(c["seconds"] for c in moe["kernel"])
    return 100.0 * least / spent if spent else None
