"""kernels: the grouped expert matmul (`gmm`) of the model with conv layers
(K 2,048 / N 1,536 and back) against its roofline, from the device trace of
the traced serving part by the kernel's name: `gmm_roofline`'s rule at this
model's shapes (harness/ops_bytes_lfm2.grouped_matmul_floor_s: per call
shape, the larger of operations over the bf16 peak and bytes over the HBM
bandwidth, counting the kernels a call's tokens really reach) times its
calls, over the kernel's measured self time, in %. A decode step's calls
(every resident row x 4 assignments = 256 rows) are bound by the kernels'
bytes and are held to the live rows and to the kernels those reached in the
traced seconds, as the program counted them on the device
(`serving/held_experts_hit` over `serving/decode_steps` and the expert
layers, inside the traced seconds: `InsideTrace`). A prefill piece's calls
take a uniform router's expectation (all 64 from a few hundred tokens on).
Nothing where the trace has no such kernel or the program no such counter."""

from harness import ops_bytes_lfm2 as ob
from layer_metrics.lfm2_decode_roofline import live_rows


def read(run):
    moe, counters = run.get("moe_trace"), run.get("traced_counters")
    cfg = run.get("config", {})
    if (not moe or not moe.get("kernel") or not counters or len(counters) != 2
            or "conv_L_cache" not in cfg):
        return None
    before, after = counters
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        hit = after["serving/held_experts_hit"] - before["serving/held_experts_hit"]
    except KeyError:
        return None
    live = live_rows(run)
    if steps <= 0 or not live:
        return None
    reached = hit / (steps * ob.widths(cfg)["Le"])
    # a decode step's call: every resident row x 4 assignments, which the
    # kernel's caller pads to whole row tiles of 128 (ops/moe._grouped_matmul)
    decode_m = int(run["traffic"]["engine"]["rows"]) * int(cfg["num_experts_per_tok"])
    step = lambda c: c["m"] in (decode_m, -(-decode_m // 128) * 128)  # noqa: E731
    least = sum(c["events"] * ob.grouped_matmul_floor_s(
        cfg, run["peaks"], m=c["m"], k=c["k"], n=c["n"],
        tokens=live if step(c) else None, kernels=reached if step(c) else None)
        for c in moe["kernel"])
    spent = sum(c["seconds"] for c in moe["kernel"])
    return 100.0 * least / spent if spent else None
