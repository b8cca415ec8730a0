"""kernels: the grouped expert matmul (`gmm`) of the chip's share of Trinity's
experts (K 3,072 / N 3,072 and back, 32 held groups of 18.9 MB a kernel)
against its roofline, from the device trace of the traced serving part by the
kernel's name: `gmm_roofline`'s rule at this model's shapes
(harness/ops_bytes_trinity.grouped_matmul_floor_s: per call shape, the larger
of operations over the bf16 peak and bytes over the HBM bandwidth, counting
only the rows of held experts and the held kernels a call's tokens reach)
times its calls, over the kernel's measured self time, in %. A decode step's
calls (every resident row x 4 assignments = 128 rows) are bound by the
kernels' bytes and are held to the live rows and to the kernels THOSE reached
in the traced seconds, as the program counted them on the device
(`serving/held_experts_hit` over `serving/decode_steps` and the expert
layers, inside the traced seconds: `InsideTrace`): a floor that charged more
kernels than a call read would pass 100 % (PERF.md, PR 32). A prefill piece's
calls take a uniform router's expectation (all 32 from a few hundred tokens
on) at an eighth of their rows. Nothing where the trace has no such kernel or
the program no such counter."""

from harness import ops_bytes_trinity as ob
from layer_metrics.trinity_decode_roofline import per_step


def read(run):
    moe, cfg = run.get("moe_trace"), run.get("config", {})
    if not moe or not moe.get("kernel") or cfg.get("model_type") != "afmoe":
        return None
    hit = per_step(run, "serving/held_experts_hit")
    live = per_step(run, "serving/live_row_steps")
    if hit is None or not live:
        return None
    reached = hit / ob.widths(cfg)["Le"]
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
