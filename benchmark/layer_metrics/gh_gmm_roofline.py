"""kernels: the grouped expert matmul (`gmm`) of the chip's share of this
model's experts (K 4,096 / N 768 and back, 36 held groups of 6.3 MB a
kernel, ten assignments a token) against its roofline, from the device trace
of the traced serving part by the kernel's name: `trinity_gmm_roofline`'s
rule at this model's shapes
(harness/ops_bytes_granite_h.grouped_matmul_floor_s: per call shape, the
larger of operations over the bf16 peak and bytes over the HBM bandwidth,
counting only the rows of held experts and the held kernels a call's tokens
reach) times its calls, over the kernel's measured self time, in %. A decode
step's calls (every resident row x 10 assignments, padded to whole row
tiles) are bound by the kernels' bytes and are held to the live rows and to
the kernels THOSE reached in the traced seconds, as the program counted them
on the device (`serving/held_experts_hit` over `serving/decode_steps` and
the layers). A prefill piece's calls take a uniform router's expectation
(all 36 from a few dozen tokens on) at half of their rows. Nothing where the
trace has no such kernel, the program no such counter, or the configuration
is another model's."""

from harness import ops_bytes_granite_h as ob
from layer_metrics.gh_decode_roofline import per_step
from layer_metrics.gh_decode_step_ms import granite_h


def read(run):
    moe, cfg = run.get("moe_trace"), run.get("config", {})
    if not moe or not moe.get("kernel") or not granite_h(run):
        return None
    hit = per_step(run, "serving/held_experts_hit")
    live = per_step(run, "serving/live_row_steps")
    if hit is None or not live:
        return None
    reached = hit / ob.widths(cfg)["L"]
    decode_m = int(run["traffic"]["engine"]["rows"]) * int(cfg["num_experts_per_tok"])
    step = lambda c: c["m"] in (decode_m, -(-decode_m // 128) * 128)  # noqa: E731
    least = sum(c["events"] * ob.grouped_matmul_floor_s(
        cfg, run["peaks"], m=c["m"], k=c["k"], n=c["n"],
        tokens=live if step(c) else None, kernels=reached if step(c) else None)
        for c in moe["kernel"])
    spent = sum(c["seconds"] for c in moe["kernel"])
    return 100.0 * least / spent if spent else None
