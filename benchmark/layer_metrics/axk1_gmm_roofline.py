"""kernels: the grouped expert matmul (`gmm`) of a chip's share against its
roofline, from the device trace of the traced serving part by the kernel's
name: `gmm_roofline`'s rule at this model's shapes
(harness/ops_bytes_axk1.grouped_matmul_floor_s: per call shape, the larger
of operations over the bf16 peak and bytes over the HBM bandwidth, counting
only the rows of held experts and the held kernels a call's tokens reach)
times its calls, over the kernel's measured self time, in %. Decode's calls
(every resident row x 8 assignments) are bound by the kernels' bytes, and
are held to the kernels the LIVE rows reached in the traced seconds, as the
program counted them on the device (`moe/held_experts_hit_traced` of the
run: `serving/held_experts_hit` over `serving/decode_steps` between the
profiler's start and stop; a step dispatches no other row). A prefill
chunk's calls are bound by the kernels' bytes too, at 1/16 of their rows,
and from a few hundred tokens on they reach every held kernel. Nothing where
the trace has no such kernel, or the program no such counter."""

from harness import ops_bytes_axk1
from layer_metrics.axk1_decode_roofline import live_rows


def read(run):
    moe = run.get("moe_trace")
    if not moe or not moe.get("kernel") or "kv_lora_rank" not in run["config"]:
        return None
    decode_m = (int(run["traffic"]["engine"]["rows"])
                * int(run["config"]["num_experts_per_tok"]))
    live = live_rows(run)
    reached = (run.get("moe") or {}).get("moe/held_experts_hit_traced")
    if reached is None or not live:
        return None
    step = lambda c: c["m"] == decode_m     # noqa: E731
    least = sum(c["events"] * ops_bytes_axk1.grouped_matmul_floor_s(
        run["config"], run["peaks"], m=c["m"], k=c["k"], n=c["n"],
        tokens=live if step(c) else None, kernels=reached if step(c) else None)
        for c in moe["kernel"])
    spent = sum(c["seconds"] for c in moe["kernel"])
    return 100.0 * least / spent if spent else None
