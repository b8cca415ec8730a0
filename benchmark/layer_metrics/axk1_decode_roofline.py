"""kernels, latent-attention expert model: the bytes one decode step must
move (harness/ops_bytes_axk1: the dense layer, every expert layer's
attention, router and shared expert, the held experts some live row reached
(`moe/held_experts_hit` of the run: counted by the program on the device,
`serving/held_experts_hit` over `serving/decode_steps` in the window), the
live rows' filled latent cache, head and f32 logits) over the HBM bandwidth of peaks.json, divided by the
step's time (`axk1_decode_step_ms`), in %. Live rows are the mean of the
window's snapshots; a row's mean fill is its prompt plus half its answer,
weighted by the steps it stayed. A step-level share of the memory
roofline."""

from harness import ops_bytes_axk1
from layer_metrics import axk1_decode_step_ms


def live_rows(run):
    snaps = run.get("snapshots") or []
    return sum(s["active"] for s in snaps) / len(snaps) if snaps else None


def filled_mean(run):
    done = [r for r in run.get("records", []) if r.get("status") == "ok"]
    steps = sum(r["n"] for r in done)
    if not steps:
        return None
    return sum(r["n"] * (r["prompt_len"] + r["n"] / 2) for r in done) / steps


def floor_ms(run):
    rows, fill = live_rows(run), filled_mean(run)
    if not rows or fill is None:
        return None
    hit = (run.get("moe") or {}).get("moe/held_experts_hit")
    if hit is None:
        return None
    b = ops_bytes_axk1.decode_step_bytes(
        run["config"], rows=rows, filled_mean=fill, experts_hit=hit)
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    step_ms = axk1_decode_step_ms.read(run)
    if not step_ms or "kv_lora_rank" not in run.get("config", {}):
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
