"""kernels, expert model: the bytes one decode step must move
(harness/ops_bytes_moe: attention kernels + every expert kernel a row
reaches once + router + filled KV at its mean fill + head and f32 logits)
over chips x the HBM bandwidth of peaks.json, divided by the step's time
(`moe_decode_step_ms`), in %. A step-level share of the memory roofline."""

import statistics

from harness import ops_bytes_moe


def floor_ms(run):
    mix, cfg = run["traffic"], run["config"]
    filled = ((mix["prompt_len_min"] + mix["prompt_len_max"]) / 2
              + mix["response_length"] / 2)
    b = ops_bytes_moe.decode_step_bytes(
        cfg, rows=mix["prompts"] * mix["sample_n"], filled_mean=filled,
        lora_r=cfg["assumed"]["lora"]["r"])
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    rows = run.get("rows")
    if not rows or not run["config"].get("num_experts"):
        return None
    step_ms = 1e3 * statistics.median(r["time/rollout_s"] for r in rows) \
        / run["traffic"]["response_length"]
    return 100.0 * floor_ms(run) / step_ms
