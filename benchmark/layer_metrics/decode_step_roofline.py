"""kernels: the bytes one decode step must move (harness/ops_bytes: weights
once + filled KV at its mean fill over the response + logits) over chips x
the HBM bandwidth of peaks.json, divided by `decode_step_ms`, in %. A
step-level share of the memory roofline, named as such; per-kernel shares
wait for spans inside the program."""

import statistics

from harness import ops_bytes


def floor_ms(run):
    mix = run["traffic"]
    filled = ((mix["prompt_len_min"] + mix["prompt_len_max"]) / 2
              + mix["response_length"] / 2)
    b = ops_bytes.decode_step_bytes(
        run["config"], rows=mix["prompts"] * mix["sample_n"], filled_mean=filled,
        lora_r=run["config"]["assumed"]["lora"]["r"])
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    rows = run.get("rows")
    if not rows:
        return None
    step_ms = 1e3 * statistics.median(r["time/rollout_s"] for r in rows) \
        / run["traffic"]["response_length"]
    return 100.0 * floor_ms(run) / step_ms
