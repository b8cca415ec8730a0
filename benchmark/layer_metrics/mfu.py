"""model: model operations the window's updates required (harness/ops_bytes:
prefill, decode, two scoring forwards, the LoRA update's forward + backward;
recomputation not counted) over their wall seconds, over chips x the bf16
peak of peaks.json, in %. An end-to-end utilisation, not a kernel's share."""

from harness import ops_bytes


def update_flops(run):
    mix = run["traffic"]
    return ops_bytes.grpo_update_flops(
        run["config"], prompts=mix["prompts"], sample_n=mix["sample_n"],
        context=run["context"],
        prompt_mean=(mix["prompt_len_min"] + mix["prompt_len_max"]) / 2,
        response=mix["response_length"], kept_rows=mix["prompts"],
        lora_r=run["config"]["assumed"]["lora"]["r"])


def read(run):
    if not run.get("update_seconds"):
        return None
    flops = update_flops(run)["total"] * len(run["update_seconds"])
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / sum(run["update_seconds"]) / peak
