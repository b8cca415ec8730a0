"""kernels, generation by blocks: the block read (a block's queries over its
row's live pages, in place: the scope `attn.block`, whose kernel's custom call
takes its name) against the HBM bandwidth, from the device trace by the
kernel's name (harness/block_trace.py, one event a layer a forward): the K
and V bytes of the pages a traced call read (`serving/attn_live_pages` over
`serving/decode_steps` inside the traced seconds, times the page's slots x 2
x kv heads x head_dim x 2 B, times the trace's own count of events) over the
bandwidth of peaks.json, over the kernel's measured self time, in %. Nothing
where the trace has no such kernel (the plain form off the TPU, every other
model) or the program no such counter."""

from harness import ops_bytes_sdar as ob
from layer_metrics.sdar_block_roofline import per_forward


def read(run):
    attn, cfg = run.get("block_trace"), run.get("config", {})
    if not attn or not attn.get("seconds") or cfg.get("model_type") != "sdar_moe":
        return None
    pages = per_forward(run, "serving/attn_live_pages")
    if not pages:
        return None
    page = int(run["traffic"]["engine"]["page_size"])
    least = attn["events"] * pages * page * ob.kv_bytes_per_token_layer(cfg) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / attn["seconds"]
