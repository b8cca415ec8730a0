"""kernels: the sparse layer's decode READ (docs/SALA.md,
ops/sparse_attention.py's kernel under the scope `attn.read` of a sparse
layer) against the HBM bandwidth: K and V of the slots the EQUATIONS read a
layer a step (the selecting rows' chosen slots, `serving/sparse_slots_read`,
and the other live rows' every slot, `serving/global_slots_read` less
`serving/sparse_slots_held`, over `serving/decode_steps` around the traced
seconds), over the bandwidth of peaks.json, over the device's self seconds
under `decode/../attn.read`, in %. The kernel fetches whole pages for a
chosen block of half a page at an arbitrary slot, so the share reads low by
what a page holds beside the block. Nothing where the trace has no such
scope or the program no such counters."""

from harness import ops_bytes_sala as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    if "mixer_types" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/sparse_slots_read", "serving/sparse_slots_held",
                   "serving/global_slots_read", "serving/decode_steps")
    t = scope_trace.table(run)
    if not gains or not t or gains[3] <= 0 or not t.get("steps"):
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope_trace.has(scope, "attn.read"))
    slots = (gains[0] + gains[2] - gains[1]) / gains[3]
    if not spent or slots <= 0:
        return None
    cfg = run["config"]
    layer_steps = t["steps"] * ob.widths(cfg)["Ls"]
    least = layer_steps * ob.sparse_read_floor_s(
        cfg, run["peaks"], slots=slots) / run["chips"]
    return 100.0 * least / spent
