"""kernels: the decode step's pass over the recurrent state at this model's
sizes (128 heads of 64 x 128 float32, all of a row's heads one block of
`ops/ssm.ssm_update_in_place`; scope `attn.ssm.update`) against the HBM
bandwidth: the bytes the EQUATIONS move a mixer layer a step
(harness/ops_bytes_granite_h.ssm_update_bytes: the live rows' state and tail
read and written once, a token's operands), over the bandwidth of
peaks.json, over the device's self seconds under `decode/../attn.ssm.update`
and the state's `attn.write` beside it, a mixer layer a step, in %. Live
rows a step are the program's own count inside the traced seconds
(`serving/live_row_steps` over `serving/decode_steps`); mixer layers are the
file's and steps the trace's (`steps` of harness/scope_trace.py). Nothing
where the trace has no such scope, the program no such counters, or the
configuration is another model's."""

from harness import ops_bytes_granite_h as ob
from harness import scope_trace
from layer_metrics.gh_decode_roofline import per_step
from layer_metrics.gh_decode_step_ms import granite_h


def read(run):
    if not granite_h(run):
        return None
    rows = per_step(run, "serving/live_row_steps")
    t = scope_trace.table(run)
    if not rows or not t or not t.get("steps"):
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope_trace.has(scope, "attn.ssm")
                and scope.split("/")[-1] in ("attn.ssm.update", "attn.write"))
    if not spent:
        return None
    cfg = run["config"]
    layer_steps = t["steps"] * ob.widths(cfg)["Lm"]
    least = layer_steps * ob.ssm_update_floor_s(
        cfg, run["peaks"], rows=rows) / run["chips"]
    return 100.0 * least / spent
