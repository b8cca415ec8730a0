"""kernels: the decode step's pass over the lightning state (docs/SALA.md,
`ops/ssm.ssm_update_in_place` under the scope `attn.linear.update`) against
the HBM bandwidth: the bytes the EQUATIONS move a layer a step
(harness/ops_bytes_sala.state_update_bytes: the live rows' state read and
written once, a token's operands), over the bandwidth of peaks.json, over
the device's self seconds under `decode/../attn.linear.update`, a layer a
step, in %. Live rows a step are the program's own count around the traced
seconds (`serving/live_row_steps` over `serving/decode_steps`); lightning
layers are the configuration's, steps the trace's. Nothing where the trace
has no such scope or the program no such counters."""

from harness import ops_bytes_sala as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    if "mixer_types" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/live_row_steps", "serving/decode_steps")
    t = scope_trace.table(run)
    if not gains or not t or gains[1] <= 0 or not t.get("steps"):
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope.split("/")[-1] == "attn.linear.update")
    if not spent:
        return None
    cfg = run["config"]
    layer_steps = t["steps"] * ob.widths(cfg)["Ll"]
    least = layer_steps * ob.state_update_floor_s(
        cfg, run["peaks"], rows=gains[0] / gains[1]) / run["chips"]
    return 100.0 * least / spent
