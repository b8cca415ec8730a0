"""kernels: the decode step's pass over the recurrent state (docs/SSM.md,
`ops/ssm.ssm_update` under the scope `attn.ssm.update`) against the HBM
bandwidth: the bytes the EQUATIONS move a layer a step
(harness/ops_bytes_falcon_h1.ssm_update_bytes: the live rows' state and tail
read and written once, a token's operands), over the bandwidth of peaks.json,
over the device's self seconds under `decode/../attn.ssm.update` and the
state's `attn.write` beside it, a layer a step, in %. Live rows a step are
the program's own count around the traced seconds (`serving/live_row_steps`
over `serving/decode_steps`: only their RATIO belongs to the trace); layers
and steps are the trace's (`steps` of harness/scope_trace.py). The program
passes over every RESIDENT row's state, so the share reads low by the rows
nobody listens to. Nothing where the trace has no such scope or the program
no such counters."""

from harness import ops_bytes_falcon_h1 as ob
from harness import scope_trace


def traced(run, *keys):
    """The counters' gains between the profiler's start and stop, or None."""
    counters = run.get("traced_counters")
    if not counters or len(counters) != 2:
        return None
    before, after = counters
    try:
        return [after[k] - before[k] for k in keys]
    except KeyError:
        return None


def read(run):
    if "mamba_d_state" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/live_row_steps", "serving/decode_steps")
    t = scope_trace.table(run)
    if not gains or not t or gains[1] <= 0 or not t.get("steps"):
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope_trace.has(scope, "attn.ssm")
                and scope.split("/")[-1] in ("attn.ssm.update", "attn.write"))
    if not spent:
        return None
    cfg = run["config"]
    layer_steps = t["steps"] * cfg["num_hidden_layers"]
    least = layer_steps * ob.ssm_update_floor_s(
        cfg, run["peaks"], rows=gains[0] / gains[1]) / run["chips"]
    return 100.0 * least / spent
