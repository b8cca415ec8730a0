"""kernels: a prefill piece's lightning recurrence (docs/SALA.md,
`ops/ssm.ssd_scan` under the scope `attn.linear.scan`) against the chip's
peaks: the operations and bytes of the EQUATIONS over the tokens the traced
seconds prefilled (harness/ops_bytes_sala.linear_scan_floor_s: `4 hd hd`
operations a head a token, a token's operands, a row's state read and
written once a piece; the larger of operations over the bf16 peak and bytes
over the HBM bandwidth), over the device's self seconds under
`prefill/../attn.linear.scan`, in %. The tokens and pieces are the program's
own counts between the profiler's start and stop (`serving/state_tokens`,
`serving/state_resets` + `serving/state_piece_carries`), a lightning layer
each. A float32 scan reads low against the matrix unit's peak. Nothing where
the trace has no such scope or the program no such counters."""

from harness import ops_bytes_sala as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    if "mixer_types" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/state_tokens", "serving/state_resets",
                   "serving/state_piece_carries")
    t = scope_trace.table(run)
    if not gains or not t or gains[0] <= 0:
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "prefill")
                and scope.split("/")[-1] == "attn.linear.scan")
    if not spent:
        return None
    cfg = run["config"]
    least = ob.widths(cfg)["Ll"] * ob.linear_scan_floor_s(
        cfg, run["peaks"], tokens=gains[0], pieces=gains[1] + gains[2])
    return 100.0 * least / (run["chips"] * spent)
