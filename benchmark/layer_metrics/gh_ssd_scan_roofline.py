"""kernels: a prefill piece's recurrence at this model's sizes (128 heads of
64, ONE group of state 128, chunks of 256; `ops/ssm.ssd_scan` under the
scope `attn.ssm.scan`) against the chip's peaks: the operations and bytes of
the EQUATIONS over the tokens the traced seconds prefilled
(harness/ops_bytes_granite_h.ssd_scan_floor_s: `5 P N` operations a head a
token, a token's operands, a row's state read and written once a piece; the
larger of operations over the bf16 peak and bytes over the HBM bandwidth),
over the device's self seconds under `prefill/../attn.ssm.scan`, in %. The
tokens and pieces are the program's own counts inside the traced seconds
(`serving/state_tokens`: the REAL tokens of the admission forwards;
`serving/state_resets` + `serving/state_piece_carries`: the forwards), a
mixer layer each. A float32 scan padded up to whole chunks reads low against
the matrix unit's peak. Nothing where the trace has no such scope, the
program no such counters, or the configuration is another model's."""

from harness import ops_bytes_granite_h as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced
from layer_metrics.gh_decode_step_ms import granite_h


def read(run):
    if not granite_h(run):
        return None
    gains = traced(run, "serving/state_tokens", "serving/state_resets",
                   "serving/state_piece_carries")
    t = scope_trace.table(run)
    if not gains or not t or gains[0] <= 0:
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "prefill")
                and scope.split("/")[-1] == "attn.ssm.scan")
    if not spent:
        return None
    cfg = run["config"]
    least = ob.widths(cfg)["Lm"] * ob.ssd_scan_floor_s(
        cfg, run["peaks"], tokens=gains[0], pieces=gains[1] + gains[2])
    return 100.0 * least / (run["chips"] * spent)
