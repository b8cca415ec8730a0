"""kernels: the sparse layer's decode SELECTION (docs/SALA.md: the row's
compressed keys read, the score products, the group sum, the pooling and
the top-k, under the scope `attn.select`) against the chip's peaks: the
selecting rows' compressed keys read once a layer a step or their score
products, the larger (harness/ops_bytes_sala.select_floor_s), over the
device's self seconds under `decode/../attn.select`, in %. The slots the
selecting rows held a step are the program's own count around the traced
seconds (`serving/sparse_slots_held` over `serving/decode_steps`); sparse
layers are the configuration's, steps the trace's. The program gathers
EVERY resident row's compressed keys through its table, selecting or not,
so the share reads low by the rows that read dense. Nothing where the trace
has no such scope or the program no such counters."""

from harness import ops_bytes_sala as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    if "mixer_types" not in (run.get("config") or {}):
        return None
    gains = traced(run, "serving/sparse_slots_held", "serving/decode_steps")
    t = scope_trace.table(run)
    if not gains or not t or gains[1] <= 0 or not t.get("steps"):
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope_trace.has(scope, "attn.select"))
    if not spent or gains[0] <= 0:
        return None
    cfg = run["config"]
    layer_steps = t["steps"] * ob.widths(cfg)["Ls"]
    least = layer_steps * ob.select_floor_s(
        cfg, run["peaks"], slots_held=gains[0] / gains[1]) / run["chips"]
    return 100.0 * least / spent
