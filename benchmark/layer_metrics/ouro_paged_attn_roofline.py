"""kernels: the in-place paged decode read on a looped model's pool (16 KV
heads of 128: 1 MiB of K and V a page a cache layer; the kernel is every
served cell's, its geometry here is not) against the HBM bandwidth: the K
and V bytes of the slots the live rows held (`serving/global_slots_read` a
step inside the traced seconds, times the steps the trace counted, times
`kv_bytes_per_token` over all `cache_layers`) over the bandwidth of
peaks.json, over the device's self seconds under `decode` in `attn.read`
(harness/scope_trace.py; a model of one kind writes beside `attn.read`, so
the write is not in it), in %. Nothing where the trace has no such scope or
the program no such counters."""

from harness import ops_bytes_ouro as ob
from harness import scope_trace
from layer_metrics.ouro_decode_roofline import per_step
from layer_metrics.ouro_decode_step_ms import looped


def read(run):
    if not looped(run):
        return None
    t = scope_trace.table(run)
    slots = per_step(run, "serving/global_slots_read")
    if not t or not t.get("steps") or not slots:
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "decode")
                and scope.split("/")[-1] == "attn.read")
    if not spent:
        return None
    least = t["steps"] * slots * ob.kv_bytes_per_token(run["config"]) / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
