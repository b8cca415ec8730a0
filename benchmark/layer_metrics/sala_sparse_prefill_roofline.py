"""kernels: a prefill piece's sparse attention (docs/SALA.md: a piece of a
prompt past `dense_len` selects for every query and walks the row's key
blocks under the selection's mask, `core/sala._attend_chosen`; a shorter
prompt's piece is the dense flash kernel) against the bf16 peak: the QK and
PV products of each query with the slots of the blocks it CHOSE
(harness/ops_bytes_sala.sparse_prefill_floor_s over
`prefill_query_slots`), for the requests due in the window, a sparse layer
each, over the device's self seconds under `prefill` in the sparse layers'
`attn.select` and `attn.read` (the dense piece's `attn.paged_flash` lies
inside it), scaled from the traced seconds to the window by the
prefilled tokens (`serving/state_tokens`), in %. A masked form that attends
over every key block some query of a piece chose reads low, and says so:
that is what a sparse prefill kernel would repair. Nothing where the trace
has no such scope or the run no records."""

from harness import ops_bytes_sala as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced


def read(run):
    cfg = run.get("config") or {}
    if "mixer_types" not in cfg:
        return None
    gains = traced(run, "serving/state_tokens")
    t = scope_trace.table(run)
    records = run.get("records") or []
    c = run.get("counters") or {}
    try:
        window_tokens = (c["end"]["serving/state_tokens"]
                         - c["start"]["serving/state_tokens"])
    except KeyError:
        return None
    if not gains or not t or gains[0] <= 0 or window_tokens <= 0 or not records:
        return None
    spent = sum(sec for scope, sec in t["by_scope"].items()
                if scope_trace.under(scope, "prefill")
                and (scope_trace.has(scope, "attn.select")
                     or scope_trace.has(scope, "attn.read")))
    if not spent:
        return None
    query_slots = sum(ob.prefill_query_slots(cfg, int(r["prompt_len"]))
                      for r in records if r.get("prompt_len"))
    if not query_slots:
        return None
    # the traced seconds' share of the window's prefill work, by its tokens
    share = gains[0] / window_tokens
    least = ob.widths(cfg)["Ls"] * ob.sparse_prefill_floor_s(
        cfg, run["peaks"], query_slots=query_slots * share)
    return 100.0 * least / (run["chips"] * spent)
