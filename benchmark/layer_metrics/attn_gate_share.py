"""model: of the device's self seconds under `decode`, those of the attention
gate: the scope `attn.gate` (the product with the sigmoid, between the read
and `attn.out`) plus the gate projection's part of `attn.qkv`, taken as its
share of that scope's kernels (a decode step's projections are bound by
their weights' bytes: harness/ops_bytes_trinity.gate_params over the q, k, v
and gate projections), in % (harness/scope_trace.py). Nothing where the
program carries no such scope."""

from harness import ops_bytes_trinity as ob
from harness import scope_trace


def read(run):
    cfg = run.get("config", {})
    gate = scope_trace.share_of_decode(run, "attn.gate")
    if not gate or cfg.get("model_type") != "afmoe":
        return None
    qkv = scope_trace.share_of_decode(run, "attn.qkv") or 0.0
    w = ob.widths(cfg)
    projections = (2 * w["D"] * w["H"] * w["hd"]
                   + 2 * w["D"] * w["KV"] * w["hd"])
    return gate + qkv * ob.gate_params(cfg) / projections
