"""model: of the device's self seconds under `decode`, those of the lightning
mixer's scopes (`attn.linear` and, inside it, `attn.linear.in`,
`attn.linear.update`, `attn.linear.gate`, `attn.linear.out` and the state's
`attn.write`), in % (harness/scope_trace.py): what the linear-attention
layers cost of a decode step, beside `decode_attn_share`, which holds them
AND the sparse layers' attention. Nothing where the program carries no such
scope."""

from harness import scope_trace


def read(run):
    share = scope_trace.share_of_decode(run, "attn.linear")
    return share or None
