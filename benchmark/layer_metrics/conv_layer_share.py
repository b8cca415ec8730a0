"""model: of the device's self seconds under `decode`, those of the conv
operator's scopes (`attn.conv` and, inside it, `attn.conv.in`,
`attn.conv.mix`, `attn.conv.out` and the state's `attn.write`), in %
(harness/scope_trace.py): what the conv layers' operators cost of a decode
step, beside `decode_attn_share`, which holds both kinds of operator.
Nothing where the program carries no such scope."""

from harness import scope_trace


def read(run):
    share = scope_trace.share_of_decode(run, "attn.conv")
    return share or None
