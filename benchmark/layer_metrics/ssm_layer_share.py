"""model: of the device's self seconds under `decode`, those of the
state-space mixer's scopes (`attn.ssm` and, inside it, `attn.ssm.in`,
`attn.ssm.conv`, `attn.ssm.update`, `attn.ssm.gate`, `attn.ssm.out` and the
state's `attn.write`), in % (harness/scope_trace.py): what the mixers cost of
a decode step, beside `decode_attn_share`, which holds them AND the attention
they stand beside. Nothing where the program carries no such scope."""

from harness import scope_trace


def read(run):
    share = scope_trace.share_of_decode(run, "attn.ssm")
    return share or None
