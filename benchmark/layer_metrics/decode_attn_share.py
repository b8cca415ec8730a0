"""model: of the device's self seconds under `decode`, those of the scopes
with `attn` on their path (`attn.qkv`, `attn.write`, `attn.read`, `attn.out`,
a pattern model's `attn.global` / `attn.window`, a latent model's `mla.*`,
which lie inside `attn`), in % (harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    return scope_trace.share_of_decode(run, "attn")
