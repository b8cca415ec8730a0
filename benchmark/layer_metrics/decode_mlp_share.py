"""model: of the device's self seconds under `decode`, those of the scopes
with `mlp` on their path (the dense SwiGLU and its residual add; an expert
model's `moe.*`, which lie inside `mlp`), in % (harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    return scope_trace.share_of_decode(run, "mlp", "moe")
