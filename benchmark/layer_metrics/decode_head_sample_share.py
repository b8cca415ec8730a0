"""model: of the device's self seconds under `decode`, those of `head` (final
norm and unembedding) and `sample` (filtering, top-k, the draw), in %: what a
step pays a ROW of the batch and not a live request
(harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    return scope_trace.share_of_decode(run, "head", "sample")
