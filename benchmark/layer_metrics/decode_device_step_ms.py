"""rollout: the device's self seconds under the scope `decode` (every decode
loop of the one-jit rollout, the serving session's chunk) over the decode
steps COUNTED IN THE TRACE (the events of the costliest instruction under
`decode` .. `head`, which runs once a step: harness/scope_trace.py), in ms.
What a step costs on the device, whatever the host waits for. Nothing where
the program carries no such scope (the parent of the PR that wrote them)."""

from harness import scope_trace


def read(run):
    t = scope_trace.table(run)
    if not t or not t["steps"]:
        return None
    return 1e3 * scope_trace.seconds_under(t, "decode") / t["steps"]
