"""serving: the device's self seconds under the scope `prefill` (an
admission's suffix forward, a piece of a chunked one) over the calls of the
programs that carry it, counted on the trace's module line, in ms: the
device half of an admission, which `admit_ms` does not see
(harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    t = scope_trace.table(run)
    calls = scope_trace.calls_with(t, "prefill") if t else 0
    if not calls:
        return None
    return 1e3 * scope_trace.seconds_under(t, "prefill") / calls
