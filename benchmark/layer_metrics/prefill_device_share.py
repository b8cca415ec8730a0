"""serving: the device's self seconds under the scope `prefill` over its busy
seconds in the traced window, in %: what the admissions take from the decode
steps they run between (harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    t = scope_trace.table(run)
    prefill = scope_trace.seconds_under(t, "prefill") if t else 0.0
    if not prefill:
        return None
    return 100.0 * prefill / t["busy_s"]
