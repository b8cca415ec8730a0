"""device: of the device's busy (self) seconds in the trace, those of ops that
joined the trace's own HLO table AND carry a scope of the vocabulary, in %:
the guard on the instrument. Low where an executable came out of a compile
cache written before the scopes (jax leaves metadata out of the cache's key)
or a fusion crossed out of every scope (harness/scope_trace.py)."""

from harness import scope_trace


def read(run):
    t = scope_trace.table(run)
    if not t or not t["busy_s"] or not t["programs_in_table"]:
        return None
    return 100.0 * (1.0 - t["unscoped_s"] / t["busy_s"])
