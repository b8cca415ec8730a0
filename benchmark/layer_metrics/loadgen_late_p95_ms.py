"""serving (the yardstick itself): how late the generator sent a request,
actual send minus due time, 95th percentile over the window's requests. A
guard: a starved generator is not a fast server."""

from harness.window import percentile


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.get("records", ())
            if "sent" in r]
    return percentile(late, 95) if late else None
