"""serving: the client's time to first token (due instant -> first streamed
token, over HTTP through the gateway), 90th percentile over the window's
completed requests. Recorded, not judged: a first token waits for the next
boundary of a ~275 ms decode chunk wherever its request lands, so at the
cell's ~180 requests the percentile moves 2-6 % between runs of the same code
(PERF.md section 6), more than half of any bound a metric may have."""

from harness import client


def read(run):
    return client.ttft_percentile(run, 90)
