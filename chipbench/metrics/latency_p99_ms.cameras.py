"""Serving front end, seen from the client: 99th percentile, over every
frame due in the window, of the time from its due time to the client
holding its ready result (host clock).  Host stalls of about 100 ms, in
most runs on a one-chip host, set it; it is the end-to-end tail without a
bound."""
from chipbench.record import percentile


def read(run):
    if not run.open_loop:
        return None
    return percentile(run.latencies_ms(), 99)
