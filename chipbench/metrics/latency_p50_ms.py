"""Median, over every frame due in the window, of the time from its due
time to the client holding its ready result (host clock)."""
from chipbench.record import percentile


def read(run):
    if not run.open_loop:
        return None
    return percentile(run.latencies_ms(), 50)
