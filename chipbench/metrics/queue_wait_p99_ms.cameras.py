"""Serving front end: 99th percentile of ``Request.queue_ms`` (submit to
batch pickup) over every frame due in the window."""
from chipbench.record import percentile


def read(run):
    if not run.open_loop:
        return None
    return percentile([f.queue_ms for f in run.attempted()
                       if f.queue_ms is not None], 99)
