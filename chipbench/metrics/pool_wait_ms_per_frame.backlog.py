"""Executor: host time admission waited for a full token pool to free a
slot (``ExecutorStats.pool_wait_ms``, the program's ``dispatch.pool_wait``
spans) per frame retired in the window.  None where the program keeps no
such counter."""


def read(run):
    ex = run.executor
    if not ex or "pool_wait_ms" not in ex or not ex["tokens_retired"]:
        return None
    return ex["pool_wait_ms"] / ex["tokens_retired"]
