"""Executor: host time retirement spent slicing finished groups into
frames (``ExecutorStats.unstack_ms``, the program's ``retire.unstack``
spans) per frame retired in the window.  None where the program keeps no
such counter."""


def read(run):
    ex = run.executor
    if not ex or "unstack_ms" not in ex or not ex["tokens_retired"]:
        return None
    return ex["unstack_ms"] / ex["tokens_retired"]
