"""Executor: share of the group rows dispatched to the stage programs that
carried no frame, the padding of ragged groups up to the microbatch
(``ExecutorStats.rows_padded`` over ``rows_dispatched``).  None where the
program keeps no such counter."""


def read(run):
    ex = run.executor
    if not ex or not ex.get("rows_dispatched"):
        return None
    return 100.0 * ex["rows_padded"] / ex["rows_dispatched"]
