"""Executor: host time dispatching stage programs (the sum over stages of
``per_stage[].issue_ms``) per frame retired in the window."""


def read(run):
    return run.per_frame("issue_ms")
