"""Frames whose result the client held, ready, inside the window, over the
window's length (host clock)."""


def read(run):
    return len(run.ready_in_window()) / run.seconds
