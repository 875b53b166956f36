"""Device: busy time in the traced window (summed over the chips used),
in ms, per frame whose result was ready in the window."""


def read(run):
    done = len(run.ready_in_window())
    if run.trace is None or not done:
        return None
    return sum(run.trace.busy_s.values()) * 1e3 / done
