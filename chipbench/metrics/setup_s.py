"""Process start to the start of the measured window (host clock):
tracing the app, planning, compiling or loading every program from the
cache, warming every shape, and drawing the frames."""


def read(run):
    return run.setup_s
