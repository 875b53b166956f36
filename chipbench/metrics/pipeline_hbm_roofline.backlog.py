"""Stage programs and kernels: the least HBM traffic the app needs for the
frames completed in the traced window, over what the chips could move in
the time they were busy.

The least traffic of one frame is 16 bytes a pixel: the float32 RGB frame
read once (12) and the float32 result written once (4).  It counts the
work the app must do, not what an implementation moves, so fusing or
re-laying-out a kernel changes the time and never the count.  Busy time
is summed over the chips, and the peak is one chip's.
"""


def read(run):
    pixels = sum(f.size for f in run.ready_in_window())
    if run.trace is None or not pixels:
        return None
    busy = sum(run.trace.busy_s.values())
    need = 16 * pixels
    return 100.0 * need / (busy * run.peak["hbm_bytes_per_s"])
