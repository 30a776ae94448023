"""The traced window's share of the chip's int8 peak, in percent: the
fewest int8 operations any exact method needs for all the matches and
contractions of the batches completed inside the window (``roofline``,
from their shapes), over the traced window's length (the profiler's own
clock) times the peak. A kernel taken off the path leaves its roofline
share silent; this one still reads the whole window."""


def read(run):
    if run.peaks is None or run.device is None or not run.work:
        return None
    ops = sum(w.int8_ops for works in run.work.values() for w in works)
    return 100.0 * ops / (run.device.window_s * run.peaks["int8_ops_per_s"])
