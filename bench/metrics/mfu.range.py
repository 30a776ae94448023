"""The traced window's share of the least time its work needs, in
percent: the sum of the least times (``roofline.least_seconds``) of every
ripple segment, AA match and contraction of the batches completed inside
the window, from their shapes, over the traced window's length (the
profiler's own clock)."""


def read(run):
    import roofline
    if run.peaks is None or run.device is None or not run.work:
        return None
    least = sum(roofline.least_seconds(w, run.peaks)
                for works in run.work.values() for w in works)
    return 100.0 * least / run.device.window_s
