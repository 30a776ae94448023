"""The SS-SUB ripple's share of its roofline, in percent: the least time
of every ripple segment of the batches completed inside the window (the
range chains and the MAX tournament's levels; ``systems.analytics.ripple``,
from their shapes, bound by HBM bytes) over the device time of the ripple
programs, found by name in the trace."""

# the jnp backend's fused segment under jit, for ranges and tournaments
PROGRAMS = ("jit_ripple_segment",)


def read(run):
    import roofline
    work = run.work.get("ripple")
    if not work or run.device is None or run.peaks is None:
        return None
    seconds = run.device.seconds_of(PROGRAMS)
    if seconds <= 0:
        return None
    least = sum(roofline.least_seconds(w, run.peaks) for w in work)
    return 100.0 * least / seconds
