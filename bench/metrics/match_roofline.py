"""The AA match's share of its roofline, in percent: the least time of
every stacked match of the batches completed inside the window (``roofline.match``, from
their shapes) over the device time of the matcher programs, found by name
in the trace."""

# the jnp backend's batched and sliding matchers under jit
PROGRAMS = ("jit_run", "jit_aa_slide")


def read(run):
    import roofline
    work = run.work.get("match")
    if not work or run.device is None or run.peaks is None:
        return None
    seconds = run.device.seconds_of(PROGRAMS)
    if seconds <= 0:
        return None
    least = sum(roofline.least_seconds(w, run.peaks) for w in work)
    return 100.0 * least / seconds
