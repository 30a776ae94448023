"""The share-space contraction's share of its roofline, in percent: the
least time of every contraction the batches completed inside the
window made (``roofline``:
one-hot fetch and embedding lookup shares against the relation or table,
counted from their shapes) over the device time of the programs that run
it, found by name in the trace."""

# field.matmul under jit (the jnp backend), and the Pallas kernel
PROGRAMS = ("jit_matmul", "jit_ss_matmul")


def read(run):
    import roofline
    work = run.work.get("contraction")
    if not work or run.device is None or run.peaks is None:
        return None
    seconds = run.device.seconds_of(PROGRAMS)
    if seconds <= 0:
        return None
    least = sum(roofline.least_seconds(w, run.peaks) for w in work)
    return 100.0 * least / seconds
