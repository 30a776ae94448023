"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's op intervals / window), from the profiler's
trace, in percent."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * run.device.idle_share
