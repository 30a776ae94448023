"""Share of the traced window in which the chip was idle while the host
was under no program span (the scheduler between its spans, the
benchmark's clients), in percent (``idlesplit.idle_under``). With the
three other ``idle.*`` metrics it splits ``device.idle_share``."""


def read(run):
    import idlesplit
    return idlesplit.idle_percent(run, lambda name: name == idlesplit.UNTRACED)
