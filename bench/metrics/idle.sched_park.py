"""Share of the traced window in which the chip was idle while the
server's scheduler was parked (the span ``serve.park``: no batch due, the
scheduler waiting out a deadline or for a request), in percent
(``idlesplit.idle_under``)."""


def read(run):
    import idlesplit
    return idlesplit.idle_percent(run, lambda name: name == "serve.park")
