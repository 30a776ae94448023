"""Share of the traced window in which the chip was idle while the host
issued cloud steps (``cloud.<phase>``) or ran the round engine between
them (inside ``serve.batch`` but no inner span), in percent
(``idlesplit.idle_under``)."""


def read(run):
    import idlesplit
    return idlesplit.idle_percent(
        run, lambda name: name.startswith("cloud.") or name == "serve.batch")
