"""Share of the traced window in which the chip was idle while the host
worked between protocol rounds: under the planner (``client.*``), the
user's steps (``user.*``), the clouds' steps and re-shares (``cloud.*``)
or the round engine (``serve.batch`` outside any inner span), in percent
(``idlesplit.idle_under``)."""


def read(run):
    import idlesplit
    return idlesplit.idle_percent(
        run, lambda name: (name.split(".")[0] in ("client", "user", "cloud")
                           or name == "serve.batch"))
