"""Share of the traced window in which the chip was idle while the host
did the user's side of a batch: planning (``client.plan``), sharing the
batch's predicates and one-hots (``user.share``) and opening what the
clouds returned (``user.open``), in percent (``idlesplit.idle_under``)."""


def read(run):
    import idlesplit
    return idlesplit.idle_percent(
        run, lambda name: name.startswith(("client.", "user.")))
