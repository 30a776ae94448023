"""Median client-side latency (submit to ``wait()`` return) of the
window's ``max_price`` requests, on the host clock: the cost of one
query family, apart from the mix's weights."""


def read(run):
    return run.family_p50_ms("max_price")
