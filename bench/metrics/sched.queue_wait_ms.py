"""Mean time a request waited in the server's queue before its batch
closed, over the window's requests (``ServeStats.queue_waits_s``, taken by
the server on the host clock)."""


def read(run):
    waits = run.serve["queue_waits_s"]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
