"""Mean batch size over the batches the server closed in the window
(``ServeStats.batch_fill``: batch size -> batches)."""


def read(run):
    fill = run.serve["batch_fill"]
    batches = sum(fill.values())
    if not batches:
        return None
    return sum(int(size) * n for size, n in fill.items()) / batches
