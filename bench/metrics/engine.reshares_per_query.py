"""Re-sharing rounds (degree reductions) the round engine issued per
request served in the window (``DispatchStats.reshares`` folded into
``ServeStats.snapshot()``, counted by the program). None where the
program does not count them."""


def read(run):
    served, reshares = run.serve["served"], run.serve.get("reshares")
    if not served or reshares is None:
        return None
    return reshares / served
