"""Cloud steps the round engine issued per request served in the window
(the dataplane's ``DispatchStats.steps``, counted by the program)."""


def read(run):
    served = run.serve["served"]
    if not served:
        return None
    return run.steps / served
