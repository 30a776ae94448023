"""Device seconds of the round engine's named copy programs over the
chip's busy seconds, in percent, from the profiler's trace: the column
stack, the fetch's relayout of the relation, the prefix tile, the window
slices and the one-hot stack. 0.0 when none ran; None where the trace has
no device plane or no program span (a program that emits no spans
predates these names)."""

# rounds.stack_columns, fetch_relayout, prefix_tile, window_bits,
# stack_onehots under jit
PROGRAMS = ("jit_stack_columns", "jit_fetch_relayout", "jit_prefix_tile",
            "jit_window_bits", "jit_stack_onehots")


def read(run):
    import idlesplit
    if idlesplit.program_idle(run) is None or run.device.busy_s <= 0:
        return None
    return 100.0 * run.device.seconds_of(PROGRAMS) / run.device.busy_s
