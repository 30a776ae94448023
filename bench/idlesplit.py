"""Charge the chip's idle time to the program's own spans.

The program opens a span (``repro.core.dataplane.span``, a
``jax.profiler.TraceAnnotation``) around the server's park and batch
(``serve.*``), the planner (``client.*``), the user's steps (``user.*``)
and the clouds' steps (``cloud.*``); they land on the host plane of the
profiler's trace, on the device's clock. :func:`idle_under` walks the idle
instants of a traced window and charges each to the innermost program span
open then; the ``idle.*`` metrics read its result through
:func:`idle_percent`, which loads the run's trace once (:func:`program_idle`)
and keeps the split on the run.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Sequence, Tuple

import devtrace
from devtrace import Event

PROGRAM_SPAN = re.compile(r"^(serve|client|user|cloud)\.\S+$")
UNTRACED = "untraced"
WINDOW = "bench.window"


def idle_under(events: Sequence[Event], window: Tuple[float, float]
               ) -> Optional[Dict[str, float]]:
    """Idle seconds of ``window`` (trace nanoseconds) by program span.

    Walks the idle instants of the first chip, the op union that
    ``devtrace.summarize`` reads, and charges each to the innermost
    program span open then on any host thread (latest start, then
    shortest); instants under no program span go to ``UNTRACED``. Every
    program span seen in the window has a key, 0.0 where no idle fell
    under it. None when no device operation ran in the window.
    """
    lo, hi = window
    inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
    ops = (devtrace._device_events(inside, devtrace.OPS_LINE)
           or devtrace._device_events(inside, devtrace.MODULES_LINE))
    if not ops or hi <= lo:
        return None
    merged = devtrace._union([(max(e.start_ns, lo), min(e.end_ns, hi))
                              for e in ops[sorted(ops)[0]]])
    gaps = [(a, b) for (_, a), (b, _) in zip(
        [(lo, lo)] + merged, merged + [(hi, hi)]) if b > a]
    spans = [e for e in inside if e.plane == devtrace.HOST_PLANE
             and PROGRAM_SPAN.match(e.name)]
    out = {e.name: 0.0 for e in spans}
    out[UNTRACED] = 0.0
    # (time, 0 = opens / 1 = closes, span): at equal times a span opens
    # before it closes, so an empty span leaves nothing open
    edges = sorted([(max(e.start_ns, lo), 0, i) for i, e in enumerate(spans)]
                   + [(min(e.end_ns, hi), 1, i) for i, e in enumerate(spans)])
    times = sorted({t for gap in gaps for t in gap}
                   | {t for t, _, _ in edges})
    open_: set = set()
    j = g = 0
    for t, t_next in zip(times, times[1:]):
        while j < len(edges) and edges[j][0] <= t:
            _, closes, i = edges[j]
            (open_.discard if closes else open_.add)(i)
            j += 1
        while g < len(gaps) and gaps[g][1] <= t:
            g += 1
        if g == len(gaps) or gaps[g][0] > t:
            continue                        # the chip is busy here
        name = UNTRACED
        if open_:
            inner = max(open_, key=lambda i: (spans[i].start_ns,
                                              -spans[i].dur_ns))
            name = spans[inner].name
        out[name] += (t_next - t) * 1e-9
    return out


def program_idle(run) -> Optional[Tuple[Dict[str, float], float]]:
    """The run's idle split (:func:`idle_under`) and its window in
    seconds, from the trace the harness left in ``harness.TRACE_DIR``,
    read once per run and kept on it. None where the trace has no device
    plane or holds no program span, as for a program that emits none."""
    if not hasattr(run, "_idle_split"):
        split = None
        if run.device is not None:
            import harness
            events = devtrace.load(harness.TRACE_DIR)
            windows = [e for e in events if e.name == WINDOW]
            if windows:
                w = windows[0]
                idle = idle_under(events, (w.start_ns, w.end_ns))
                if idle is not None:
                    split = (idle, w.dur_ns * 1e-9)
        run._idle_split = split
    split = run._idle_split
    if split is None or set(split[0]) == {UNTRACED}:
        return None
    return split


def idle_percent(run, charged: Callable[[str], bool]) -> Optional[float]:
    """Percent of the traced window idle under the program spans whose
    names ``charged`` accepts; None as :func:`program_idle` is."""
    split = program_idle(run)
    if split is None:
        return None
    idle, window_s = split
    return 100.0 * sum(s for name, s in idle.items()
                       if charged(name)) / window_s
