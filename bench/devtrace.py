"""Reduce a JAX profiler trace to the device numbers the benchmark reports.

``load`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes into
:class:`Event` rows; everything else works on those rows, so the tests
feed it a small recorded trace. On a TPU the trace holds one plane per
chip (``/device:TPU:<i>``) whose ``XLA Modules`` line has one event per
program run (``jit_matmul(123)``) and whose ``XLA Ops`` line has one per
operation; host threads sit on ``/host:CPU``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host events that name what the host was doing; the benchmark's own
# spans start with ``bench.``
_HOST_LABEL = re.compile(r"^(bench\.\S+|PjitFunction\(.+\))$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


def program_name(event_name: str) -> str:
    """``jit_matmul(1234)`` -> ``jit_matmul``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _device_events(events: Iterable[Event], line: str) -> Dict[str, List[Event]]:
    out: Dict[str, List[Event]] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == line:
            out.setdefault(e.plane, []).append(e)
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


@dataclasses.dataclass
class DeviceSummary:
    """What one traced window says about the chips it used."""
    window_s: float
    busy_s: float                   # union of op intervals, mean over chips
    program_s: Dict[str, float]     # program -> summed device seconds
    op_s: Dict[str, float]          # operation -> summed device seconds
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, programs: Sequence[str]) -> float:
        """Summed device seconds of the named programs (exact names)."""
        return sum(self.program_s.get(p, 0.0) for p in programs)


def summarize(events: Sequence[Event], window: Tuple[float, float],
              n_gaps: int = 10) -> Optional[DeviceSummary]:
    """Reduce the events that fall in ``window`` (start, end in the
    trace's nanoseconds). None when no device operation ran in it."""
    lo, hi = window
    inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
    ops = _device_events(inside, OPS_LINE) or _device_events(inside,
                                                             MODULES_LINE)
    if not ops or hi <= lo:
        return None
    busy = []
    for evs in ops.values():
        merged = _union([(max(e.start_ns, lo), min(e.end_ns, hi))
                         for e in evs])
        busy.append(sum(b - a for a, b in merged))
    program_s: Dict[str, float] = {}
    for evs in _device_events(inside, MODULES_LINE).values():
        for e in evs:
            name = program_name(e.name)
            program_s[name] = program_s.get(name, 0.0) + e.dur_ns * 1e-9
    op_s: Dict[str, float] = {}
    for evs in ops.values():
        for e in evs:
            op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns * 1e-9
    first = sorted(ops)[0]
    merged = _union([(e.start_ns, e.end_ns) for e in ops[first]])
    gaps = [(a, b) for (_, a), (b, _) in zip(
        [(lo, lo)] + merged, merged + [(hi, hi)]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in inside if e.plane == HOST_PLANE
            and _HOST_LABEL.match(e.name)]
    labelled = [(_label_gap(host, a, b), (b - a) * 1e-9)
                for a, b in gaps[:n_gaps]]
    return DeviceSummary(window_s=(hi - lo) * 1e-9,
                         busy_s=sum(busy) / len(busy) * 1e-9,
                         program_s=program_s, op_s=op_s,
                         idle_gaps=labelled)


def _label_gap(host: Sequence[Event], lo: float, hi: float) -> str:
    """What the host was doing in a device gap: the program call (a
    ``PjitFunction`` event) that covers most of it, else the benchmark's
    own span that does; of equal cover, the innermost (shortest)."""
    best, best_key = "host: no traced span", None
    for e in host:
        cover = min(e.end_ns, hi) - max(e.start_ns, lo)
        if cover <= 0:
            continue
        key = (not e.name.startswith("bench."), cover, -e.dur_ns)
        if best_key is None or key > best_key:
            best, best_key = e.name, key
    return best


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(items.items(),
                                      key=lambda kv: -kv[1])[:n]]
