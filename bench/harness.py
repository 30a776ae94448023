"""Run one cell of the benchmark once and print its result line.

Everything that belongs to a cell is found by name: the ``workloads``
entry of ``BENCHMARK.json``, ``configs/<config>.json`` (whose ``system``
names a module of ``systems/``), ``traffic/<cell>.json`` and one
``metrics/<metric>.py`` per per-layer metric. A run:

1. refuses to start without a TPU (or with fewer chips than the cell asks
   for), and keeps JAX's compile cache at a fixed path of the checkout;
2. builds the deployment from the seed and warms every batch shape the
   traffic can produce through the same server (set-up ends here);
3. starts the server and the closed-loop clients, each calling
   ``QueryServer.submit`` then ``QueryRequest.wait``, for ``seconds``;
4. waits for the requests still in flight, reads the device's peak
   memory, frees the program's state and compares every answer with the
   plain reference (``reference.py``).

With ``trace`` the window runs under the JAX profiler and the line holds
the per-layer metrics; without, the end-to-end metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
LATE_S = 60.0                # a request may finish this long after the close


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload with its configuration, traffic and metrics."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    import traffic as traffic_mod
    bench = bench or load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in e2e_names]
    return Cell(name, w["chips"], config, traffic_mod.load(name), e2e, layer)


def metric_reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the machine
# ---------------------------------------------------------------------------

def use_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    ``.jax_cache/`` in the checkout; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(chips: int) -> dict:
    """The devices, or :class:`NoChip`: never a fall-back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_memory(chips: int) -> dict:
    """The peak bytes in use on the fullest chip, and the least limit any
    of the chips allows (None where the backend reports neither)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    peaks = [m["peak_bytes_in_use"] for m in stats
             if "peak_bytes_in_use" in m]
    limits = [m["bytes_limit"] for m in stats if "bytes_limit" in m]
    return {"memory_peak_bytes": max(peaks) if peaks else None,
            "memory_limit_bytes": min(limits) if limits else None}


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.count, self.seconds = 0, 0.0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """One request as its client saw it (host clock, seconds)."""
    client: int
    request: dict
    submitted: float
    done: Optional[float] = None
    result: object = None
    error: Optional[str] = None
    handle: object = None           # the program's QueryRequest

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done is None else self.done - self.submitted


def _span(name: str, trace: bool):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation(name)
    import contextlib
    return contextlib.nullcontext()


def drive(deployment, streams, seconds: float, trace: bool) -> tuple:
    """Closed-loop clients for ``seconds``; returns (records, window start,
    window end, whether a client is still stuck after the late allowance).
    """
    server = deployment.server
    records: List[Record] = []
    lock = threading.Lock()
    start = threading.Event()
    t_bounds = {}

    def client(i: int, stream) -> None:
        start.wait()
        while True:
            now = time.perf_counter()
            if now >= t_bounds["end"]:
                return
            req = next(stream)
            rec = Record(i, req, now)
            with lock:
                records.append(rec)
            try:
                with _span("bench.submit", trace):
                    rec.handle = server.submit(deployment.plan(req),
                                               relation=deployment.relation)
                with _span("bench.wait", trace):
                    rec.handle.wait(timeout=t_bounds["end"] - now + LATE_S)
                rec.done = time.perf_counter()
                if rec.handle.error is not None:
                    rec.error = repr(rec.handle.error)
                else:
                    rec.result = rec.handle.result
            except Exception as e:  # noqa: BLE001 — the request failed
                rec.error = repr(e)
                return

    threads = [threading.Thread(target=client, args=(i, s), daemon=True,
                                name=f"bench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    with _span("bench.window", trace):
        t0 = time.perf_counter()
        t_bounds["end"] = t0 + seconds
        start.set()
        time.sleep(seconds)
    for t in threads:
        t.join(timeout=LATE_S + 30.0)
    return records, t0, t0 + seconds, any(t.is_alive() for t in threads)


def batches_of(records: List[Record]) -> List[List[Record]]:
    """The server's batches, from when each request completed: a batch's
    requests share one completion stamp."""
    groups: Dict[float, List[Record]] = {}
    for r in records:
        h = r.handle
        if h is None or not h.done():
            continue
        groups.setdefault(round(h.enqueued_at + h.latency_s, 4), []).append(r)
    return [groups[k] for k in sorted(groups)]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer metric reads."""
    serve: dict                      # ServeStats.snapshot() of the window
    steps: int                       # dataplane cloud steps in the window
    compiles: int                    # backend compiles in the window
    device: Optional[object]         # devtrace.DeviceSummary, or None
    peaks: Optional[dict]
    work: Dict[str, list]            # kind -> [roofline.Work per batch
    #                                  completed inside the window]
    family_ms: Dict[str, list]       # request family -> latencies (ms) of
    #                                  its requests answered in the window

    def family_p50_ms(self, family: str) -> Optional[float]:
        lat = self.family_ms.get(family)
        return percentile(lat, 50) if lat else None


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import roofline
    import devtrace
    import traffic as traffic_mod
    if require_chip:
        use_compile_cache()
    device = (find_chips(cell.chips) if require_chip
              else device_info(cell.chips))
    peaks = roofline.peaks_for(device["kind"]) if require_chip else None
    import repro  # noqa: F401  (x64 before any program runs)
    clock = CompileClock()
    system = importlib.import_module(f"systems.{cell.config['system']}")

    deployment = system.Deployment(cell.config, cell.traffic, seed)
    server = deployment.server
    warm_errors: List[str] = []
    for batch in system.warm_requests(cell.traffic, deployment):
        handles = [server.submit(deployment.plan(r),
                                 relation=deployment.relation)
                   for r in batch]
        while server.pending():
            server.pump("warm-up")
        warm_errors += [repr(h.error) for h in handles
                        if h.error is not None]
    streams = traffic_mod.client_streams(cell.traffic, seed,
                                         deployment.column_values,
                                         cell.config)
    server.reset()
    steps0 = deployment.plane_stats().steps
    compiles0 = clock.count
    server.start()
    if trace:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    records, t0, t_end, stuck = drive(deployment, streams, seconds, trace)
    if trace:
        import jax
        jax.profiler.stop_trace()
    server.stop()
    serve = dict(server.stats.snapshot(),
                 queue_waits_s=list(server.stats.queue_waits_s))
    steps = deployment.plane_stats().steps - steps0
    compiles = clock.count - compiles0
    memory = peak_memory(cell.chips)
    work = {}
    for b in batches_of(records):
        if any(r.done is None or r.done > t_end for r in b):
            continue                # its device work lies past the window
        for kind, w in deployment.batch_work([r.request for r in b]).items():
            work.setdefault(kind, []).append(w)
    for r in records:
        r.handle = None
    server.close()
    deployment.release()
    del server
    gc.collect()

    checks = deployment.compare(records)
    checks["unanswered"] = (sum(r.result is None for r in records)
                            + len(warm_errors) + int(stuck))
    limits = dict(cell.config["limits"])
    correct = all(checks[k] <= limits[k] for k in limits) and not stuck

    in_window = [r for r in records if r.done is not None and r.done <= t_end
                 and r.result is not None]
    lat_ms = [r.latency_s * 1e3 for r in records if r.result is not None]
    family_ms: Dict[str, list] = {}
    for r in in_window:
        family_ms.setdefault(r.request.get("family"), []).append(
            r.latency_s * 1e3)
    e2e = {"setup_s": setup_s,
           "queries_per_s": len(in_window) / seconds,
           "latency_p50_ms": percentile(lat_ms, 50) if lat_ms else None,
           "latency_p95_ms": percentile(lat_ms, 95) if lat_ms else None}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": sum(r.error is not None for r in records)}
    dev = dict(device, **memory)
    if trace:
        events = devtrace.load(TRACE_DIR)
        windows = [e for e in events if e.name == "bench.window"]
        summary = (devtrace.summarize(events, (windows[0].start_ns,
                                                windows[0].end_ns))
                   if windows else None)
        run = Run(serve, steps, compiles, summary, peaks, work, family_ms)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
            result["breakdown"] = {
                "device_ops": devtrace.top(summary.program_s),
                "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": units[m["name"]]}
                             for m in cell.end_to_end
                             if e2e.get(m["name"]) is not None}
    result["device"] = dev
    errors = {r.error for r in records if r.error} | set(warm_errors)
    result["errors"] = [e[:300] for e in sorted(errors)[:3]]
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result


def print_result(result: dict) -> None:
    for e in result["errors"]:
        print(f"failed request: {e}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
