"""Idle time charged to the program's spans: the innermost span open at
an idle instant takes it, idle under no program span is ``untraced``, and
the parts split the idle share. A traced CPU run of ``lineitem.match``
carries the program's spans and reads no device metric."""
import time
import types

import pytest

import devtrace
import harness
import idlesplit
from devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def _events():
    """Busy [0,1] [10,30] [50,60] ms of a 100-ms window; the server thread
    parks, then runs one batch, with two spans on a worker thread."""
    def span(line, name, lo, hi):
        return Event(HOST, line, name, lo * MS, (hi - lo) * MS)
    return [
        Event(HOST, "main", "bench.window", 0.0, 100 * MS),
        Event(DEV, "XLA Ops", "copy.4", -5 * MS, 6 * MS),
        Event(DEV, "XLA Ops", "dot.1", 10 * MS, 15 * MS),
        Event(DEV, "XLA Ops", "fusion.2", 20 * MS, 10 * MS),
        Event(DEV, "XLA Ops", "fusion.3", 50 * MS, 10 * MS),
        Event(DEV, "XLA Modules", "jit_run(3)", 50 * MS, 10 * MS),
        span("server", "serve.park", 0, 12),
        span("server", "serve.batch", 12, 90),
        span("server", "client.plan", 12, 14),
        span("server", "user.share", 14, 20),
        span("server", "cloud.match", 20, 35),
        span("server", "user.open", 35, 48),
        # same start as user.open and shorter: it is the innermost
        span("worker", "cloud.fetch", 35, 40),
        # starts inside serve.batch on another thread: latest start wins
        span("worker", "user.share", 55, 70),
        span("client", "bench.wait", 30, 100),
    ]


def _idle():
    return idlesplit.idle_under(_events(), (0.0, 100 * MS))


def test_the_innermost_span_takes_the_idle():
    idle = _idle()
    assert idle["serve.park"] == pytest.approx(0.009)    # 1..10
    assert idle["cloud.match"] == pytest.approx(0.005)   # 30..35
    assert idle["cloud.fetch"] == pytest.approx(0.005)   # 35..40
    assert idle["user.open"] == pytest.approx(0.008)     # 40..48
    assert idle["user.share"] == pytest.approx(0.010)    # 60..70
    assert idle["serve.batch"] == pytest.approx(0.022)   # 48..50, 70..90
    assert idle["client.plan"] == 0.0                    # the chip was busy
    assert "bench.wait" not in idle


def test_idle_under_no_program_span_is_untraced():
    assert _idle()[idlesplit.UNTRACED] == pytest.approx(0.010)  # 90..100


def test_the_parts_split_the_idle_share():
    summary = devtrace.summarize(_events(), (0.0, 100 * MS))
    idle = _idle()
    assert sum(idle.values()) == pytest.approx(
        (1 - summary.busy_s / summary.window_s) * summary.window_s)
    run = types.SimpleNamespace(device=summary,
                                _idle_split=(idle, summary.window_s))
    parts = [harness.metric_reader(m)(run) for m in (
        "idle.sched_park", "idle.user", "idle.engine", "idle.untraced")]
    assert parts == pytest.approx([9.0, 18.0, 32.0, 10.0])
    assert sum(parts) == pytest.approx(
        harness.metric_reader("device.idle_share")(run))


def test_a_trace_without_program_spans_reads_nothing():
    plain = [e for e in _events()
             if not idlesplit.PROGRAM_SPAN.match(e.name)]
    idle = idlesplit.idle_under(plain, (0.0, 100 * MS))
    assert idle == {idlesplit.UNTRACED: pytest.approx(0.069)}
    summary = devtrace.summarize(plain, (0.0, 100 * MS))
    run = types.SimpleNamespace(device=summary,
                                _idle_split=(idle, summary.window_s))
    for m in ("idle.sched_park", "idle.user", "idle.engine",
              "idle.untraced", "device.copy_share"):
        assert harness.metric_reader(m)(run) is None
    host_only = [e for e in _events() if e.plane == HOST]
    assert idlesplit.idle_under(host_only, (0.0, 100 * MS)) is None


def test_copy_share_is_zero_when_no_copy_program_ran():
    summary = devtrace.summarize(_events(), (0.0, 100 * MS))
    run = types.SimpleNamespace(device=summary,
                                _idle_split=(_idle(), summary.window_s))
    assert harness.metric_reader("device.copy_share")(run) == 0.0


NEW_METRICS = ("idle.sched_park", "idle.user", "idle.engine",
               "idle.untraced", "device.copy_share")


def test_traced_cpu_run_has_the_spans_and_no_device_metric(monkeypatch,
                                                          tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    groups = []
    batches_of = harness.batches_of

    def recording(records):
        out = batches_of(records)
        groups.append((records, out))
        return out
    monkeypatch.setattr(harness, "batches_of", recording)
    cell = harness.load_cell("lineitem.match")
    cell.config["rows"] = 48
    r = harness.run_cell(cell, 2**31 + 91, 1.5, True,
                         t_start=time.perf_counter(), require_chip=False)
    assert r["correct"], r["checks"]
    assert not set(NEW_METRICS) & set(r["metrics"])
    names = {e.name for e in devtrace.load(str(tmp_path))}
    assert {"serve.park", "serve.batch", "client.plan", "user.share",
            "cloud.match", "user.open"} <= names
    # the scheduler's clock stamps every request of one batch alike
    (records, batches), = groups
    assert sum(len(b) for b in batches) == sum(
        rec.done is not None for rec in records)
    assert sum(len(b) for b in batches) / len(batches) == pytest.approx(
        r["metrics"]["sched.batch_fill"]["value"])
