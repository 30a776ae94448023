"""``correct`` for ``lineitem.range`` at 48 rows, on the CPU: a sound run
passes the reference; the float32 ripple control, an answer altered where
it is produced and half of a batch left out come out not correct. The
ripple's work follows the planner's segments."""
import time

import numpy as np
import pytest

import control_range
import harness
import reference_numeric
import roofline
from systems import analytics

SEED = 2**31 + 1577
CELL = "lineitem.range"


def _run(seconds=2.0):
    cell = harness.load_cell(CELL)
    cell.config["rows"] = 48
    return harness.run_cell(cell, SEED, seconds, False,
                            t_start=time.perf_counter(), require_chip=False)


@pytest.fixture
def served_backend():
    """Put the served backend back after a test that swaps it."""
    from repro.api import backends
    saved = backends.get_backend("jnp")
    yield
    backends.register_backend(saved, overwrite=True)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], (r["checks"], r["errors"])
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_ripple_control_is_not_correct(served_backend):
    control_range.install_control()
    r = _run()
    assert r["correct"] is False, r["checks"]
    assert (r["checks"]["wrong_answers"]["value"] > 0
            or r["checks"]["unanswered"]["value"] > 0), r["checks"]


def _alter_answers(monkeypatch):
    from repro.core.queries import rounds
    range_rounds = rounds.range_rounds

    def altered(be, db, jobs):
        out = range_rounds(be, db, jobs)
        return [out[0] + 1] + out[1:] if out else out
    monkeypatch.setattr(rounds, "range_rounds", altered)


def _drop_half_of_each_batch(monkeypatch):
    from repro.api import QueryClient
    run_batch = QueryClient.run_batch

    def half(self, plans, *, relation=None):
        kept = list(plans)[:(len(plans) + 1) // 2]
        out = run_batch(self, kept, relation=relation)
        return [out[i % len(out)] for i in range(len(plans))]
    monkeypatch.setattr(QueryClient, "run_batch", half)


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half_of_each_batch])
def test_faults_are_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = _run()
    assert not r["correct"], (fault.__name__, r["checks"])


def test_reference_bounds_follow_q6():
    assert reference_numeric.bounds({"year": 1993}) == (366, 730)
    assert reference_numeric.bounds({"around": 5, "within": 1}) == (4, 6)
    assert reference_numeric.bounds({"below": 24}) == (0, 23)
    table = reference_numeric.Table({
        "f": np.array(["A", "N", "A"]), "q": np.array([3, 5, 8])})
    agg = {"plan": "aggregate", "column": "q",
           "where": {"column": "f", "value": "A"}}
    assert table.answer(dict(agg, op="avg")) == ("avg", 11, 2, 5.5)
    assert table.answer(dict(agg, op="sum")) == ("sum", 11)
    assert table.answer({"plan": "aggregate", "op": "max",
                         "column": "q"}) == ("max", 8)


def test_ripple_work_follows_the_planner():
    """A 14-bit range group at 27 clouds ripples in two segments (13 and 1
    bits, one re-share); a 26-bit MAX in three a level (12, 12, 2). Q1's
    sums contract against as many limb columns as the program opens."""
    assert analytics.segments(14, 13) == [13, 1]
    assert analytics.segments(26, 12) == [12, 12, 2]
    assert analytics.segments(8, 0) == [8]
    assert analytics.ripple(27, 4, 16, 13, False).bytes == 4 * 27 * 4 * 16 * 28
    assert analytics.ripple(27, 4, 16, 1, True).bytes == 4 * 27 * 4 * 16 * 5
    cfg = harness.load_cell(CELL).config
    dep = analytics.Deployment.__new__(analytics.Deployment)
    dep.config = dict(cfg, rows=5)
    work = dep.batch_work([
        {"plan": "range_count", "column": "l_shipdate", "year": 1994},
        {"plan": "aggregate", "op": "max", "column": "l_extendedprice"}])
    c = cfg["clouds"]
    # range: 2 rows a job; MAX over 5 rows: levels of 2, 1, 1 pairs
    want = (analytics.ripple(c, 2, 5, 13, False).bytes
            + analytics.ripple(c, 2, 5, 1, True).bytes
            + sum(analytics.ripple(c, 1, p, k, i > 0).bytes
                  for p in (2, 1, 1)
                  for i, k in enumerate((12, 12, 2))))
    assert work["ripple"].bytes == want and work["ripple"].macs == 0
    assert set(work) == {"ripple"}
    # Q1 at 8,192 rows: one match and contraction a column width, a 26-bit
    # sum contracting against its two limbs (17 and 9 bits)
    dep.config = dict(cfg)
    flag = {"column": "l_returnflag", "value": "A"}
    work = dep.batch_work([
        {"plan": "aggregate", "op": "sum", "column": col, "where": flag}
        for col in ("l_quantity", "l_extendedprice", "l_extendedprice")])
    n, w, a = cfg["rows"], cfg["word_length"], cfg["alphabet_size"]
    assert work["match"] == (roofline.match(c, 1, n, w, a)
                             + roofline.match(c, 2, n, w, a))
    assert work["contraction"] == (roofline.contraction(c, 1, n, 1)
                                   + roofline.contraction(c, 2, n, 2))
