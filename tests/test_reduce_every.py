"""The planner's degree reduction: ``reduce_every`` chosen from (c, base
degree, t) so that no ripple carry, result bit or opened indicator passes
degree c − 1; a plan that no segment fits is refused at plan time."""
import jax
import numpy as np
import pytest

from repro.api import (Aggregate, Between, Eq, QueryClient, RangeCount,
                       RangeSelect, planner)
from repro.core import Codec, outsource
from repro.core.queries import rounds
from repro.core.queries.range_query import range_count
from repro.launch.serve import QueryServer

CODEC = Codec(word_length=8)


@pytest.mark.parametrize("c, d, t, tournament, want", [
    (27, 1, 14, False, 13),     # 26, then 1 + 2 = 3 after one re-share
    (27, 1, 26, True, 12),      # 24, 25, then 1 + 4 + 1 (the mux) = 6
    (7, 1, 8, False, 2),        # 4, then 5 after each re-share
    (27, 1, 8, False, 0),       # 16 fits: no reduction
    (27, 1, 6, True, 0),        # 12 + 1 fits
    # the clouds the client-level tests pick to get a segmented ripple
    (20, 1, 14, False, 9),      # 18, then 1 + 10
    (20, 1, 14, True, 9),       # 18, then 1 + 10 + 1
    (7, 1, 14, False, 2),       # 4, then 5 after each re-share
    (7, 1, 8, True, 2),         # 4, 5, 5, then 5 + 1
    (14, 1, 8, True, 6),        # 12, then 1 + 4 + 1
])
def test_planner_choice(c, d, t, tournament, want):
    got = planner.choose_reduce_every(c, d, t, tournament=tournament)
    assert got == want
    mux = d if tournament else 0

    def worst(every):
        degrees = [g for _, _, g in rounds.ripple_schedule(t, every, d)]
        return max(degrees[:-1] + [degrees[-1] + mux])
    assert worst(got) <= c - 1
    if got:         # the widest: one bit more overflows
        assert worst(got + 1) > c - 1


def test_planner_refuses_what_no_segment_fits():
    with pytest.raises(ValueError, match="no reduce_every"):
        planner.choose_reduce_every(3, 1, 8)
    with pytest.raises(ValueError, match="sentinel mask re-shares at "
                                         "degree 25; 20 clouds"):
        planner.choose_reduce_every(20, 1, 8, tournament=True,
                                    match_degree=24)


def _db(values, t, c, seed=0, flags=None):
    flags = flags or ["a"] * len(values)
    rows = [[f, str(v)] for f, v in zip(flags, values)]
    return outsource(jax.random.PRNGKey(seed), rows,
                     column_names=["F", "V"], codec=CODEC, n_shares=c,
                     degree=1, numeric_columns={1: t})


RNG = np.random.default_rng(15)
DAYS = [int(v) for v in RNG.integers(0, 2500, 12)]
QTY = [int(v) for v in RNG.integers(1, 51, 12)]


@pytest.mark.parametrize("values, t, c, lo, hi", [
    (DAYS, 14, 27, 366, 1460),
    (QTY, 8, 7, 0, 23),
])
def test_range_count_answers_where_no_reduction_overflows(values, t, c,
                                                          lo, hi):
    """Without the planner's choice both open above c − 1 and raise."""
    db = _db(values, t, c)
    want = sum(lo <= v <= hi for v in values)
    plan = RangeCount(Between("V", lo, hi))
    res = QueryClient(db, key=1).run(plan)
    assert res.count == want
    server = QueryServer(db, key=1)
    req = server.submit(plan)
    while server.pending():
        server.pump()
    assert req.error is None and req.result.count == want
    with pytest.raises(ValueError, match="need .* shares|reaches degree"):
        range_count(jax.random.PRNGKey(2), db, 1, lo, hi)


def test_range_select_rides_the_choice():
    db = _db(DAYS, 14, 27)
    res = QueryClient(db, key=3).run(RangeSelect(Between("V", 500, 1500)))
    assert res.addresses == [i for i, v in enumerate(DAYS)
                             if 500 <= v <= 1500]


def test_max_over_a_26_bit_column_at_27_clouds():
    prices = [int(v) for v in RNG.integers(0, 10_495_001, 12)]
    flags = ["x" if i % 3 else "y" for i in range(12)]
    db = _db(prices, 26, 27, flags=flags)
    cl = QueryClient(db, key=4)
    assert cl.run(Aggregate("max", "V")).value == max(prices)
    res = cl.run(Aggregate("min", "V", where=Eq("F", "y")))
    ys = [p for p, f in zip(prices, flags) if f == "y"]
    assert (res.value, res.count) == (min(ys), len(ys))


@pytest.mark.parametrize("plan", [
    RangeCount(Between("V", 1, 9)),
    RangeSelect(Between("V", 1, 9)),
])
def test_overflowing_reduce_every_raises_at_plan_time(plan):
    """At 3 clouds even one bit a segment opens at degree 3 > c − 1."""
    db = _db(DAYS, 14, 3)
    cl = QueryClient(db, key=5)
    cl.attach(shards=1)
    with pytest.raises(ValueError, match="no reduce_every keeps a 14-bit "
                                         "ripple within 3 clouds"):
        cl.explain(plan)
    with pytest.raises(ValueError, match="reaches degree 3, above 2"):
        cl.run(plan)
    assert cl.dataplane.stats.steps == 0       # refused before any round


def test_overflowing_max_reduce_every_raises_at_plan_time():
    """A tournament's one-bit segment adds the mux: degree 4 at 4 clouds."""
    db = _db([1, 2, 3, 4], 26, 4)
    cl = QueryClient(db, key=6)
    cl.attach(shards=1)
    with pytest.raises(ValueError, match="26-bit ripple within 4 clouds: "
                                         "one bit a segment reaches degree 4"):
        cl.run(Aggregate("max", "V"))
    assert cl.dataplane.stats.steps == 0


@pytest.mark.parametrize("plan, c, every", [
    (RangeCount(Between("V", 366, 1460)), 27, 13),
    (RangeCount(Between("V", 366, 1460)), 10, 4),
    (Aggregate("max", "V"), 27, 12),
    (Aggregate("min", "V", where=Eq("F", "a")), 27, 12),
])
def test_explain_equals_the_measured_ledger(plan, c, every):
    t = 14 if not isinstance(plan, Aggregate) else 26
    assert planner.choose_reduce_every(
        c, 1, t, tournament=isinstance(plan, Aggregate)) == every
    db = _db(DAYS, t, c)
    cl = QueryClient(db, key=7)
    exp = cl.explain(plan)
    led = cl.run(plan).ledger
    assert (exp.bits, exp.rounds) == (led.communication_bits, led.rounds)


def test_plans_that_fit_keep_their_ledgers():
    """A 14-bit range at 34 clouds opens at degree 28 without reduction:
    the planner leaves it so, and its transcript and ledger are those of
    the round engine called with no reduction at all."""
    db = _db(DAYS, 14, 34)
    res = QueryClient(db, key=8).run(RangeCount(Between("V", 366, 1460)))
    cnt, led = range_count(jax.random.PRNGKey(9), db, 1, 366, 1460)
    assert res.count == cnt
    assert res.ledger == led
    assert led.rounds == 1                      # no re-share round


def test_counters_follow_the_segments():
    """A 14-bit range group at 27 clouds: two segments, one re-share, for
    the whole batch and whatever the shard count; the server folds both
    counts into its snapshot."""
    db = _db(DAYS, 14, 27)
    server = QueryServer(db, key=10, shards=2)
    reqs = [server.submit(RangeCount(Between("V", lo, lo + 700)))
            for lo in (0, 400, 800)]
    while server.pending():
        server.pump()
    assert all(r.error is None for r in reqs)
    snap = server.stats.snapshot()
    assert (snap["ripple_segments"], snap["reshares"]) == (2, 1)
    plane = server.dataplane.stats
    assert (plane.ripple_segments, plane.reshares) == (2, 1)


PRICES = [int(v) for v in RNG.integers(90_000, 10_495_001, 40)]


@pytest.mark.parametrize("plan", [
    Aggregate("sum", "V"),
    Aggregate("avg", "V"),
    Aggregate("sum", "V", where=Eq("F", "y")),
    Aggregate("avg", "V", where=Eq("F", "y")),
])
def test_sum_of_a_26_bit_column_past_the_half_range(plan):
    """Q1's SUM and AVG of l_extendedprice in cents (t = 26) at 27 clouds:
    40·2^25 passes the half-range, so the sum opens in two limbs, exact,
    through QueryClient and QueryServer, and explain() prices the limbs."""
    flags = ["x" if i % 3 else "y" for i in range(len(PRICES))]
    db = _db(PRICES, 26, 27, flags=flags)
    kept = [p for p, f in zip(PRICES, flags)
            if plan.where is None or f == "y"]
    cl = QueryClient(db, key=11)
    exp = cl.explain(plan)
    res = cl.run(plan)
    assert (exp.bits, exp.rounds) == (res.ledger.communication_bits,
                                      res.ledger.rounds)
    want = sum(kept) if plan.op == "sum" else sum(kept) / len(kept)
    assert res.value == want
    server = QueryServer(db, key=11)
    req = server.submit(plan)
    while server.pending():
        server.pump()
    assert req.error is None and req.result.value == want
