"""Batched oblivious query engine: fusion + equivalence acceptance tests.

Two properties anchor this suite:

  1. *Round fusion*: ``select_tree`` issues exactly one device dispatch and
     one interpolation per Q&A round (never per block), and a
     ``run_batch`` group executes each protocol round once for the whole
     group (never per query).
  2. *Bit-identical accounting*: every query inside a batch returns the
     same rows/addresses and the same per-query ``CostLedger`` totals as
     the same plan run sequentially — batching is free in protocol cost.
"""
import jax
import pytest

from repro.api import (Between, Count, DBStats, Eq, Join, Padding,
                       QueryClient, RangeCount, RangeSelect, Select,
                       MapReduceDispatcher, choose_select_strategy,
                       get_backend, planner)
from repro.api.backends import Backend, batched_matcher, ripple_stepper
from repro.core import outsource, Codec
from repro.core.queries import CardinalityError, select_tree
from repro.core import shamir
from repro.core.queries import rounds
from repro.runtime import MapReduceRunner, WorkerPool

CODEC = Codec(word_length=8)
COLUMNS = ["EmployeeId", "FirstName", "LastName", "Salary", "Department"]

EMPLOYEE = [
    ["E101", "Adam", "Smith", "1000", "Sale"],
    ["E102", "John", "Taylor", "2000", "Design"],
    ["E103", "Eve", "Smith", "500", "Sale"],
    ["E104", "John", "Williams", "5000", "Sale"],
]


@pytest.fixture(scope="module")
def employee_db():
    return outsource(jax.random.PRNGKey(7), EMPLOYEE, column_names=COLUMNS,
                     codec=CODEC, n_shares=20, degree=1,
                     numeric_columns={3: 14})


def _counting_backend(name="jnp"):
    """Wrap a registered backend so every hotspot dispatch is counted."""
    base = get_backend(name)
    calls = {"aa_match": 0, "aa_match_batch": 0, "ss_matmul": 0,
             "match_matrix": 0, "ripple_carry": 0}

    def wrap(op_name, fn):
        def run(a, b):
            calls[op_name] += 1
            return fn(a, b)
        return run

    base_ripple = ripple_stepper(base)

    def ripple(a, b, carry=None):
        calls["ripple_carry"] += 1
        return base_ripple(a, b, carry)

    be = Backend(
        name=f"{name}+counting",
        aa_match=wrap("aa_match", base.aa_match),
        ss_matmul=wrap("ss_matmul", base.ss_matmul),
        match_matrix=wrap("match_matrix", base.match_matrix),
        aa_match_batch=wrap("aa_match_batch", batched_matcher(base)),
        ripple_carry=ripple)
    return be, calls


def _count_interpolations(monkeypatch):
    """Count user-side opens: ``shamir.interpolate`` on the device and
    ``rounds._open_on_host``, one host open per phase."""
    counter = {"n": 0}

    def counting(real):
        def wrapped(*args, **kw):
            counter["n"] += 1
            return real(*args, **kw)
        return wrapped

    monkeypatch.setattr(shamir, "interpolate", counting(shamir.interpolate))
    monkeypatch.setattr(rounds, "_open_on_host",
                        counting(rounds._open_on_host))
    return counter


def _assert_results_equal(a, b):
    assert a.strategy == b.strategy
    assert a.rows == b.rows
    assert a.addresses == b.addresses
    assert a.count == b.count
    assert a.ledger == b.ledger       # bit-for-bit: rounds, bits, ops


# ---------------------------------------------------------------------------
# acceptance: one dispatch + one interpolation per Q&A round
# ---------------------------------------------------------------------------

def _tree_db(n=64):
    # "John" clustered at 0,1 and 32,33 (ℓ=4). Q&A trace: round 1 splits
    # into 4 blocks of 16 (counts 2,0,2,0); round 2 splits the two live
    # blocks into 4×4 (one count-2 block each); round 3 isolates four
    # singles, all address-fetched in ONE fused round.
    rows = [[f"id{i}", "John" if i in (0, 1, 32, 33) else f"nm{i}"]
            for i in range(n)]
    return rows, outsource(jax.random.PRNGKey(3), rows, codec=CODEC,
                           n_shares=20)


def test_select_tree_one_dispatch_per_round(monkeypatch):
    _, db = _tree_db()
    be, calls = _counting_backend()
    interps = _count_interpolations(monkeypatch)
    rows, addrs, led = select_tree(jax.random.PRNGKey(5), db, 1, "John",
                                   backend=be)
    assert addrs == [0, 1, 32, 33]
    # phases: count(1) + Q&A count rounds(3) + fused address round(1)
    # -> 5 match dispatches; the fetch is 1 ss_matmul. 20 blocks were
    # counted/address-fetched in total, yet no per-block dispatch happened.
    assert calls["aa_match_batch"] == 5
    assert calls["aa_match"] == 0
    assert calls["ss_matmul"] == 1
    # one interpolation per phase: count, 3 count rounds, address, fetch
    assert interps["n"] == 6
    # ledger rounds unchanged by fusion: count + 3 Q&A + fetch
    assert led.rounds == 5


def test_select_tree_rows_and_ledger_unchanged_by_fusion():
    """The fused tree must agree with a brute-force oracle on rows and with
    the historical per-block accounting on totals."""
    rows, db = _tree_db()
    got, addrs, led = select_tree(jax.random.PRNGKey(5), db, 1, "John")
    assert got == [rows[i] for i in (0, 1, 32, 33)]
    # cloud elems (×wa): count 64 + r1 4×16 + r2 8×4 + r3 8×1 + addr 4×1,
    # then the fetch term 4 rows × n(64) × m(2) × wa.
    wa = CODEC.word_length * CODEC.alphabet_size
    assert led.cloud_ops_bits == ((64 + 64 + 32 + 8 + 4) * wa
                                  + 4 * 64 * 2 * wa) * 31


# ---------------------------------------------------------------------------
# acceptance: B=32 same-strategy batch executes each round once
# ---------------------------------------------------------------------------

def _wide_db(n=32):
    pats = ["ann", "bob", "cat", "dan"]
    rows = [[f"id{i}", pats[i % 4], str(100 + i)] for i in range(n)]
    return rows, outsource(jax.random.PRNGKey(11), rows,
                           column_names=["Id", "Name", "Val"],
                           codec=Codec(word_length=6), n_shares=16)


def test_batch32_one_round_selects_execute_rounds_once(monkeypatch):
    _, db = _wide_db()
    plans = [Select(Eq("Name", ["ann", "bob", "cat", "dan"][i % 4]),
                    strategy="one_round") for i in range(32)]
    seq = [QueryClient(db, key=9).run(p) for p in plans]

    be, calls = _counting_backend()
    interps = _count_interpolations(monkeypatch)
    bat = QueryClient(db, key=9, backend=be).run_batch(plans)

    # the whole B=32 group: ONE fused match dispatch + ONE fused fetch
    assert calls["aa_match_batch"] == 1
    assert calls["ss_matmul"] == 1
    assert calls["aa_match"] == 0
    assert interps["n"] == 2
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_batch32_tree_selects_execute_rounds_once(monkeypatch):
    _, db = _tree_db()
    plans = [Select(Eq(1, "John"), strategy="tree") for _ in range(32)]
    seq = [QueryClient(db, key=13).run(p) for p in plans]

    be, calls = _counting_backend()
    interps = _count_interpolations(monkeypatch)
    bat = QueryClient(db, key=13, backend=be).run_batch(plans)

    # same dispatch/interp count as ONE query (see the B=1 acceptance
    # test): lockstep fusion makes the group free.
    assert calls["aa_match_batch"] == 5
    assert calls["ss_matmul"] == 1
    assert interps["n"] == 6
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


# ---------------------------------------------------------------------------
# batch == sequential across mixed strategies / families
# ---------------------------------------------------------------------------

def test_run_batch_mixed_strategies_equals_sequential(employee_db):
    plans = [
        Count(Eq("FirstName", "John")),
        Select(Eq("Department", "Sale"), strategy="tree"),
        Select(Eq("FirstName", "John"), strategy="one_round"),
        Select(Eq("FirstName", "Eve"), strategy="one_tuple"),
        Select(Eq("FirstName", "John"), strategy="one_round",
               padding=Padding.to_rows(4)),
        Select(Eq("FirstName", "Zoe"), strategy="tree"),   # ℓ = 0
        RangeCount(Between("Salary", 900, 2100)),
        Select(Eq("LastName", "Smith")),                   # auto strategy
    ]
    seq = [QueryClient(employee_db, key=42).run(p) for p in plans]
    bat = QueryClient(employee_db, key=42).run_batch(plans)
    same_client_seq = []
    cl = QueryClient(employee_db, key=42)
    for p in plans:
        same_client_seq.append(cl.run(p))
    for a, b in zip(same_client_seq, bat):
        _assert_results_equal(a, b)
    # fresh-client-per-plan also agrees (keys never leak across queries)
    for a, b in zip(seq, bat):
        assert a.rows == b.rows and a.count == b.count


def test_run_batch_auto_replans_wrong_hint_like_sequential():
    big_rows = ([[f"E{i}", f"nm{i}", "X", "1", "D"] for i in range(316)]
                + EMPLOYEE)
    db = outsource(jax.random.PRNGKey(1), big_rows, column_names=COLUMNS,
                   codec=CODEC, n_shares=20)
    plans = [Select(Eq("FirstName", "John"), expected_matches=1),
             Select(Eq("FirstName", "Adam"), expected_matches=1)]
    seq_cl = QueryClient(db, key=7)
    seq = [seq_cl.run(p) for p in plans]
    bat = QueryClient(db, key=7).run_batch(plans)
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)
    assert bat[0].strategy == "one_round"      # replanned: ℓ=2
    assert bat[0].addresses == [317, 319]
    assert bat[1].strategy == "one_tuple"      # hint was right
    assert bat[1].rows == [EMPLOYEE[0]]


def test_run_batch_forced_one_tuple_wrong_cardinality_raises(employee_db):
    plans = [Select(Eq("FirstName", "John"), strategy="one_tuple")]
    with pytest.raises(CardinalityError):
        QueryClient(employee_db, key=3).run_batch(plans)


def test_run_batch_empty_and_single(employee_db):
    assert QueryClient(employee_db, key=1).run_batch([]) == []
    res = QueryClient(employee_db, key=1).run_batch(
        [Count(Eq("FirstName", "Eve"))])
    assert len(res) == 1 and res[0].count == 1


def test_run_batch_pallas_matches_jnp():
    _, db = _wide_db(n=8)
    plans = [Count(Eq("Name", "ann")),
             Select(Eq("Name", "bob"), strategy="one_round")]
    rj = QueryClient(db, key=5, backend="jnp").run_batch(plans)
    rp = QueryClient(db, key=5, backend="pallas").run_batch(plans)
    for a, b in zip(rj, rp):
        _assert_results_equal(a, b)


def _mr_client(db, key):
    """A client whose 3 shard dispatches run as MapReduce tasks."""
    runner = MapReduceRunner(WorkerPool(3), lease_s=5.0, max_attempts=30)
    cl = QueryClient(db, key=key)
    cl.attach(shards=3, dispatcher=MapReduceDispatcher(runner))
    return cl


def test_run_batch_mapreduce_executor_splits_fused_batch():
    _, db = _wide_db()
    cl_mr = _mr_client(db, 21)
    cl = QueryClient(db, key=21)
    plans = [Select(Eq("Name", p), strategy="one_round")
             for p in ("ann", "bob", "cat")]
    for a, b in zip(cl.run_batch(plans), cl_mr.run_batch(plans)):
        _assert_results_equal(a, b)


# ---------------------------------------------------------------------------
# batched ranges: one fused ripple dispatch per bit-round
# ---------------------------------------------------------------------------

def _range_db(n=32, word_length=6, t_bits=14, n_shares=20):
    """At the default 20 clouds the planner re-shares a 14-bit ripple
    after 9 bits; at 7 clouds, every 2."""
    rows = [[f"id{i}", f"nm{i % 5}", str(500 + 137 * i)] for i in range(n)]
    return rows, outsource(jax.random.PRNGKey(19), rows,
                           column_names=["Id", "Name", "Val"],
                           codec=Codec(word_length=word_length),
                           n_shares=n_shares, degree=1,
                           numeric_columns={2: t_bits})


def _child_db(rows, k=6, word_length=6, n_shares=20, dup=False):
    """A child relation whose join column references ``rows``' Id column."""
    child = [[rows[(i // 2 if dup else i) % len(rows)][0], f"t{i}"]
             for i in range(k)]
    return outsource(jax.random.PRNGKey(23), child,
                     column_names=["Id", "Task"],
                     codec=Codec(word_length=word_length),
                     n_shares=n_shares, degree=1)


def test_batch16_ranges_one_ripple_dispatch_per_bit_round(monkeypatch):
    _, db = _range_db(n_shares=7)
    plans = [RangeCount(Between("Val", 600, 600 + 200 * i))
             if i % 2 == 0 else
             RangeSelect(Between("Val", 500, 700 + 150 * i))
             for i in range(16)]
    seq = [QueryClient(db, key=33).run(p) for p in plans]

    be, calls = _counting_backend()
    interps = _count_interpolations(monkeypatch)
    bat = QueryClient(db, key=33, backend=be).run_batch(plans)

    # the whole B=16 group ripples in ONE carry chain: t_bits dispatches
    # (LSB + 13 steps), never B per bit; the 8 range-selects' fetches ride
    # ONE ss_matmul; counts/bits/tuples interpolate once each.
    assert calls["ripple_carry"] == 14
    assert calls["ss_matmul"] == 1
    assert calls["aa_match_batch"] == 0
    assert interps["n"] == 3
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def _two_width_db():
    """A 14-bit and an 8-bit numeric column at 7 clouds: the planner
    re-shares both ripples every 2 bits (7 and 4 segments)."""
    rows = [[f"id{i}", str(500 + 137 * i), str(i * 7 % 60)]
            for i in range(12)]
    return outsource(jax.random.PRNGKey(29), rows,
                     column_names=["Id", "Val", "Qty"], codec=CODEC,
                     n_shares=7, degree=1, numeric_columns={1: 14, 2: 8})


def _range_batch(plans, key):
    """(sequential results, batched results, counting calls, plane stats)
    of ``plans`` over :func:`_two_width_db`."""
    db = _two_width_db()
    seq = [QueryClient(db, key=key).run(p) for p in plans]
    be, calls = _counting_backend()
    cl = QueryClient(db, key=key, backend=be)
    plane = cl.attach(shards=1)
    return seq, cl.run_batch(plans), calls, plane.stats


def test_range_groups_split_by_reduce_every():
    """Ranges on two column widths cannot share a carry chain: they form
    one group per width (each fused, each re-sharing where the planner
    says for its width), and results still match sequential."""
    plans = [RangeCount(Between("Val", 500, 3000)),
             RangeCount(Between("Qty", 10, 60)),
             RangeSelect(Between("Val", 600, 2000)),
             RangeCount(Between("Qty", 0, 30))]
    seq, bat, calls, stats = _range_batch(plans, 3)
    every = {t: planner.choose_reduce_every(7, 1, t) for t in (14, 8)}
    assert every == {14: 2, 8: 2}
    assert calls["ripple_carry"] == 14 + 8      # two groups, one per width
    assert stats.ripple_segments == sum(
        len(rounds.ripple_schedule(t, k, 1)) for t, k in every.items())
    assert stats.reshares == 6 + 3
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_range_plans_on_one_width_form_one_group():
    """RangeCount and RangeSelect plans on one column width, whatever their
    bounds, run as ONE ripple group: one group's segments and re-shares."""
    plans = [RangeCount(Between("Val", 500, 3000)),
             RangeSelect(Between("Val", 700, 1500)),
             RangeCount(Between("Val", 0, 4000)),
             RangeCount(Between("Val", 600, 2000))]
    seq, bat, calls, stats = _range_batch(plans, 4)
    schedule = rounds.ripple_schedule(14, 2, 1)
    assert calls["ripple_carry"] == 14
    assert stats.ripple_segments == len(schedule) == 7
    assert stats.reshares == len(schedule) - 1
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


# ---------------------------------------------------------------------------
# cross-group fetch fusion: one ss_matmul for one_round+tree+range+pkfk
# ---------------------------------------------------------------------------

def test_cross_group_fetch_is_one_matmul(monkeypatch):
    rows, db = _range_db()
    child = _child_db(rows)
    plans = [Select(Eq("Name", "nm1"), strategy="one_round"),
             Select(Eq("Name", "nm2"), strategy="tree"),
             RangeSelect(Between("Val", 550, 2500)),
             Join(right=child, on=("Id", "Id"), kind="pkfk")]
    seq = [QueryClient(db, key=77).run(p) for p in plans]

    be, calls = _counting_backend()
    bat = QueryClient(db, key=77, backend=be).run_batch(plans)

    # one_round + tree + range one-hot matrices AND the join's transposed
    # match matrix stack into a single fused fetch dispatch; the planner
    # evaluates the join's n² string match in the aggregate form (W = 6
    # positions > its 2 launches), one flattened ss_matmul of its own.
    assert calls["ss_matmul"] == 1 + 1
    assert calls["match_matrix"] == 0
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_client_has_no_passthrough_path():
    """Every plan family routes through the batched round engine — the
    pre-PR-3 per-query fallback methods are gone."""
    for legacy in ("_run_range_count", "_run_range_select", "_run_join"):
        assert not hasattr(QueryClient, legacy)


# ---------------------------------------------------------------------------
# mixed Count/Select/Range/Join batches == sequential (B ≥ 16)
# ---------------------------------------------------------------------------

def test_run_batch_all_families_b16_equals_sequential():
    rows, db = _range_db()
    child_pk = _child_db(rows)
    child_dup = _child_db(rows, dup=True)
    plans = [
        Count(Eq("Name", "nm1")),
        Select(Eq("Name", "nm2"), strategy="one_round"),
        Select(Eq("Name", "nm3"), strategy="tree"),
        Select(Eq("Id", "id7"), strategy="one_tuple"),
        Select(Eq("Name", "nm4")),                       # auto
        RangeCount(Between("Val", 500, 2000)),
        RangeSelect(Between("Val", 900, 1800)),
        Join(right=child_pk, on=("Id", "Id"), kind="pkfk"),
        Join(right=child_dup, on=("Id", "Id"), kind="equi",
             padding=Padding.fake_values(1)),
        Select(Eq("Name", "nm0"), strategy="one_round",
               padding=Padding.to_rows(8)),
        RangeCount(Between("Val", 0, 8000)),
        Select(Eq("Name", "zzz"), strategy="tree"),      # ℓ = 0
        RangeSelect(Between("Val", 4000, 5000)),
        Count(Eq("Name", "nm0")),
        Join(right=child_pk, on=("Id", "Id"), kind="pkfk"),
        Select(Eq("Name", "nm1"), strategy="one_round"),
    ]
    assert len(plans) >= 16
    seq_cl = QueryClient(db, key=42)
    seq = [seq_cl.run(p) for p in plans]
    bat = QueryClient(db, key=42).run_batch(plans)
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_equijoin_no_common_values_returns_empty():
    """Disjoint join columns (and no padding) must yield zero rows, both
    standalone and inside a batch — not crash on the empty fetch stack."""
    from repro.core.queries import equijoin
    codec = Codec(word_length=6)
    dbX = outsource(jax.random.PRNGKey(1), [["a1", "b1"], ["a2", "b2"]],
                    column_names=["A", "B"], codec=codec, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(2), [["b8", "c1"], ["b9", "c2"]],
                    column_names=["B", "C"], codec=codec, n_shares=16)
    rows, led = equijoin(jax.random.PRNGKey(3), dbX, dbY, 1, 0)
    assert rows == [] and led.rounds == 1       # only the column-open round
    res = QueryClient(dbX, key=4).run_batch(
        [Join(right=dbY, on=("B", "B"), kind="equi")])[0]
    assert res.rows == [] and res.count == 0


def test_run_batch_range_join_pallas_matches_jnp():
    rows, db = _range_db(n=8)
    child = _child_db(rows, k=4)
    plans = [RangeCount(Between("Val", 500, 1200)),
             RangeSelect(Between("Val", 500, 900)),
             Join(right=child, on=("Id", "Id"), kind="pkfk")]
    rj = QueryClient(db, key=5, backend="jnp").run_batch(plans)
    rp = QueryClient(db, key=5, backend="pallas").run_batch(plans)
    for a, b in zip(rj, rp):
        _assert_results_equal(a, b)


def test_zero_match_select_empty_fetch_stack_all_backends():
    """An unpadded zero-match select/range contributes a 0-row block to the
    fused fetch; every backend must return [] instead of choking on the
    empty matmul."""
    _, db = _range_db(n=8)
    plans = [Select(Eq("Name", "zzz"), strategy="one_round"),
             RangeSelect(Between("Val", 8000, 8100))]
    for backend in ("jnp", "pallas"):
        res = QueryClient(db, key=6, backend=backend).run_batch(plans)
        assert res[0].rows == [] and res[0].addresses == []
        assert res[1].rows == [] and res[1].addresses == []


def test_run_batch_range_join_mapreduce_matches_plain():
    rows, db = _range_db()
    child = _child_db(rows)
    cl_mr = _mr_client(db, 21)
    cl = QueryClient(db, key=21)
    plans = [RangeCount(Between("Val", 500, 2500)),
             RangeSelect(Between("Val", 600, 1500)),
             Join(right=child, on=("Id", "Id"), kind="pkfk"),
             Select(Eq("Name", "nm1"), strategy="one_round")]
    for a, b in zip(cl.run_batch(plans), cl_mr.run_batch(plans)):
        _assert_results_equal(a, b)


# ---------------------------------------------------------------------------
# planner batching-awareness: ride a non-empty group's fused rounds
# ---------------------------------------------------------------------------

def test_planner_marginal_round_pricing_steers_borderline():
    stats = DBStats(n=64, m=5, c=20, w=8, a=128)
    solo_or = choose_select_strategy(stats, ell=4)
    assert solo_or.strategy == "one_round"      # bits-optimal at small n
    from repro.api.planner import estimate_select_cost
    bits_or = estimate_select_cost("one_round", stats, ell=4).bits
    bits_tree = estimate_select_cost("tree", stats, ell=4).bits
    assert bits_tree > bits_or                  # borderline: tree costs more
    rcb = (bits_tree - bits_or) // 2 + 1        # 2·rcb > bits gap

    # sequentially (or with no tree group) one_round still wins...
    assert choose_select_strategy(stats, ell=4,
                                  round_cost_bits=rcb).strategy == "one_round"
    # ...but when a tree group is already running, its Q&A/fetch rounds are
    # free to ride — the marginal price tips the borderline query over.
    ridden = choose_select_strategy(
        stats, ell=4, round_cost_bits=rcb,
        group_sizes={"one_tuple": 0, "one_round": 0, "tree": 8})
    assert ridden.strategy == "tree"
    # depth-aware: the same rider over a SHALLOW tree group pays the Q&A
    # rounds it would add beyond the group's deepest member — not free
    deep_rider = choose_select_strategy(
        stats, ell=4, round_cost_bits=rcb,
        group_sizes={"tree": 8}, group_rounds={"tree": 2})
    assert deep_rider.strategy == "one_round"
    # ...while a group at least as deep as the rider stays free to ride
    assert choose_select_strategy(
        stats, ell=4, round_cost_bits=rcb, group_sizes={"tree": 8},
        group_rounds={"tree": 20}).strategy == "tree"
    # with the default pricing the group never changes the choice (the
    # batch == sequential identity the equality tests rely on)
    assert choose_select_strategy(
        stats, ell=4,
        group_sizes={"tree": 8}).strategy == "one_round"


def test_estimate_batch_group_cost_pays_rounds_once():
    from repro.api import estimate_batch_group_cost
    from repro.api.planner import estimate_select_cost
    stats = DBStats(n=64, m=5, c=20, w=8, a=128)
    singles = [estimate_select_cost("tree", stats, ell=e) for e in (2, 4, 8)]
    grp = estimate_batch_group_cost(stats, "tree", ells=[2, 4, 8])
    assert grp.strategy == "tree"
    assert grp.bits == sum(e.bits for e in singles)       # bits add up...
    assert grp.rounds == max(e.rounds for e in singles)   # ...rounds fuse
    assert estimate_batch_group_cost(stats, "one_round", ells=[]).rounds == 0


def test_client_steers_auto_select_onto_running_group():
    _, db = _tree_db()
    stats = DBStats.of(db)
    from repro.api.planner import estimate_select_cost
    bits_or = estimate_select_cost("one_round", stats, ell=4).bits
    bits_tree = estimate_select_cost("tree", stats, ell=4).bits
    rcb = abs(bits_tree - bits_or) // 2 + 1
    plans = [Select(Eq(1, "John"), strategy="tree") for _ in range(4)]
    borderline = Select(Eq(1, "John"), expected_matches=4)
    cheap_strategy = choose_select_strategy(stats, ell=4,
                                            round_cost_bits=rcb).strategy
    res = QueryClient(db, key=9, round_cost_bits=rcb).run_batch(
        plans + [borderline])[-1]
    # the AUTO query rides the live tree group even though a fresh client
    # would have opened a new round chain for it
    assert res.strategy == "tree"
    assert cheap_strategy == "one_round"
    assert res.addresses == [0, 1, 32, 33]


# ---------------------------------------------------------------------------
# micro-batching QueryServer
# ---------------------------------------------------------------------------

def test_query_server_micro_batches_and_stats(employee_db):
    from repro.launch.serve import QueryRequest, QueryServer
    server = QueryServer(employee_db, key=11, max_batch=4)
    reqs = [QueryRequest(Count(Eq("FirstName", "John"))),
            QueryRequest(Select(Eq("Department", "Sale"), strategy="tree")),
            QueryRequest(Select(Eq("FirstName", "Eve"),
                                strategy="one_tuple")),
            QueryRequest(Select(Eq("FirstName", "John"),
                                strategy="one_round")),
            QueryRequest(Count(Eq("Department", "Design")))]
    done = server.serve(reqs)
    assert [r.result.count for r in done] == [2, 3, 1, 2, 1]
    assert all(r.latency_s > 0 for r in done)
    st = server.stats
    assert st.served == 5
    assert st.batches == 2                    # max_batch=4 -> 4 + 1
    assert 2.0 < st.mean_batch_size <= 4.0
    d = st.as_dict()
    assert d["p50_latency_s"] >= 0 and d["busy_s"] > 0
    # results identical to an unbatched client with the same root key
    cl = QueryClient(employee_db, key=11)
    direct = [cl.run(r.plan) for r in reqs]
    for r, want in zip(done, direct):
        assert r.result.rows == want.rows
        assert r.result.count == want.count


def test_query_server_isolates_failing_request(employee_db):
    """One bad plan in a micro-batch must not take its batch-mates down."""
    from repro.launch.serve import QueryRequest, QueryServer
    server = QueryServer(employee_db, key=17, max_batch=8)
    reqs = [QueryRequest(Count(Eq("FirstName", "Eve"))),
            # forced one_tuple on a 2-match predicate -> CardinalityError
            QueryRequest(Select(Eq("FirstName", "John"),
                                strategy="one_tuple")),
            QueryRequest(Select(Eq("FirstName", "John"),
                                strategy="one_round"))]
    done = server.serve(reqs)
    assert done[0].result.count == 1 and done[0].error is None
    assert done[1].result is None
    assert isinstance(done[1].error, CardinalityError)
    assert done[2].result.addresses == [1, 3] and done[2].error is None
    assert server.stats.served == 2 and server.stats.failed == 1


def test_query_server_batches_range_join_traffic(employee_db):
    """Range and join requests join the micro-batch (no passthrough) and
    the per-family breakdown shows up in ServeStats."""
    from repro.launch.serve import QueryRequest, QueryServer
    child = outsource(jax.random.PRNGKey(31),
                      [["E101", "x1"], ["E103", "x2"], ["E101", "x3"]],
                      column_names=["EmployeeId", "Tag"], codec=CODEC,
                      n_shares=20, degree=1)
    server = QueryServer(employee_db, key=19, max_batch=8)
    reqs = [QueryRequest(Count(Eq("FirstName", "John"))),
            QueryRequest(RangeCount(Between("Salary", 900, 2100))),
            QueryRequest(RangeSelect(Between("Salary", 400, 1500))),
            QueryRequest(Join(right=child, on=("EmployeeId", "EmployeeId"),
                              kind="pkfk")),
            QueryRequest(Select(Eq("Department", "Sale"), strategy="tree"))]
    done = server.serve(reqs)
    assert all(r.error is None for r in done)
    assert done[1].result.count == 2
    assert done[2].result.addresses == [0, 2]
    assert len(done[3].result.rows) == 3        # one per child tuple
    assert server.stats.batches == 1            # ONE micro-batch served all
    assert server.stats.served_by_family == {
        "count": 1, "range_count": 1, "range_select": 1, "join": 1,
        "select": 1}
    assert server.stats.as_dict()["served_by_family"]["join"] == 1
    # identical to an unbatched client with the same root key
    cl = QueryClient(employee_db, key=19)
    for r, want in zip(done, [cl.run(r.plan) for r in reqs]):
        assert r.result.rows == want.rows
        assert r.result.count == want.count


def test_query_server_pump_drains_incrementally(employee_db):
    from repro.launch.serve import QueryRequest, QueryServer
    server = QueryServer(employee_db, key=2, max_batch=8)
    assert server.pump() == []                # empty queue is a no-op
    server.submit(QueryRequest(Count(Eq("FirstName", "Eve"))))
    server.submit(QueryRequest(Count(Eq("FirstName", "John"))))
    assert server.pending() == 2
    out = server.pump()
    assert server.pending() == 0
    assert [r.result.count for r in out] == [1, 2]
    server.reset()
    assert server.stats.served == 0
