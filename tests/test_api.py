"""Unified QueryClient API: plans, planner, backends, MapReduce placement.

Covers plan construction + validation, name-based column resolution, the
cost-based planner's strategy choice across (n, ℓ) regimes, exact
``CostLedger``/row equivalence between the client and the legacy free
functions, and shard dispatches placed as MapReduce tasks.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.api import (AUTO, Between, Count, DBStats, Eq, Join,
                       MapReduceDispatcher, Padding, QueryClient, QueryResult,
                       RangeCount, RangeSelect, Select, available_backends,
                       candidate_estimates, choose_select_strategy,
                       estimate_select_cost, get_backend, planner,
                       resolve_column)
from repro.core import outsource, Codec
from repro.core.queries import (count_query, pkfk_join, range_count,
                                range_select, select_one_round,
                                select_one_tuple, select_tree)
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


@pytest.fixture()
def client(employee_db):
    return QueryClient(employee_db, key=42)


# ---------------------------------------------------------------------------
# plan construction + validation
# ---------------------------------------------------------------------------

def test_plans_are_frozen_plain_data():
    plan = Select(Eq("FirstName", "John"), padding=Padding.to_rows(4))
    assert plan.where.pattern == "John"
    assert plan.padding.rows == 4
    with pytest.raises(dataclasses.FrozenInstanceError):
        plan.strategy = "tree"


def test_plan_validation():
    with pytest.raises(ValueError):
        Select(Eq("A", "x"), strategy="bogus")
    with pytest.raises(ValueError):
        Between("Salary", 10, 5)
    with pytest.raises(ValueError):
        Padding(rows=-1)
    with pytest.raises(ValueError):
        Padding(values=-2)


def test_join_plan_validation(employee_db):
    with pytest.raises(ValueError):
        Join(right=employee_db, on=("A", "B"), kind="hash")


def test_query_result_count_defaults_to_len_rows():
    res = QueryResult(plan=Count(Eq("A", "x")), ledger=None,
                      strategy="count", rows=[["a"], ["b"]])
    assert res.count == 2


# ---------------------------------------------------------------------------
# column resolution by name
# ---------------------------------------------------------------------------

def test_resolve_column_by_name_and_index(employee_db):
    assert resolve_column(employee_db, "FirstName") == 1
    assert resolve_column(employee_db, 3) == 3
    with pytest.raises(KeyError):
        resolve_column(employee_db, "NoSuchColumn")
    with pytest.raises(IndexError):
        resolve_column(employee_db, 99)


def test_client_accepts_names_and_indices(client):
    by_name = client.count("FirstName", "John")
    by_idx = client.count(1, "John")
    assert by_name.count == by_idx.count == 2


# ---------------------------------------------------------------------------
# planner strategy choice (§3.2 bit/round formulas)
# ---------------------------------------------------------------------------

STATS_SMALL = DBStats(n=32, m=5, c=20, w=8, a=69)
STATS_LARGE = DBStats(n=1 << 20, m=5, c=20, w=8, a=69)


def test_planner_small_n_prefers_one_round():
    assert choose_select_strategy(STATS_SMALL, ell=4).strategy == "one_round"


def test_planner_large_n_prefers_tree():
    # one_round ships (and the user interpolates) all n match bits;
    # tree replaces them with O(ℓ log n) block counts.
    assert choose_select_strategy(STATS_LARGE, ell=4).strategy == "tree"


def test_planner_single_match_large_n_prefers_one_tuple():
    big = DBStats(n=4096, m=5, c=20, w=8, a=69)
    assert choose_select_strategy(big, ell=1).strategy == "one_tuple"


def test_planner_one_tuple_requires_ell_one():
    with pytest.raises(ValueError):
        estimate_select_cost("one_tuple", STATS_SMALL, ell=3)
    # unknown ℓ -> one_tuple never eligible
    names = [e.strategy for e in candidate_estimates(STATS_LARGE)]
    assert "one_tuple" not in names


def test_planner_round_cost_breaks_ties_toward_fewer_rounds():
    # price rounds high enough and the 2-round one_round beats tree
    # even at large n
    est = choose_select_strategy(STATS_LARGE, ell=4,
                                 round_cost_bits=10 ** 12)
    assert est.strategy == "one_round"


def test_planner_estimates_match_measured_ledger(employee_db):
    """The §3.2 formulas are in CostLedger units: the one_round estimate
    must equal the measured communication bits exactly."""
    stats = DBStats.of(employee_db)
    est = estimate_select_cost("one_round", stats, ell=2)
    _, _, led = select_one_round(jax.random.PRNGKey(0), employee_db, 1,
                                 "John")
    assert est.bits == led.communication_bits
    assert est.rounds == led.rounds


# ---------------------------------------------------------------------------
# QueryResult + ledger equivalence with the legacy free functions
# ---------------------------------------------------------------------------

def test_count_matches_legacy(client, employee_db):
    res = client.count("FirstName", "John")
    cnt, led = count_query(jax.random.PRNGKey(0), employee_db, 1, "John")
    assert res.count == cnt == 2
    assert res.strategy == "count"
    assert res.ledger == led


def test_select_auto_ledger_matches_legacy_exactly(client, employee_db):
    """Acceptance: auto-picked strategy's (bits, rounds) ledger equals the
    legacy per-function ledger on the quickstart dataset."""
    res = client.select("FirstName", "John")
    assert res.strategy == "one_round"        # small n -> one_round
    assert res.addresses == [1, 3]
    assert res.rows == [EMPLOYEE[1], EMPLOYEE[3]]
    _, _, led = select_one_round(jax.random.PRNGKey(0), employee_db, 1,
                                 "John")
    assert res.ledger == led


def test_select_forced_strategies_match_legacy(client, employee_db):
    key = jax.random.PRNGKey(0)
    res = client.select("FirstName", "Eve", strategy="one_tuple")
    rows, led = select_one_tuple(key, employee_db, 1, "Eve")
    assert res.rows == rows == [EMPLOYEE[2]]
    assert res.ledger == led

    res = client.select("Department", "Sale", strategy="tree")
    rows, addrs, led = select_tree(key, employee_db, 4, "Sale")
    assert res.rows == rows and res.addresses == addrs == [0, 2, 3]
    assert res.ledger == led


def test_select_padding_policy(client):
    res = client.select("FirstName", "John", strategy="one_round",
                        padding=Padding.to_rows(4))
    assert res.rows == [EMPLOYEE[1], EMPLOYEE[3]]  # padding stripped


def test_select_auto_falls_back_on_wrong_cardinality_hint(client):
    # hint says ℓ=1 at a size where the planner trusts it; reality is ℓ=2
    big_rows = ([[f"E{i}", f"nm{i}", "X", "1", "D"] for i in range(316)]
                + EMPLOYEE)
    db = outsource(jax.random.PRNGKey(1), big_rows, column_names=COLUMNS,
                   codec=CODEC, n_shares=20)
    cl = QueryClient(db, key=7)
    plan = Select(Eq("FirstName", "Eve"), expected_matches=1)
    assert cl.explain(plan)[0].strategy == "one_tuple"
    res = cl.run(dataclasses.replace(plan, where=Eq("FirstName", "John")))
    # John appears twice: one_tuple raises internally, the client replans
    assert res.strategy == "one_round"
    assert res.count == 2
    assert res.addresses == [317, 319]


def test_select_forced_wrong_strategy_raises(client):
    with pytest.raises(ValueError):
        client.select("FirstName", "John", strategy="one_tuple")


def test_fallback_ledger_includes_aborted_count_phase(client, employee_db):
    # planner hint wrong at small n: forced-path equivalent spends a count
    # round before replanning; the result ledger must report it
    res = client.run(Select(Eq("FirstName", "John"), strategy=AUTO,
                            expected_matches=2))
    base = client.run(Select(Eq("FirstName", "John"), strategy="one_round"))
    assert res.ledger == base.ledger    # no fallback happened: same cost
    big_rows = ([[f"E{i}", f"nm{i}", "X", "1", "D"] for i in range(316)]
                + EMPLOYEE)
    db = outsource(jax.random.PRNGKey(1), big_rows, column_names=COLUMNS,
                   codec=CODEC, n_shares=20)
    cl = QueryClient(db, key=7)
    fell = cl.run(Select(Eq("FirstName", "John"), expected_matches=1))
    clean = cl.run(Select(Eq("FirstName", "John"), strategy="one_round"))
    assert fell.strategy == "one_round"
    # aborted one_tuple = one count round + pattern upload on top
    assert fell.ledger.rounds == clean.ledger.rounds + 1
    assert (fell.ledger.communication_bits
            > clean.ledger.communication_bits)


def test_fallback_replans_with_learned_cardinality():
    """When the ℓ=1 hint fails on a large relation, the client replans with
    the true ℓ (CardinalityError.count) — picking tree, and reusing the
    aborted attempt's count via known_count instead of re-counting."""
    big_rows = ([[f"E{i}", f"nm{i}", "X", "1", "D"] for i in range(696)]
                + EMPLOYEE)
    db = outsource(jax.random.PRNGKey(2), big_rows, column_names=COLUMNS,
                   codec=CODEC, n_shares=20)
    cl = QueryClient(db, key=9)
    res = cl.run(Select(Eq("FirstName", "John"), expected_matches=1))
    assert res.strategy == "tree"
    assert res.addresses == [697, 699]
    assert res.count == 2


def test_unsupported_padding_raises(client, employee_db):
    with pytest.raises(ValueError):
        client.select("FirstName", "Eve", strategy="one_tuple",
                      padding=Padding.to_rows(4))
    with pytest.raises(ValueError):
        client.join(employee_db, on=(1, 1), kind="pkfk",
                    padding=Padding.fake_values(2))
    with pytest.raises(ValueError):
        client.join(employee_db, on=(1, 1), kind="equi",
                    padding=Padding.to_rows(3))


def test_pkfk_join_keyword_call_forms():
    codec = Codec(word_length=6)
    X = [["a1", "b1"]]
    Y = [["b1", "c1"]]
    dbX = outsource(jax.random.PRNGKey(3), X, codec=codec, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(4), Y, codec=codec, n_shares=16)
    want = [["a1", "b1", "c1"]]
    assert pkfk_join(dbX, dbY, 1, 0)[0] == want
    assert pkfk_join(dbX, dbY, col_x=1, col_y=0)[0] == want
    assert pkfk_join(jax.random.PRNGKey(5), dbX, dbY, 1, 0)[0] == want
    assert pkfk_join(key=jax.random.PRNGKey(5), dbX=dbX, dbY=dbY,
                     col_x=1, col_y=0)[0] == want
    with pytest.raises(TypeError):
        pkfk_join(dbX, dbY, 1)                     # missing col_y
    with pytest.raises(TypeError):
        pkfk_join(dbX, dbY, 1, 0, col_x=1)         # duplicate col_x
    with pytest.raises(TypeError):
        pkfk_join(jax.random.PRNGKey(5), dbX, dbY, 1, 0,
                  key=jax.random.PRNGKey(6))       # duplicate key


def test_range_queries_match_legacy():
    # at 7 clouds the planner re-shares the 14-bit ripple every 2 bits
    db7 = outsource(jax.random.PRNGKey(7), EMPLOYEE, column_names=COLUMNS,
                    codec=CODEC, n_shares=7, degree=1,
                    numeric_columns={3: 14})
    assert planner.choose_reduce_every(7, 1, 14) == 2
    res = QueryClient(db7, key=42).range_count("Salary", 1000, 2000)
    cnt, led = range_count(jax.random.PRNGKey(0), db7, 3, 1000,
                           2000, reduce_every=2)
    assert res.count == cnt == 2
    assert res.ledger == led

    db34 = outsource(jax.random.PRNGKey(20), EMPLOYEE, column_names=COLUMNS,
                     codec=CODEC, n_shares=34, degree=1,
                     numeric_columns={3: 14})
    res = QueryClient(db34, key=5).range_select("Salary", 400, 1500)
    rows, addrs, led = range_select(jax.random.PRNGKey(0), db34, 3, 400,
                                    1500)
    assert res.rows == rows == [EMPLOYEE[0], EMPLOYEE[2]]
    assert res.addresses == addrs == [0, 2]
    assert res.ledger == led


def test_join_matches_legacy():
    codec = Codec(word_length=6)
    X = [["a1", "b1"], ["a2", "b2"], ["a3", "b3"]]
    Y = [["b1", "c1"], ["b2", "c2"], ["b2", "c3"], ["b2", "c4"]]
    dbX = outsource(jax.random.PRNGKey(1), X, column_names=["A", "B"],
                    codec=codec, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(2), Y, column_names=["B", "C"],
                    codec=codec, n_shares=16)
    res = QueryClient(dbX, key=3).join(dbY, on=("B", "B"))
    rows, led = pkfk_join(dbX, dbY, 1, 0)           # legacy key-less form
    assert res.rows == rows
    assert res.strategy == "pkfk"
    # keyed join re-randomizes outputs: same traffic/rounds, extra metered
    # cloud work for the zero-sharing additions
    assert res.ledger.communication_bits == led.communication_bits
    assert res.ledger.rounds == led.rounds == 1
    assert res.ledger.cloud_ops_bits > led.cloud_ops_bits

    res = QueryClient(dbX, key=4).join(dbY, on=("B", "B"), kind="equi",
                                       padding=Padding.fake_values(2))
    assert sorted(map(tuple, res.rows)) == sorted(map(tuple, rows))
    # 2 common values + 2 fake jobs, 2 rounds each (k hidden), 1 column open
    assert res.ledger.rounds == 1 + 2 * 4


def test_pkfk_join_key_rerandomizes_but_preserves_result():
    """The new key parameter re-randomizes transmitted shares (zero-sharing
    added) without changing the joined relation."""
    codec = Codec(word_length=6)
    X = [["a1", "b1"], ["a2", "b2"]]
    Y = [["b1", "c1"], ["b2", "c2"], ["b9", "c3"]]
    dbX = outsource(jax.random.PRNGKey(3), X, codec=codec, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(4), Y, codec=codec, n_shares=16)
    rows_legacy, _ = pkfk_join(dbX, dbY, 1, 0)
    rows_keyed, _ = pkfk_join(jax.random.PRNGKey(5), dbX, dbY, 1, 0)
    assert rows_keyed == rows_legacy == [["a1", "b1", "c1"],
                                        ["a2", "b2", "c2"]]


def test_per_query_keys_fold_in_deterministically(employee_db):
    a = QueryClient(employee_db, key=42)
    b = QueryClient(employee_db, key=42)
    ra, rb = a.count("FirstName", "Eve"), b.count("FirstName", "Eve")
    assert ra.count == rb.count == 1
    # same root key, same counter -> same derived key; counter advances
    k1 = QueryClient(employee_db, key=42)._next_key()
    k2 = QueryClient(employee_db, key=42)._next_key()
    assert np.array_equal(np.asarray(k1), np.asarray(k2))


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_registry_has_builtins():
    names = available_backends()
    assert "jnp" in names and "pallas" in names
    assert get_backend("jnp").name == "jnp"
    with pytest.raises(ValueError):
        get_backend("cuda-nope")


def test_client_pallas_backend_matches_jnp(employee_db):
    rj = QueryClient(employee_db, key=1).count("FirstName", "John")
    rp = QueryClient(employee_db, key=1, backend="pallas").count(
        "FirstName", "John")
    assert rj.count == rp.count == 2
    assert rj.ledger == rp.ledger


# ---------------------------------------------------------------------------
# MapReduce placement of shard dispatches
# ---------------------------------------------------------------------------

def _mr_client(db, **pool_kw):
    pool = WorkerPool(3, **pool_kw)
    runner = MapReduceRunner(pool, lease_s=5.0, max_attempts=30)
    cl = QueryClient(db, key=42)
    cl.attach(shards=3, dispatcher=MapReduceDispatcher(runner))
    return cl


def test_mapreduce_executor_count_and_select(employee_db):
    cl = _mr_client(employee_db)
    assert isinstance(cl.dataplane.dispatcher, MapReduceDispatcher)
    assert cl.dataplane.n_shards == 3
    plain = QueryClient(employee_db, key=42)
    res_mr, res = cl.count("FirstName", "John"), plain.count("FirstName",
                                                             "John")
    assert res_mr.count == res.count == 2
    assert res_mr.ledger == res.ledger      # fan-out is cost-transparent
    sel_mr = cl.select("Department", "Sale", strategy="one_round")
    sel = plain.select("Department", "Sale", strategy="one_round")
    assert sel_mr.rows == sel.rows and sel_mr.addresses == [0, 2, 3]
    assert sel_mr.ledger == sel.ledger


def test_mapreduce_executor_handles_zero_matches(employee_db):
    cl = _mr_client(employee_db)
    res = cl.select("FirstName", "Nobody", strategy="one_round")
    assert res.rows == [] and res.addresses == []


@pytest.mark.slow
def test_mapreduce_executor_survives_worker_failures(employee_db):
    cl = _mr_client(employee_db, fail_prob=0.3, seed=3)
    res = cl.count("FirstName", "John")
    assert res.count == 2
