"""Sharded dataplane: partitioning, dispatch fan-out, transcript identity.

The anchor property of `repro.core.dataplane`: the shard count S is pure
*execution* policy. For every plan family, `run_batch` over a
``ShardedRelation(S)`` — serial, threaded, or MapReduce-placed — returns
bit-identical rows/addresses/counts AND equal per-query ``CostLedger``s to
the S = 1 path, while the cloud-side device fan-out scales as one dispatch
per shard per cloud step (ceil(n/S)-tuple blocks).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (Aggregate, Backend, Between, Count, Eq, Join,
                       Padding, QueryClient, RangeCount, RangeSelect, Select,
                       ShardedRelation, ThreadedDispatcher,
                       MapReduceDispatcher, batched_match_matrix,
                       batched_matcher, get_backend, planner,
                       ripple_segmenter, ripple_stepper)
from repro.core import Codec, outsource
from repro.core.dataplane import as_dataplane
from repro.runtime import MapReduceRunner, WorkerPool

CODEC = Codec(word_length=6)


@pytest.fixture(scope="module")
def range_db():
    rows = [[f"id{i}", f"nm{i % 5}", str(500 + 137 * i)] for i in range(32)]
    db = outsource(jax.random.PRNGKey(19), rows,
                   column_names=["Id", "Name", "Val"], codec=CODEC,
                   n_shares=20, degree=1, numeric_columns={2: 14})
    return rows, db


@pytest.fixture(scope="module")
def child_db(range_db):
    rows, _ = range_db
    child = [[rows[i % len(rows)][0], f"t{i}"] for i in range(6)]
    return outsource(jax.random.PRNGKey(23), child,
                     column_names=["Id", "Task"], codec=CODEC,
                     n_shares=20, degree=1)


def _all_family_plans(child):
    return [
        Count(Eq("Name", "nm1")),
        Select(Eq("Name", "nm2"), strategy="one_round"),
        Select(Eq("Name", "nm3"), strategy="tree"),
        Select(Eq("Id", "id7"), strategy="one_tuple"),
        Select(Eq("Name", "nm4")),                          # auto
        RangeCount(Between("Val", 500, 2000)),
        RangeSelect(Between("Val", 900, 1800)),
        Join(right=child, on=("Id", "Id"), kind="pkfk"),
        Join(right=child, on=("Id", "Id"), kind="equi",
             padding=Padding.fake_values(1)),
        Select(Eq("Name", "zzz"), strategy="one_round"),    # zero match
        # aggregation: per-shard partial sums reduce exactly mod p; the
        # MIN/MAX tournament runs on the gathered relation — either way S
        # must stay invisible in values and ledgers. (Conditional MAX is
        # absent by design: range_db values reach 4747 > 2^(t-2)-1 = 4095,
        # outside the sentinel-masking headroom the comparator requires.)
        Aggregate("sum", "Val"),
        Aggregate("sum", "Val", where=Eq("Name", "nm1"), verify=True),
        Aggregate("avg", "Val", where=Eq("Name", "nm2")),
        Aggregate("min", "Val", where=Eq("Name", "nm1")),
        Aggregate("max", "Val"),
    ]


def _assert_results_equal(a, b):
    assert a.strategy == b.strategy
    assert a.rows == b.rows
    assert a.addresses == b.addresses
    assert a.count == b.count
    assert a.value == b.value
    assert a.ledger == b.ledger


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def test_sharded_relation_partitions_cover_and_clamp(range_db):
    _, db = range_db
    plane = ShardedRelation(db, shards=4)
    assert plane.n_shards == 4
    assert [s.lo for s in plane.shards][0] == 0
    assert plane.shards[-1].hi == db.n_tuples
    for a, b in zip(plane.shards, plane.shards[1:]):
        assert a.hi == b.lo                    # contiguous, no gaps
    assert plane.max_shard_rows == 8           # ceil(32/4)
    # views slice the share arrays without copying metadata semantics
    v = plane.view(1)
    assert v.n_tuples == 8
    np.testing.assert_array_equal(
        np.asarray(v.relation.values),
        np.asarray(db.relation.values[:, 8:16]))
    np.testing.assert_array_equal(
        np.asarray(v.numeric[2].values),
        np.asarray(db.numeric[2].values[:, 8:16]))
    # more shards than tuples clamps (split_bounds never yields empties)
    tiny = ShardedRelation(db, shards=100)
    assert tiny.n_shards == db.n_tuples
    # delegation: the plane reads like its relation
    assert plane.n_tuples == db.n_tuples and plane.codec is db.codec
    # re-wrapping a plane re-shards the underlying db
    assert ShardedRelation(plane, shards=2).n_shards == 2
    # as_dataplane: plain db -> S=1 plane, plane passes through
    assert as_dataplane(db).n_shards == 1
    assert as_dataplane(plane) is plane


def test_oversharded_tiny_relation_regression():
    """Regression (n=1, S=4): more shards than tuples must clamp to n
    non-empty shards — never emit zero-width shard dispatches — and the
    oversharded plane must still answer queries correctly end to end."""
    from repro.core.partition import split_bounds
    assert split_bounds(0, 1, 4) == [(0, 1)]      # clamp, no empties
    one = [["E1", "Ada", "Byron", "900", "Math"]]
    db1 = outsource(jax.random.PRNGKey(3), one,
                    column_names=["Id", "First", "Last", "Sal", "Dept"],
                    codec=CODEC, n_shares=20, degree=1)
    plane = ShardedRelation(db1, shards=4)
    assert plane.n_shards == 1
    assert all(s.n_tuples > 0 for s in plane.shards)
    assert plane.max_shard_rows == 1
    client = QueryClient(plane, key=9)
    assert client.stats().shards == 1              # planner sees the clamp
    res = client.run(Count(Eq("First", "Ada")))
    assert res.count == 1
    sel = client.run(Select(Eq("First", "Ada"), strategy="one_round"))
    assert sel.rows == [one[0]]
    # through attach too: an explicit shards=4 on a 1-tuple relation
    via_attach = QueryClient(db1, key=9)
    assert via_attach.attach(shards=4).n_shards == 1
    assert via_attach.run(Count(Eq("First", "Ada"))).count == 1


# ---------------------------------------------------------------------------
# S ∈ {1,2,4}: sharded batch == unsharded sequential, all five families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_batch_equals_unsharded_sequential(range_db, child_db,
                                                   shards):
    _, db = range_db
    plans = _all_family_plans(child_db)
    seq = [QueryClient(db, key=42).run(p) for p in plans]

    client = QueryClient(db, key=42)
    plane = client.attach(shards=shards)
    bat = client.run_batch(plans)
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)
    # fan-out accounting: every sharded cloud step emitted exactly one
    # dispatch per shard
    assert plane.stats.dispatches == plane.stats.steps * plane.n_shards


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_aggregation_matches_plaintext_oracle(range_db, shards):
    """SUM/AVG/MIN-MAX open the exact plaintext answer at every S: the
    per-shard partial sums combine additively mod p, the tournament's
    candidates are shard-order-independent by construction."""
    rows, db = range_db
    vals = np.array([int(r[2]) for r in rows])
    names = np.array([r[1] for r in rows])
    client = QueryClient(db, key=42)
    client.attach(shards=shards)
    res = client.run_batch([
        Aggregate("sum", "Val"),
        Aggregate("avg", "Val", where=Eq("Name", "nm2")),
        Aggregate("min", "Val", where=Eq("Name", "nm1")),
        Aggregate("max", "Val", verify=True),
    ])
    assert res[0].value == int(vals.sum())
    assert res[1].value == pytest.approx(vals[names == "nm2"].mean())
    assert res[2].value == int(vals[names == "nm1"].min())
    assert res[3].value == int(vals.max())


def test_shard_count_never_changes_step_count(range_db, child_db):
    """Steps (cloud rounds' dispatch sets) are a protocol property; only
    the per-step fan-out scales with S."""
    _, db = range_db
    plans = _all_family_plans(child_db)
    steps = set()
    for s in (1, 2, 4):
        client = QueryClient(db, key=42)
        plane = client.attach(shards=s)
        client.run_batch(plans)
        steps.add(plane.stats.steps)
        # at 20 clouds the 14-bit ripples re-share after 9 bits
        assert plane.stats.reshares > 0
    assert len(steps) == 1


def test_threaded_and_mapreduce_dispatchers_match_serial(range_db,
                                                         child_db):
    _, db = range_db
    plans = _all_family_plans(child_db)
    base = QueryClient(db, key=7).run_batch(plans)

    threaded = QueryClient(db, key=7)
    threaded.attach(shards=4, dispatcher=ThreadedDispatcher(max_workers=4))
    for a, b in zip(base, threaded.run_batch(plans)):
        _assert_results_equal(a, b)

    runner = MapReduceRunner(WorkerPool(3), lease_s=5.0, max_attempts=30)
    placed = QueryClient(db, key=7)
    placed.attach(shards=3, dispatcher=MapReduceDispatcher(runner))
    for a, b in zip(base, placed.run_batch(plans)):
        _assert_results_equal(a, b)


def test_sharded_client_constructor_and_attach_agree(range_db):
    _, db = range_db
    plans = [Count(Eq("Name", "nm1")), Select(Eq("Name", "nm2"))]
    via_ctor = QueryClient(ShardedRelation(db, shards=2), key=5)
    via_attach = QueryClient(db, key=5)
    via_attach.attach(shards=2)
    assert via_ctor.stats().shards == 2 == via_attach.stats().shards
    for a, b in zip(via_ctor.run_batch(plans), via_attach.run_batch(plans)):
        _assert_results_equal(a, b)


def test_attach_dispatcher_swap_preserves_sharding(range_db):
    """Swapping the placement policy must never collapse an existing
    partitioning; an explicit shards>1 re-shards."""
    _, db = range_db
    client = QueryClient(ShardedRelation(db, shards=4), key=5)
    pool = ThreadedDispatcher(max_workers=2)
    plane = client.attach(dispatcher=pool)
    assert plane.n_shards == 4 and plane.dispatcher is pool
    assert client.stats().shards == 4
    assert client.attach(shards=2).n_shards == 2
    pool.close()
    # a closed pool degrades to serial execution, still correct
    client2 = QueryClient(db, key=5)
    client2.attach(shards=3, dispatcher=pool)
    res = client2.run(Count(Eq("Name", "nm1")))
    assert res.count == QueryClient(db, key=5).run(
        Count(Eq("Name", "nm1"))).count


# ---------------------------------------------------------------------------
# dispatch counting backends: segments + batched join matrices
# ---------------------------------------------------------------------------

def _counting_backend(name="jnp"):
    """Count every hotspot dispatch, including the new fused ops."""
    base = get_backend(name)
    calls = {"aa_match_batch": 0, "ss_matmul": 0, "match_matrix": 0,
             "match_matrix_batch": 0, "ripple_carry": 0,
             "ripple_segment": 0}

    def wrap(op_name, fn):
        def run(a, b):
            calls[op_name] += 1
            return fn(a, b)
        return run

    base_ripple = ripple_stepper(base)
    base_segment = ripple_segmenter(base)

    def ripple(a, b, carry=None):
        calls["ripple_carry"] += 1
        return base_ripple(a, b, carry)

    def segment(a, b, carry=None):
        calls["ripple_segment"] += 1
        return base_segment(a, b, carry)

    be = Backend(
        name=f"{name}+counting",
        aa_match=wrap("aa_match", base.aa_match),
        ss_matmul=wrap("ss_matmul", base.ss_matmul),
        match_matrix=wrap("match_matrix", base.match_matrix),
        aa_match_batch=wrap("aa_match_batch", batched_matcher(base)),
        ripple_carry=ripple,
        ripple_segment=segment,
        match_matrix_batch=wrap("match_matrix_batch",
                                batched_match_matrix(base)))
    return be, calls


def test_range_phase_dispatches_one_segment_per_boundary(range_db):
    """t=14 bits at 7 clouds re-share every 2 bits -> 7 fused segment
    dispatches (never 14 per-bit steps) when the backend provides
    ripple_segment."""
    rows, _ = range_db
    db = outsource(jax.random.PRNGKey(19), rows,
                   column_names=["Id", "Name", "Val"], codec=CODEC,
                   n_shares=7, degree=1, numeric_columns={2: 14})
    assert planner.choose_reduce_every(7, 1, 14) == 2
    plans = [RangeCount(Between("Val", 600, 600 + 200 * i))
             for i in range(4)]
    seq = [QueryClient(db, key=33).run(p) for p in plans]
    be, calls = _counting_backend()
    bat = QueryClient(db, key=33, backend=be).run_batch(plans)
    assert calls["ripple_segment"] == 7
    assert calls["ripple_carry"] == 0
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)
    # at 34 clouds no reduction: the whole chain is ONE dispatch (the
    # carry degree climbs to 2t — needs enough clouds to open)
    deep = outsource(jax.random.PRNGKey(2),
                     [[f"i{k}", str(600 + 10 * k)] for k in range(8)],
                     column_names=["Id", "Val"], codec=CODEC, n_shares=34,
                     degree=1, numeric_columns={1: 14})
    calls_before = calls["ripple_segment"]
    QueryClient(deep, key=3, backend=be).run(
        RangeCount(Between("Val", 500, 900)))
    assert calls["ripple_segment"] == calls_before + 1


def test_join_group_stacks_match_matrices_into_one_dispatch(range_db,
                                                            child_db):
    """Equal-size right relations in a join group ride ONE (c,B,nx,ny)
    batched dispatch — the per-pkfk-job match_matrix loop is retired. At
    W = 6 the planner evaluates it in the aggregate form: one flattened
    ss_matmul for the group."""
    _, db = range_db
    plans = [Join(right=child_db, on=("Id", "Id"), kind="pkfk")
             for _ in range(3)]
    seq = [QueryClient(db, key=77).run(p) for p in plans]
    be, calls = _counting_backend()
    bat = QueryClient(db, key=77, backend=be).run_batch(plans)
    assert calls["match_matrix_batch"] == 0
    assert calls["match_matrix"] == 0
    assert calls["ss_matmul"] == 1 + 1         # 3 joins' match, the fetch
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_join_groups_split_by_right_relation_size(range_db, child_db):
    """Different-size right relations cannot stack: one batched dispatch
    per size class, results still sequential-identical."""
    rows, db = range_db
    other = outsource(jax.random.PRNGKey(29),
                      [[rows[i][0], f"u{i}"] for i in range(4)],
                      column_names=["Id", "Task"], codec=CODEC,
                      n_shares=20, degree=1)
    plans = [Join(right=child_db, on=("Id", "Id"), kind="pkfk"),
             Join(right=other, on=("Id", "Id"), kind="pkfk"),
             Join(right=child_db, on=("Id", "Id"), kind="pkfk")]
    seq = [QueryClient(db, key=13).run(p) for p in plans]
    be, calls = _counting_backend()
    bat = QueryClient(db, key=13, backend=be).run_batch(plans)
    assert calls["ss_matmul"] == 2 + 1         # one per ny class, fetch
    for a, b in zip(seq, bat):
        _assert_results_equal(a, b)


def test_sharded_dispatch_counts_scale_with_shards(range_db):
    """One fused dispatch per cloud step at S=1 becomes S per step."""
    _, db = range_db
    plans = [Select(Eq("Name", "nm1"), strategy="one_round"),
             Select(Eq("Name", "nm2"), strategy="one_round")]
    be1, calls1 = _counting_backend()
    QueryClient(db, key=9, backend=be1).run_batch(plans)
    assert calls1["aa_match_batch"] == 1 and calls1["ss_matmul"] == 1

    be4, calls4 = _counting_backend()
    client = QueryClient(db, key=9, backend=be4)
    client.attach(shards=4)
    client.run_batch(plans)
    assert calls4["aa_match_batch"] == 4 and calls4["ss_matmul"] == 4


@pytest.mark.parametrize("shards", [1, 3])
def test_match_relation_fetch_makes_no_table_digits(range_db, shards):
    """Only an embedding table is kept as resident digits: a one-round
    select's fetch contracts the relation's uint32 shares as before."""
    rows, db = range_db
    client = QueryClient(db, key=9)
    plane = client.attach(shards=shards)
    plans = [Select(Eq("Name", "nm2"), strategy="one_round"),
             Select(Eq("Name", "zzz"), strategy="one_round")]
    got = client.run_batch(plans)
    for res, name in zip(got, ("nm2", "zzz")):
        assert res.strategy == "one_round"
        assert res.addresses == [i for i, r in enumerate(rows)
                                 if r[1] == name]
        assert res.rows == [r for r in rows if r[1] == name]
    assert plane.stats.table_splits == 0
    assert plane.stats.presplit_contractions == 0


# ---------------------------------------------------------------------------
# fused-op parity oracles
# ---------------------------------------------------------------------------

def test_ripple_segment_equals_per_bit_stepper():
    from repro.api.backends import jnp_ripple_carry, jnp_ripple_segment
    key = jax.random.PRNGKey(0)
    a = jax.random.randint(key, (3, 4, 8, 6), 0, 2).astype(jnp.uint32)
    b = jax.random.randint(jax.random.fold_in(key, 1), (3, 4, 8, 6), 0,
                           2).astype(jnp.uint32)
    # from-LSB chain
    rb_s, co_s = jnp_ripple_segment(a, b, None)
    rb, co = None, None
    for i in range(6):
        rb, co = jnp_ripple_carry(a[..., i], b[..., i], co if i else None)
    np.testing.assert_array_equal(np.asarray(rb_s), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(co_s), np.asarray(co))
    # mid-chain continuation with an incoming carry
    carry0 = jax.random.randint(jax.random.fold_in(key, 2), (3, 4, 8), 0,
                                7).astype(jnp.uint32)
    rb_s, co_s = jnp_ripple_segment(a, b, carry0)
    rb, co = None, carry0
    for i in range(6):
        rb, co = jnp_ripple_carry(a[..., i], b[..., i], co)
    np.testing.assert_array_equal(np.asarray(rb_s), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(co_s), np.asarray(co))


def test_ripple_segment_pallas_equals_jnp():
    from repro.api.backends import jnp_ripple_segment
    from repro.kernels import ops
    key = jax.random.PRNGKey(5)
    a = jax.random.randint(key, (2, 6, 10, 5), 0, 2).astype(jnp.uint32)
    b = jax.random.randint(jax.random.fold_in(key, 1), (2, 6, 10, 5), 0,
                           2).astype(jnp.uint32)
    for carry in (None, jax.random.randint(jax.random.fold_in(key, 2),
                                           (2, 6, 10), 0,
                                           11).astype(jnp.uint32)):
        rb_p, co_p = ops.ripple_segment(a, b, carry)
        rb_j, co_j = jnp_ripple_segment(a, b, carry)
        np.testing.assert_array_equal(np.asarray(rb_p), np.asarray(rb_j))
        np.testing.assert_array_equal(np.asarray(co_p), np.asarray(co_j))


def test_match_matrix_batch_equals_per_pair(range_db, child_db):
    for name in ("jnp", "pallas"):
        be = get_backend(name)
        _, db = range_db
        bx = jnp.stack([db.column(0).values, db.column(1).values], axis=1)
        by = jnp.stack([child_db.column(0).values,
                        child_db.column(0).values], axis=1)
        fused = batched_match_matrix(be)(bx, by)
        for k in range(2):
            np.testing.assert_array_equal(
                np.asarray(fused[:, k]),
                np.asarray(be.match_matrix(bx[:, k], by[:, k])))


# ---------------------------------------------------------------------------
# planner: shard-aware dispatch pricing + batch explanation
# ---------------------------------------------------------------------------

def test_explain_batch_predicts_run_batch_ledger(range_db, child_db):
    _, db = range_db
    plans = _all_family_plans(child_db)
    client = QueryClient(db, key=1)
    exp = client.explain(plans)
    assert exp.shards == 1
    assert exp.bits > 0 and exp.rounds > 0 and exp.dispatches > 0
    # RangeCount and RangeSelect share a column width -> ONE fused
    # range group, reported under range_select because a member fetches
    assert {g.family for g in exp.groups} == {
        "count", "one_round", "tree", "one_tuple", "range_select",
        "pkfk", "equi", "aggregate"}
    # bits/rounds are protocol: invariant to S; dispatches scale with it
    sharded = QueryClient(db, key=1)
    sharded.attach(shards=4)
    exp4 = sharded.explain(plans)
    assert exp4.shards == 4
    assert exp4.bits == exp.bits and exp4.rounds == exp.rounds
    assert exp4.dispatches > exp.dispatches


def test_reattach_invalidates_cached_explanations(range_db, child_db):
    """Regression: attach(shards=S) after explain() left stale
    ``CostEstimate.dispatches`` (priced at the OLD shard count) in cached
    BatchExplanations — re-attaching must invalidate the cache."""
    _, db = range_db
    plans = _all_family_plans(child_db)
    client = QueryClient(db, key=1)
    exp1 = client.explain(plans)
    assert client.explain(plans) is exp1            # cached while valid
    client.attach(shards=4)
    exp4 = client.explain(plans)
    assert exp4 is not exp1                         # invalidated
    assert exp4.shards == 4 and exp4.dispatches > exp1.dispatches
    # fresh-client parity: the recomputed estimate IS the sharded truth
    fresh = QueryClient(db, key=1)
    fresh.attach(shards=4)
    assert fresh.explain(plans) == exp4
    # per-relation namespaces cache (and label) independently
    multi = QueryClient(db, key=1)
    multi.attach(child_db, name="tasks")
    exp_default = multi.explain(
        [Select(Eq("Name", "nm1"), strategy="one_round")])
    exp_tasks = multi.explain(
        [Select(Eq("Task", "t1"), strategy="one_round")], relation="tasks")
    assert exp_default.relation == "default"
    assert exp_tasks.relation == "tasks"
    assert exp_tasks.bits != exp_default.bits       # priced per target n
    multi.attach(shards=2, name="tasks")
    assert multi.explain(
        [Select(Eq("Task", "t1"), strategy="one_round")],
        relation="tasks").dispatches > exp_tasks.dispatches


def test_explain_batch_select_group_matches_group_estimate(range_db):
    from repro.api import estimate_batch_group_cost
    _, db = range_db
    plans = [Select(Eq("Name", "nm1"), strategy="one_round",
                    expected_matches=4),
             Select(Eq("Name", "nm2"), strategy="one_round",
                    expected_matches=2)]
    client = QueryClient(db, key=1)
    exp = client.explain(plans)
    (grp,) = exp.groups
    want = estimate_batch_group_cost(client.stats(), "one_round",
                                     ells=[4, 2])
    assert grp.family == "one_round" and grp.size == 2
    assert grp.estimate == want
    assert exp.bits == want.bits and exp.rounds == want.rounds


def test_explain_single_select_carries_dispatches(range_db):
    _, db = range_db
    client = QueryClient(db, key=1)
    ests = client.explain(Select(Eq("Name", "nm1")))
    assert all(e.dispatches >= 1 for e in ests)
    client.attach(shards=4)
    ests4 = client.explain(Select(Eq("Name", "nm1")))
    by_strategy = {e.strategy: e for e in ests4}
    for e in ests:
        assert by_strategy[e.strategy].dispatches > e.dispatches
        assert by_strategy[e.strategy].bits == e.bits


def test_explain_batch_counts_shared_fetch_once(range_db, child_db):
    """Two fetch-riding groups must not double-price the single
    cross-group fetch dispatch set."""
    from repro.api import estimate_pkfk_cost, estimate_select_cost, DBStats
    _, db = range_db
    client = QueryClient(db, key=1)
    exp = client.explain([Select(Eq("Name", "nm1"), strategy="one_round"),
                          Join(right=child_db, on=("Id", "Id"),
                               kind="pkfk")])
    stats = client.stats()
    solo = (estimate_select_cost("one_round", stats).dispatches
            + estimate_pkfk_cost(stats, DBStats.of(child_db)).dispatches)
    assert exp.dispatches == solo - stats.shards
