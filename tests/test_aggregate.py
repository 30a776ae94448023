"""Verified secret-shared aggregation (SUM / AVG / MIN-MAX).

Anchor properties:
  * batched SUM/AVG/MIN-MAX open exactly what a plaintext NumPy oracle
    computes (including negative values and empty predicates);
  * a batch's per-query rows/values/ledgers are bit-identical to the
    equivalent sequential runs;
  * ``verify=True`` is a no-op on an honest transcript, detects an
    injected corrupted cloud share, and its priced overhead appears in
    ``explain()`` — which predicts every aggregate ledger EXACTLY
    (comm bits and rounds), not just approximately;
  * unknown plan classes fail with a clear ``PlanNotSupported``.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.api import (Aggregate, Count, Eq, Plan, PlanNotSupported,
                       QueryClient, Select, VerificationError, get_backend,
                       ripple_segmenter)
from repro.core import Codec, outsource
from repro.core.queries import aggregate as agg_mod

CODEC = Codec(word_length=6)


@pytest.fixture(scope="module")
def signed_db():
    """12 rows with NEGATIVE and positive values — exercises the signed
    two's-complement opening and the comparator's sign handling."""
    rows = [[f"id{i}", f"nm{i % 5}", str(-300 + 137 * i)] for i in range(12)]
    db = outsource(jax.random.PRNGKey(19), rows,
                   column_names=["Id", "Name", "Val"], codec=CODEC,
                   n_shares=20, degree=1, numeric_columns={2: 14})
    return rows, db


def _oracle(rows):
    vals = np.array([int(r[2]) for r in rows])
    names = np.array([r[1] for r in rows])
    return vals, names


ALL_OPS_PLANS = [
    Aggregate("sum", "Val"),
    Aggregate("sum", "Val", where=Eq("Name", "nm1")),
    Aggregate("avg", "Val"),
    Aggregate("avg", "Val", where=Eq("Name", "nm2")),
    Aggregate("min", "Val", reduce_every=2),
    Aggregate("max", "Val", reduce_every=2),
    Aggregate("min", "Val", where=Eq("Name", "nm3"), reduce_every=2),
    Aggregate("max", "Val", where=Eq("Name", "nm4"), reduce_every=2),
]


def _expected(rows, plan):
    vals, names = _oracle(rows)
    mask = (names == plan.where.pattern if plan.where is not None
            else np.ones(len(vals), bool))
    sel = vals[mask]
    if plan.op == "sum":
        return int(sel.sum())
    if plan.op == "avg":
        return float(sel.mean()) if len(sel) else None
    if plan.op == "min":
        return int(sel.min()) if len(sel) else None
    return int(sel.max()) if len(sel) else None


# ---------------------------------------------------------------------------
# oracle parity
# ---------------------------------------------------------------------------

def test_all_ops_match_numpy_oracle(signed_db):
    rows, db = signed_db
    res = QueryClient(db, key=7).run_batch(ALL_OPS_PLANS)
    for plan, r in zip(ALL_OPS_PLANS, res):
        want = _expected(rows, plan)
        assert r.strategy == f"agg_{plan.op}"
        if plan.op == "avg":
            assert r.value == pytest.approx(want)
        else:
            assert r.value == want
        if plan.where is not None and plan.op != "sum":
            vals, names = _oracle(rows)
            assert r.count == int((names == plan.where.pattern).sum())


def test_batch_equals_sequential(signed_db):
    """Rows, values AND per-query ledgers are fusion-invariant."""
    _, db = signed_db
    seq = [QueryClient(db, key=7).run(p) for p in ALL_OPS_PLANS]
    bat = QueryClient(db, key=7).run_batch(ALL_OPS_PLANS)
    for a, b in zip(seq, bat):
        assert a.value == b.value
        assert a.count == b.count
        assert a.strategy == b.strategy
        assert a.ledger == b.ledger


def test_aggregates_fuse_with_other_families(signed_db):
    """Aggregation rides run_batch beside counts/selections; conditional
    AVG denominators fuse into the SAME count phase as explicit Counts."""
    rows, db = signed_db
    plans = [Count(Eq("Name", "nm1")),
             Aggregate("avg", "Val", where=Eq("Name", "nm1")),
             Select(Eq("Name", "nm2"), strategy="one_round"),
             Aggregate("sum", "Val")]
    seq = [QueryClient(db, key=3).run(p) for p in plans]
    bat = QueryClient(db, key=3).run_batch(plans)
    for a, b in zip(seq, bat):
        assert a.value == b.value and a.count == b.count
        assert a.rows == b.rows
        assert a.ledger == b.ledger
    vals, names = _oracle(rows)
    assert bat[1].value == pytest.approx(vals[names == "nm1"].mean())


def test_empty_predicate_yields_none_value(signed_db):
    _, db = signed_db
    cl = QueryClient(db, key=5)
    r = cl.run(Aggregate("min", "Val", where=Eq("Name", "zzz"),
                         reduce_every=2))
    assert r.value is None and r.count == 0
    r = cl.run(Aggregate("avg", "Val", where=Eq("Name", "zzz")))
    assert r.value is None and r.count == 0
    # an empty-predicate SUM is an honest 0
    r = cl.run(Aggregate("sum", "Val", where=Eq("Name", "zzz")))
    assert r.value == 0


def test_convenience_method(signed_db):
    rows, db = signed_db
    vals, _ = _oracle(rows)
    r = QueryClient(db, key=11).aggregate("max", "Val", reduce_every=2)
    assert r.value == int(vals.max())


def test_single_tuple_relation_minmax():
    """n = 1: the tournament is empty; the value opens at base degree."""
    db = outsource(jax.random.PRNGKey(2), [["E1", "42"]],
                   column_names=["Id", "V"], codec=CODEC, n_shares=20,
                   degree=1, numeric_columns={1: 8})
    cl = QueryClient(db, key=1)
    assert cl.run(Aggregate("min", "V", reduce_every=2)).value == 42
    assert cl.run(Aggregate("sum", "V", verify=True)).value == 42


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------

def test_plan_validation():
    with pytest.raises(ValueError, match="unknown aggregate op"):
        Aggregate("median", "Val")
    with pytest.raises(ValueError, match="reduce_every"):
        Aggregate("sum", "Val", reduce_every=2)
    with pytest.raises(ValueError, match="reduce_every"):
        Aggregate("min", "Val", reduce_every=-1)


def test_non_numeric_column_rejected(signed_db):
    _, db = signed_db
    cl = QueryClient(db, key=1)
    with pytest.raises(ValueError, match="binary form"):
        cl.run(Aggregate("sum", "Name"))
    with pytest.raises(ValueError, match="binary form"):
        cl.explain([Aggregate("sum", "Name")])


def test_unknown_plan_raises_plan_not_supported(signed_db):
    """Regression: an unknown plan class used to die with an opaque
    TypeError deep in run_batch — now both the executor and the explainer
    name the offending type."""
    _, db = signed_db

    class Bogus(Plan):
        pass

    cl = QueryClient(db, key=1)
    with pytest.raises(PlanNotSupported, match="Bogus"):
        cl.run_batch([Count(Eq("Name", "nm1")), Bogus()])
    with pytest.raises(PlanNotSupported, match="Bogus"):
        cl.explain([Bogus()])
    with pytest.raises(PlanNotSupported, match="int"):
        cl.explain(7)
    # PlanNotSupported subclasses TypeError: legacy handlers still catch
    assert issubclass(PlanNotSupported, TypeError)


def test_explain_single_non_select_plan(signed_db):
    """Regression: explain(Count(...)) used to AttributeError on
    ``expected_matches``; any single plan now prices as a batch of one."""
    _, db = signed_db
    cl = QueryClient(db, key=1)
    exp = cl.explain(Count(Eq("Name", "nm1")))
    assert exp.groups[0].family == "count" and exp.bits > 0
    exp = cl.explain(Aggregate("min", "Val", reduce_every=2))
    assert exp.groups[0].family == "aggregate"


# ---------------------------------------------------------------------------
# explain(): exact ledger prediction, priced verification overhead
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("verify", [False, True])
def test_explain_predicts_aggregate_ledgers_exactly(signed_db, verify):
    _, db = signed_db
    for plan in ALL_OPS_PLANS:
        plan = dataclasses.replace(plan, verify=verify)
        exp = QueryClient(db, key=7).explain([plan])
        res = QueryClient(db, key=7).run(plan)
        assert exp.bits == res.ledger.communication_bits, plan
        assert exp.rounds == res.ledger.rounds, plan
        assert exp.groups[0].family == "aggregate"


def test_verify_overhead_is_priced_and_bounded(signed_db):
    """verify=True costs exactly one extra round and c checksum elements
    per opened tensor — and never changes the opened value."""
    _, db = signed_db
    for plan in (Aggregate("sum", "Val", where=Eq("Name", "nm1")),
                 Aggregate("min", "Val", where=Eq("Name", "nm3"),
                           reduce_every=2)):
        off = QueryClient(db, key=7).run(plan)
        on = QueryClient(db, key=7).run(
            dataclasses.replace(plan, verify=True))
        assert on.value == off.value
        assert on.ledger.rounds == off.ledger.rounds + 1
        tensors = 2 if (plan.op in ("min", "max")
                        and plan.where is not None) else 1
        assert (on.ledger.communication_bits
                - off.ledger.communication_bits) == 31 * db.n_shares * tensors


# ---------------------------------------------------------------------------
# verification: honest no-op, tampered cloud detected
# ---------------------------------------------------------------------------

def test_verify_detects_tampered_sum_share(signed_db):
    """A cloud corrupting its contraction output share is caught by the
    consistency round — and silently opens WRONG without verify."""
    _, db = signed_db
    base = get_backend("jnp")

    def bad_matmul(a, b):
        return base.ss_matmul(a, b).at[3].add(5)

    be = dataclasses.replace(base, name="jnp+tamper", ss_matmul=bad_matmul)
    plan = Aggregate("sum", "Val", where=Eq("Name", "nm1"))
    with pytest.raises(VerificationError, match="SUM verification failed"):
        QueryClient(db, key=7, backend=be).run(
            dataclasses.replace(plan, verify=True))
    honest = QueryClient(db, key=7).run(plan)
    tampered = QueryClient(db, key=7, backend=be).run(plan)
    assert tampered.value != honest.value      # the attack verify stops


def test_verify_detects_tampered_minmax_share(signed_db):
    """A cloud corrupting the final tournament level's comparator output
    (the last level has exactly one pair) fails MIN verification."""
    _, db = signed_db
    base = get_backend("jnp")
    base_seg = ripple_segmenter(base)

    def bad_segment(a, b, carry=None):
        rb, co = base_seg(a, b, carry)
        if a.shape[-2] == 1:                   # final level: one pair
            rb = rb.at[2].add(1)
        return rb, co

    be = dataclasses.replace(base, name="jnp+tamper",
                             ripple_segment=bad_segment)
    with pytest.raises(VerificationError, match="MIN verification failed"):
        QueryClient(db, key=7, backend=be).run(
            Aggregate("min", "Val", reduce_every=2, verify=True))


def test_verify_needs_redundant_clouds():
    """c = degree+1 opens fine but cannot cross-check: verify must refuse
    loudly instead of silently passing (verify_consistency is vacuous
    without redundant shares)."""
    db = outsource(jax.random.PRNGKey(4),
                   [[f"i{k}", str(10 * k)] for k in range(4)],
                   column_names=["Id", "V"], codec=CODEC, n_shares=2,
                   degree=1, numeric_columns={1: 8})
    cl = QueryClient(db, key=1)
    assert cl.run(Aggregate("sum", "V")).value == 60
    with pytest.raises(VerificationError, match="degree\\+2"):
        cl.run(Aggregate("sum", "V", verify=True))


# ---------------------------------------------------------------------------
# phase-level contracts
# ---------------------------------------------------------------------------

def test_sum_phase_rejects_overflowable_relations():
    """A sum that could wrap the Mersenne-31 half-range must not wrap: it
    opens in bit-plane limbs that cannot, and comes out exact (here the
    single field sum would lift to a negative number); past 2^29 tuples, where not even a
    one-bit limb is safe, the phase refuses."""
    big = (1 << 28) - 1
    values = [big] * 6 + [-big, 12345]
    db = outsource(jax.random.PRNGKey(4),
                   [[f"i{k}", str(v)] for k, v in enumerate(values)],
                   column_names=["Id", "V"], codec=Codec(word_length=10),
                   n_shares=4, degree=1, numeric_columns={1: 30})
    assert sum(values) > (1 << 30)      # one field sum would read < 0
    assert agg_mod.sum_limbs(8, 30) == [27, 3]
    cl = QueryClient(db, key=1)
    assert cl.run(Aggregate("sum", "V")).value == sum(values)
    assert cl.run(Aggregate("avg", "V")).value == sum(values) / 8
    assert agg_mod.sum_limbs(8192, 26) == [17, 9]
    assert agg_mod.sum_limbs(8192, 8) == [8]
    with pytest.raises(ValueError, match="half-range"):
        agg_mod.sum_limbs((1 << 29) + 1, 8)


def test_mixed_bit_width_jobs_must_group():
    """agg phases demand uniform t_bits per fused call (the client groups
    by bit width, so this is a phase-level contract test)."""
    db = outsource(jax.random.PRNGKey(4),
                   [[f"i{k}", str(k), str(2 * k)] for k in range(4)],
                   column_names=["Id", "A", "B"], codec=CODEC, n_shares=20,
                   degree=1, numeric_columns={1: 8, 2: 10})
    be = get_backend("jnp")
    from repro.core.costs import CostLedger
    jobs = [agg_mod.SumJob(value_column=1, key=jax.random.PRNGKey(0),
                           ledger=CostLedger()),
            agg_mod.SumJob(value_column=2, key=jax.random.PRNGKey(1),
                           ledger=CostLedger())]
    with pytest.raises(ValueError, match="uniform"):
        agg_mod.agg_sum_phase(be, db, jobs)
    # ...while the client transparently groups them into two fused calls
    res = QueryClient(db, key=2).run_batch([Aggregate("sum", "A"),
                                            Aggregate("sum", "B")])
    assert [r.value for r in res] == [6, 12]


def test_minmax_job_validation():
    with pytest.raises(ValueError, match="min.*max|'min' or 'max'"):
        agg_mod.MinMaxJob(value_column=0, key=jax.random.PRNGKey(0),
                          ledger=None, op="sum")


def test_distinct_value_columns_fuse_in_one_batch():
    """Conditional sums over DIFFERENT value columns of the same width
    still ride one phase (one ss_matmul per distinct column)."""
    rows = [[f"i{k}", f"g{k % 2}", str(k), str(10 * k)] for k in range(6)]
    db = outsource(jax.random.PRNGKey(8), rows,
                   column_names=["Id", "G", "A", "B"], codec=CODEC,
                   n_shares=20, degree=1, numeric_columns={2: 8, 3: 8})
    plans = [Aggregate("sum", "A", where=Eq("G", "g0")),
             Aggregate("sum", "B", where=Eq("G", "g1")),
             Aggregate("sum", "A")]
    res = QueryClient(db, key=3).run_batch(plans)
    assert res[0].value == 0 + 2 + 4
    assert res[1].value == 10 + 30 + 50
    assert res[2].value == sum(range(6))
    seq = [QueryClient(db, key=3).run(p) for p in plans]
    for a, b in zip(seq, res):
        assert a.value == b.value and a.ledger == b.ledger
