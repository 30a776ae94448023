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
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (Aggregate, Count, Eq, MapReduceExecutor, Plan,
                       PlanNotSupported, QueryClient, Select,
                       VerificationError, get_backend, ripple_segmenter)
from repro.core import Codec, dataplane, encoding, field, outsource
from repro.core.costs import CostLedger
from repro.core.dataplane import ShardedRelation, span
from repro.core.queries import aggregate as agg_mod
from repro.core.queries import rounds
from repro.core.queries.aggregate import _validate_numeric, _verify_openings
from repro.core.queries.rounds import (MatchJob, _batched_matcher,
                                       _open_on_host, _reshare,
                                       _ripple_segmenter, _share_patterns,
                                       _stack_columns, _stack_numeric,
                                       _within_clouds, ripple_schedule)
from repro.core.shamir import Shares
from repro.launch.serve import QueryServer
from repro.runtime import MapReduceRunner, WorkerPool

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


# ---------------------------------------------------------------------------
# fused tournament programs: bit-identical to the host loop they replaced
# ---------------------------------------------------------------------------
# The reference below is agg_minmax_rounds as it ran when every slice, key
# split and re-share of a level was its own host dispatch, kept verbatim
# apart from its name. Its helpers come from the modules the program
# imports.

@functools.partial(jax.jit, static_argnames=("pairs",))
def _pair_level(cand, is_min, pairs: int):
    """One tournament level's fixed pairing (2i, 2i+1) of (c, B, k, t)
    candidates. SS-SUB(lhs, rhs) opens [rhs < lhs]: min wants s = [x2 < x1]
    (lhs=x1), max wants s = [x1 < x2] (lhs=x2); either way the winner is
    x1 + s·(x2 − x1)."""
    x1 = cand[:, :, 0:2 * pairs:2]                          # (c,B,pairs,t)
    x2 = cand[:, :, 1:2 * pairs:2]
    return (x1, x2, jnp.where(is_min, x1, x2), jnp.where(is_min, x2, x1))


@jax.jit
def _select_winner(x1, x2, s_bits):
    """The oblivious select x₁ + s·(x₂ − x₁), share-wise."""
    return field.add(x1, field.mul(s_bits[..., None], field.sub(x2, x1)))


def _loop_minmax_rounds(be, db, jobs):
    """MIN/MAX for B jobs, every tournament level fused across the batch.

    Returns ``(value, count)`` per job: ``count`` is the opened predicate
    cardinality for conditional jobs (None otherwise); ``value`` is None
    when a conditional job matched nothing (the tournament would open the
    sentinel). The final level's winner opens at its native comparator
    degree — no trailing re-share — so a share tampered anywhere after the
    last reduction fails verification.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    c = db.n_shares
    n = db.n_tuples
    d = db.base_degree
    t_bits = _validate_numeric(db, jobs, "agg_minmax_rounds")
    if t_bits < 2:
        raise ValueError("MIN/MAX needs a >= 2-bit value column")
    if len({j.reduce_every for j in jobs}) != 1:
        raise ValueError("a fused agg_minmax_rounds needs uniform "
                         "reduce_every across its jobs (group them)")
    reduce_every = jobs[0].reduce_every
    b = len(jobs)
    w = codec.word_length
    per_q = w * codec.alphabet_size
    cond = [i for i, j in enumerate(jobs) if j.conditional]

    # round 1: predicates travel up; the final bits come back in the same
    # logical round once the tournament's re-share rounds are done.
    for j in jobs:
        j.ledger.round()
        if j.conditional:
            j.ledger.send(c * per_q)

    # every job's key splits (pattern, reduction-chain); the fused
    # reduction chain seeds from the first job, as in range_phase —
    # re-share randomness never changes opened values.
    split_keys = [jax.random.split(j.key) for j in jobs]
    red_key = split_keys[0][1]

    counts: Optional[Shares] = None
    masked_by_pos: dict = {}
    if cond:
        cond_jobs = [jobs[i] for i in cond]
        with span(plane.stats, "user.share"):
            p_all = _share_patterns(db, [
                MatchJob(j.pred_column, j.pattern, split_keys[i][0],
                         j.ledger) for i, j in zip(cond, cond_jobs)])
        match_deg = (db.relation.degree + p_all.degree) * w
        bits = Shares(plane.run_concat(
            lambda v, sh: _batched_matcher(be)(
                _stack_columns(v, [j.pred_column
                                   for j in cond_jobs]).values,
                p_all.values), axis=2, phase="match"),
            match_deg)                                      # (c, Bc, n)
        counts = Shares(field.sum_(bits.values, axis=2), match_deg)
        # sentinel mask: non-matching rows become the op's losing extreme
        # (a public constant, so masking is cloud-local share arithmetic):
        # masked = m·(x − s) + s.
        bound = (1 << (t_bits - 2)) - 1
        sent = np.stack([encoding.encode_number_bits(
            bound if j.op == "min" else -bound, t_bits)
            for j in cond_jobs])                            # (Bc, t)
        sent_b = jnp.asarray(sent, field.DTYPE)[None, :, None, :]
        x = _stack_numeric(db, [j.value_column for j in cond_jobs])
        delta = field.sub(x.values, jnp.broadcast_to(sent_b,
                                                     x.values.shape))
        masked = field.add(field.mul(bits.values[..., None], delta),
                           jnp.broadcast_to(sent_b, x.values.shape))
        red_key, sub = jax.random.split(red_key)
        masked = _reshare(plane, sub, Shares(masked, match_deg + x.degree),
                          d, "the MIN/MAX sentinel mask")
        for i, j in enumerate(cond_jobs):
            j.ledger.round()                 # the mask re-share round
            j.ledger.send(c * c)
            j.ledger.cloud(n * (per_q + t_bits))
            masked_by_pos[cond[i]] = masked.values[:, i]
    for i, j in enumerate(jobs):
        if not j.conditional:
            j.ledger.cloud(n * t_bits)

    cand = jnp.stack(
        [masked_by_pos[i] if i in masked_by_pos
         else db.numeric[jobs[i].value_column].values
         for i in range(b)], axis=1)                        # (c, B, n, t)
    cand_deg = d

    # knockout tournament: global fixed pairing (2i, 2i+1) per level, odd
    # leftover carried unpaired; each level is one batched SS-SUB ripple
    # (sign s = [loser-side < winner-side]) plus the oblivious select
    # x₁ + s·(x₂ − x₁). Levels run on the gathered relation, like tree
    # Q&A rounds — identical transcript for every shard count.
    segment = _ripple_segmenter(be)
    is_min = jnp.asarray([j.op == "min" for j in jobs],
                         bool)[None, :, None, None]
    k = n
    while k > 1:
        pairs = k // 2
        x1, x2, lhs, rhs = _pair_level(cand, is_min, pairs)
        carry = None
        carry_deg = 0
        s_bits = None
        for s0, s1, seg_deg in ripple_schedule(t_bits, reduce_every,
                                               cand_deg):
            if carry_deg:
                red_key, sub = jax.random.split(red_key)
                carry = _reshare(plane, sub, Shares(carry, carry_deg), 1,
                                 "the comparator's carry").values
                carry_deg = 1
                for j in jobs:
                    j.ledger.round()
                    j.ledger.send(c * c)
            with span(plane.stats, "cloud.ripple"):
                s_bits, carry = segment(lhs[..., s0:s1], rhs[..., s0:s1],
                                        carry)
            plane.stats.ripple_segments += 1
            carry_deg = seg_deg
        win = _select_winner(x1, x2, s_bits)
        win_deg = carry_deg + cand_deg
        for j in jobs:
            j.ledger.cloud(2 * pairs * t_bits)
        if 2 * pairs < k:
            win = jnp.concatenate([win, cand[:, :, 2 * pairs:]], axis=2)
        k = win.shape[2]
        if k > 1:
            # inter-level re-share back to the base degree (one round);
            # the FINAL level opens at its native degree instead, so a
            # post-reduction tamper is visible to verification.
            red_key, sub = jax.random.split(red_key)
            cand = _reshare(plane, sub, Shares(win, win_deg), d,
                            "the tournament's winners").values
            cand_deg = d
            for j in jobs:
                j.ledger.round()
                j.ledger.send(c * c)
        else:
            cand = win
            cand_deg = win_deg

    _within_clouds(cand_deg, c, "the MIN/MAX value")
    val_parts = [Shares(cand[:, i, 0], cand_deg) for i in range(b)]
    cnt_parts = {i: Shares(counts.values[:, kk], counts.degree)
                 for kk, i in enumerate(cond)}
    opened = _open_on_host(plane.stats,
                           val_parts + [cnt_parts[i] for i in cond])

    for i, j in enumerate(jobs):
        j.ledger.recv(c * t_bits)
        j.ledger.user((cand_deg + 1) * t_bits)
        if j.conditional:
            j.ledger.recv(c)
            j.ledger.user(counts.degree + 1)
    for i, j in enumerate(jobs):
        if j.verify:
            tensors = [val_parts[i]]
            if j.conditional:
                tensors.append(cnt_parts[i])
            _verify_openings(j, tensors, j.op.upper())

    out: List[Tuple[Optional[int], Optional[int]]] = []
    cnt_at = {i: b + kk for kk, i in enumerate(cond)}
    for i, j in enumerate(jobs):
        val = encoding.decode_number_bits(np.asarray(opened[i]))
        if j.conditional:
            cnt = int(opened[cnt_at[i]])
            out.append((val if cnt > 0 else None, cnt))
        else:
            out.append((val, None))
    return out


def _minmax_db(n):
    """n rows of signed 8-bit values, three names for the predicates."""
    rows = [[f"id{i}", f"nm{i % 3}", str((i * 37) % 121 - 60)]
            for i in range(n)]
    return rows, outsource(jax.random.PRNGKey(n), rows,
                           column_names=["Id", "Name", "Val"], codec=CODEC,
                           n_shares=20, degree=1, numeric_columns={2: 8})


@pytest.fixture(scope="module")
def minmax_dbs():
    """48 rows leave an odd candidate over (48, 24, 12, 6, 3, 2, 1); 64
    pair evenly; both take 6 levels."""
    return {n: _minmax_db(n) for n in (48, 64)}


def _minmax_jobs(op, conditional, b, reduce_every):
    other = "max" if op == "min" else "min"
    return [agg_mod.MinMaxJob(
        value_column=2, key=jax.random.PRNGKey(100 + i), ledger=CostLedger(),
        pred_column=1 if conditional else None,
        pattern=f"nm{i + 1}" if conditional else None,
        op=op if i == 0 else other, reduce_every=reduce_every)
        for i in range(b)]


@pytest.mark.parametrize("n", [48, 64])
@pytest.mark.parametrize("reduce_every", [0, 2])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("conditional", [False, True])
@pytest.mark.parametrize("op", ["min", "max"])
def test_fused_tournament_matches_host_loop(minmax_dbs, monkeypatch, op,
                                            conditional, b, reduce_every, n):
    """The fused pairing, key splits and select open the same shares, at
    the same degree, with the same ledgers and re-share and segment counts
    as the host loop: one ripple segment a level and segment."""
    rows, db = minmax_dbs[n]
    opened = {}

    def recording(name):
        def record(sink, parts):
            opened[name] = [(p.degree, np.asarray(p.values)) for p in parts]
            return rounds._open_on_host(sink, parts)
        return record

    monkeypatch.setattr(agg_mod, "_open_on_host", recording("program"))
    monkeypatch.setitem(globals(), "_open_on_host", recording("loop"))
    be = get_backend("jnp")
    runs = {}
    for name, phase in (("program", agg_mod.agg_minmax_rounds),
                        ("loop", _loop_minmax_rounds)):
        plane = ShardedRelation(db, shards=1)
        jobs = _minmax_jobs(op, conditional, b, reduce_every)
        runs[name] = (phase(be, plane, jobs), [j.ledger for j in jobs],
                      plane.stats)
    (got, got_led, got_st), (want, want_led, want_st) = (runs["program"],
                                                         runs["loop"])
    assert got == want and got_led == want_led
    assert len(opened["program"]) == len(opened["loop"])
    for (dg, vg), (dw, vw) in zip(opened["program"], opened["loop"]):
        assert dg == dw
        np.testing.assert_array_equal(vg, vw)
    assert (got_st.reshares, got_st.ripple_segments) == (want_st.reshares,
                                                         want_st.ripple_segments)
    assert got_st.ripple_segments == 6 * len(ripple_schedule(8,
                                                             reduce_every, 1))
    vals, names = _oracle(rows)
    for i, (value, count) in enumerate(got):
        mask = names == f"nm{i + 1}" if conditional else np.ones(n, bool)
        pick = min if (op if i == 0 else {"min": "max",
                                          "max": "min"}[op]) == "min" else max
        assert value == pick(vals[mask])
        assert count == (int(mask.sum()) if conditional else None)


def test_second_identical_max_compiles_nothing(minmax_dbs):
    """Each tournament program is traced once per shape: the same MAX
    again compiles no program."""
    _, db = minmax_dbs[48]
    client = QueryClient(db, key=5)
    plane = client.attach(shards=1)
    plan = Aggregate("max", "Val", reduce_every=2)
    first = client.run(plan)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        second = client.run(plan)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert second.value == first.value
    assert plane.stats.ripple_segments == 2 * 6 * 4    # 8 bits, 2 a segment


def test_server_snapshot_counts_tournament_segments(minmax_dbs):
    """The levels run on the gathered relation, the same segments whatever
    the shard count, and the server folds their count into its
    snapshot."""
    rows, db = minmax_dbs[64]
    vals, _ = _oracle(rows)
    server = QueryServer(db, key=10, shards=2)
    reqs = [server.submit(Aggregate("max", "Val")) for _ in range(2)]
    while server.pending():
        server.pump()
    assert [r.result.value for r in reqs] == [int(vals.max())] * 2
    snap = server.stats.snapshot()
    assert snap["batches"] >= 1
    assert snap["ripple_segments"] == 6 * snap["batches"]    # one segment
    assert server.dataplane.stats.ripple_segments == 6 * snap["batches"]


def test_mapreduce_executor_runs_the_tournament(minmax_dbs):
    """A backend whose segment fans out to host workers runs each segment
    between the fused programs, with equal results."""
    _, db = minmax_dbs[48]
    runner = MapReduceRunner(WorkerPool(2), lease_s=5.0, max_attempts=30)
    plans = [Aggregate("max", "Val", reduce_every=2),
             Aggregate("min", "Val", where=Eq("Name", "nm1"),
                       reduce_every=2)]
    ref = QueryClient(db, key=21).run_batch(plans)
    got = QueryClient(db, key=21,
                      executor=MapReduceExecutor(runner, n_splits=3)
                      ).run_batch(plans)
    for a, b in zip(ref, got):
        assert (a.value, a.count, a.ledger) == (b.value, b.count, b.ledger)
