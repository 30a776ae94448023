"""Pattern-predicate engine acceptance (LIKE / prefix / suffix / substring).

The matcher pipeline's §3.1 chain is generalized to four predicate shapes
that all ride the fused round engine: masked LIKE on the full-width chain,
prefix on a truncated k-chain, suffix/substring on the sliding-window
automata step. Pinned here:

* every kind opens bit-identically to a cleartext oracle (wildcards,
  repeated substrings, empty words included);
* a wildcard-free LIKE provably lowers to the exact-equality path (same
  planner estimate field for field, same transcript, one_tuple eligible);
* mixed B=16 batches (pattern + equality + range) equal sequential
  execution in rows AND ledgers, with pattern fetches riding the single
  cross-group fetch matmul;
* rows/ledgers are invariant across S ∈ {1, 2, 4} shards on the Serial,
  Threaded and Mesh dispatchers;
* ``explain()`` is exact against measured ledgers for pattern counts and
  one-round pattern selects;
* malformed/unknown predicates raise typed ``PlanNotSupported``;
* the PK/FK join match matrix opens identically under the chain and
  aggregate evaluations (the planner prices the choice by launches).
"""
import jax
import numpy as np
import pytest

from repro.api import (AUTO, Between, Contains, Count, DBStats, Eq, Join,
                       Like, MeshDispatcher, PlanNotSupported, Prefix,
                       QueryClient, RangeCount, Select, Suffix,
                       ThreadedDispatcher, choose_match_method,
                       estimate_count_cost, estimate_match_method_launches,
                       estimate_pattern_cost, estimate_select_cost,
                       get_backend)
from repro.api.client import _lower_match
from repro.core import Codec, encoding, outsource
from repro.core.costs import CostLedger
from repro.core.queries import rounds
from repro.core.shamir import Shares
from repro.launch.mesh import make_host_mesh

CODEC = Codec(word_length=8)
ROWS = [
    ["banana", "x", "1"], ["bandana", "y", "2"], ["an", "z", "3"],
    ["nab", "x", "4"], ["ban", "y", "5"], ["anna", "z", "6"],
    ["cab", "x", "7"], ["cabana", "y", "8"],
]
WORDS = [r[0] for r in ROWS]


@pytest.fixture(scope="module")
def db():
    return outsource(jax.random.PRNGKey(0), ROWS, codec=CODEC,
                     n_shares=20, numeric_columns={2: 8})


@pytest.fixture(scope="module")
def right_db():
    return outsource(jax.random.PRNGKey(9),
                     [["banana", "r1"], ["cab", "r2"], ["zzz", "r3"]],
                     codec=CODEC, n_shares=20)


def _like_oracle(word: str, pattern: str) -> bool:
    kind, body, wild = encoding.parse_like(pattern)
    if kind == "exact":
        return word == body
    if kind == "contains":
        return body in word
    if kind == "suffix":
        return word.endswith(body)
    padded = word + "\0" * CODEC.word_length
    ok = all(i in wild or padded[i] == ch for i, ch in enumerate(body))
    if kind == "prefix":
        return ok
    # masked: fixed width — everything past the body must be terminator
    return ok and all(padded[i] == "\0"
                      for i in range(len(body), CODEC.word_length))


# ---------------------------------------------------------------------------
# oracle correctness: counts and selects, every predicate shape
# ---------------------------------------------------------------------------

LIKE_PATTERNS = ["ban%", "%ana", "%an%", "b_n%", "banana", "b_nd_na",
                 "%na", "nab", "%a%", "c%", "_an%"]


@pytest.mark.parametrize("pattern", LIKE_PATTERNS)
def test_like_count_oracle(db, pattern):
    cl = QueryClient(db, key=7)
    want = sum(_like_oracle(w, pattern) for w in WORDS)
    assert cl.run(Count(Like(0, pattern))).count == want


@pytest.mark.parametrize("pred,oracle", [
    (Prefix(0, "ba"), lambda w: w.startswith("ba")),
    (Suffix(0, "ana"), lambda w: w.endswith("ana")),
    (Contains(0, "an"), lambda w: "an" in w),
    (Contains(0, "ana"), lambda w: "ana" in w),   # overlapping windows
])
def test_predicate_class_count_oracle(db, pred, oracle):
    cl = QueryClient(db, key=7)
    assert cl.run(Count(pred)).count == sum(oracle(w) for w in WORDS)


@pytest.mark.parametrize("strategy", ["one_round", "tree", AUTO])
@pytest.mark.parametrize("pattern", ["%an%", "%na", "b_n%", "ca%"])
def test_pattern_select_rows_oracle(db, strategy, pattern):
    cl = QueryClient(db, key=3)
    ell = sum(_like_oracle(w, pattern) for w in WORDS)
    res = cl.run(Select(Like(0, pattern), strategy=strategy,
                        expected_matches=ell))
    got = sorted(row[0] for row in res.rows)
    assert got == sorted(w for w in WORDS if _like_oracle(w, pattern))
    assert res.strategy in ("one_round", "tree")
    assert res.count == ell


def test_like_convenience(db):
    cl = QueryClient(db, key=1)
    assert cl.like(0, "%an%", count_only=True).count == \
        sum("an" in w for w in WORDS)
    rows = cl.like(0, "ban%").rows
    assert sorted(r[0] for r in rows) == ["ban", "banana", "bandana"]


# ---------------------------------------------------------------------------
# wildcard-free LIKE lowers to the exact Eq path — provably
# ---------------------------------------------------------------------------

def test_wildcard_free_like_lowers_to_eq(db):
    col, body, spec = _lower_match(db, Like(0, "banana"), "t")
    assert spec is None and body == "banana" and col == 0
    # planner: the pattern estimate degenerates field-for-field to Eq's
    stats = DBStats.of(db)
    assert estimate_pattern_cost(stats, None) == estimate_count_cost(stats)
    for strat in ("one_round", "tree"):
        assert estimate_pattern_cost(stats, None, select=strat, ell=3) == \
            estimate_select_cost(strat, stats, ell=3)
    # transcript: Count(Like) == Count(Eq) bit for bit under the same key
    a = QueryClient(db, key=5).run(Count(Like(0, "banana")))
    b = QueryClient(db, key=5).run(Count(Eq(0, "banana")))
    assert a.count == b.count == 1
    assert a.ledger == b.ledger
    # and the §3.2.1 single-tuple special case stays eligible
    res = QueryClient(db, key=5).run(
        Select(Like(0, "banana"), strategy="one_tuple",
               expected_matches=1))
    assert res.strategy == "one_tuple" and res.rows[0][0] == "banana"


# ---------------------------------------------------------------------------
# B=16 mixed batch == sequential (rows + ledgers), shard/dispatcher parity
# ---------------------------------------------------------------------------

def _mixed_plans():
    return [
        Count(Eq(0, "banana")), Count(Like(0, "%an%")),
        Count(Prefix(0, "ba")),
        Select(Eq(1, "x"), strategy="one_round"),
        Select(Like(0, "ban%"), strategy="one_round"),
        Select(Suffix(0, "na"), strategy="tree",
               expected_matches=sum(w.endswith("na") for w in WORDS)),
        Select(Contains(0, "ab"), strategy="one_round"),
        RangeCount(Between(2, 2, 6)),
        Select(Eq(1, "z"), strategy="tree", expected_matches=2),
        Count(Suffix(0, "b")), Count(Contains(0, "ban")),
        Select(Like(0, "c%"), strategy=AUTO),
        Select(Like(0, "b_n%"), strategy="one_round"),
        Count(Like(0, "an")),
        Select(Prefix(0, "an"), strategy="one_round"),
        Count(Eq(1, "y")),
    ]


def _assert_equal(a, b, ctx):
    assert a.rows == b.rows, ctx
    assert a.count == b.count, ctx
    assert a.strategy == b.strategy, ctx
    assert a.ledger == b.ledger, ctx


def test_mixed_batch_equals_sequential(db):
    plans = _mixed_plans()
    assert len(plans) == 16
    batched = QueryClient(db, key=3).run_batch(plans)
    seq_cl = QueryClient(db, key=3)
    seq = [seq_cl.run(p) for p in plans]
    for i, (b, s) in enumerate(zip(batched, seq)):
        _assert_equal(b, s, i)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("dispatcher", ["serial", "threaded", "mesh"])
def test_shard_dispatcher_bit_identity(db, shards, dispatcher):
    plans = _mixed_plans()
    ref = QueryClient(db, key=5).run_batch(plans)
    cl = QueryClient(db, key=5)
    disp = {"serial": lambda: None,
            "threaded": lambda: ThreadedDispatcher(max_workers=shards),
            "mesh": lambda: MeshDispatcher(make_host_mesh())}[dispatcher]()
    cl.attach(shards=shards, dispatcher=disp)
    got = cl.run_batch(plans)
    for i, (a, b) in enumerate(zip(ref, got)):
        _assert_equal(a, b, (dispatcher, shards, i))


# ---------------------------------------------------------------------------
# explain() exactness for the pattern family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    Count(Contains(0, "an")), Count(Suffix(0, "ana")),
    Count(Like(0, "b_n%")), Count(Prefix(0, "c")),
])
def test_explain_exact_pattern_count(db, plan):
    cl = QueryClient(db, key=11)
    exp = cl.explain(plan)
    res = cl.run(plan)
    assert exp.bits == res.ledger.communication_bits
    assert exp.rounds == res.ledger.rounds


@pytest.mark.parametrize("pred,source", [
    (Suffix(0, "na"), "%na"), (Contains(0, "an"), "%an%"),
    (Like(0, "b_n%"), "b_n%")])
def test_explain_exact_pattern_one_round_select(db, pred, source):
    ell = sum(_like_oracle(w, source) for w in WORDS)
    plan = Select(pred, strategy="one_round", expected_matches=ell)
    cl = QueryClient(db, key=11)
    exp = cl.explain([plan])
    res = cl.run(plan)
    assert exp.bits == res.ledger.communication_bits
    assert exp.rounds == res.ledger.rounds


def test_explain_exact_mixed_count_one_round_batch(db):
    plans = [Count(Like(0, "%an%")), Count(Eq(0, "ban")),
             Select(Suffix(0, "na"), strategy="one_round",
                    expected_matches=sum(w.endswith("na") for w in WORDS)),
             Select(Eq(1, "x"), strategy="one_round", expected_matches=3)]
    cl = QueryClient(db, key=13)
    exp = cl.explain(plans)
    outs = cl.run_batch(plans)
    assert exp.bits == sum(o.ledger.communication_bits for o in outs)
    assert exp.rounds == max(o.ledger.rounds for o in outs)


# ---------------------------------------------------------------------------
# typed rejection: unknown predicates, malformed patterns, one_tuple
# ---------------------------------------------------------------------------

class _UnknownPredicate:
    column = 0
    pattern = "x"       # duck-typed fields must NOT be enough


@pytest.mark.parametrize("plan", [
    Count(Between(2, 1, 3)),                       # wrong predicate family
    Select(Between(2, 1, 3)),
    Count(_UnknownPredicate()),
    Select(_UnknownPredicate()),
    Count(Like(0, "a%b%")),                        # interior %
    Count(Like(0, "%a_b")),                        # _ under a shifted window
    Count(Like(0, "%%")),                          # empty body
    Count(Suffix(0, "waytoolongword")),            # tile longer than W
    Select(Like(0, "ban%"), strategy="one_tuple"),  # pattern one_tuple
])
def test_plan_not_supported(db, plan):
    cl = QueryClient(db, key=1)
    with pytest.raises(PlanNotSupported):
        cl.run(plan)
    with pytest.raises(PlanNotSupported):
        cl.explain([plan] if not isinstance(plan, Select) else plan)


def test_plan_not_supported_is_typed(db):
    cl = QueryClient(db, key=1)
    with pytest.raises(TypeError):                 # subclass contract
        cl.run(Count(Between(2, 1, 3)))
    try:
        cl.run(Count(Like(0, "a%b%")))
    except PlanNotSupported as e:
        assert "Like" in str(e)


# ---------------------------------------------------------------------------
# join match matrix: chain vs aggregate parity + planner pricing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_join_match_method_parity(db, right_db, backend):
    """The round engine's chain and aggregate evaluations open the same
    match matrix at the same degree, and join to the same rows and
    ledger; the client's join (the planner's choice) equals both."""
    be = get_backend(backend)
    key = jax.random.fold_in(jax.random.PRNGKey(13), 0)  # client's 1st key
    outs = {}
    for mm in ("chain", "aggregate"):
        job = rounds.JoinJob(right_db, 0, 0, key, CostLedger(),
                             match_method=mm)
        (entry,) = rounds.join_match_round(be, db, [job])
        (opened,) = rounds._open_on_host(None, [Shares(entry.values,
                                                       entry.degree)])
        _, fetched = rounds.fetch_fusion(be, db, [], [entry])
        (rows,) = rounds.join_emit_round(db, [job], fetched)
        outs[mm] = (entry.degree, opened, rows, job.ledger)
    (dc, mc, rc, lc), (da, ma, ra, la) = outs["chain"], outs["aggregate"]
    assert dc == da
    np.testing.assert_array_equal(mc, ma)
    assert (rc, lc) == (ra, la)
    assert [r[0] for r in rc] == ["banana", "cab"]
    res = QueryClient(db, key=13, backend=backend).run(
        Join(right=right_db, on=(0, 0)))
    assert (res.rows, res.ledger) == (rc, lc)


def test_choose_match_method_pricing(db):
    stats = DBStats.of(db)
    # W=8 chain launches vs 2 aggregate launches: the planner takes
    # aggregate; at W = 2 the chain ties and is kept
    assert estimate_match_method_launches(stats, "chain") == 8
    assert estimate_match_method_launches(stats, "aggregate") == 2
    assert choose_match_method(stats) == "aggregate"
    assert choose_match_method(DBStats(n=8, m=3, c=20, w=2,
                                       a=stats.a)) == "chain"
    with pytest.raises(ValueError):
        estimate_match_method_launches(stats, "bogus")


# ---------------------------------------------------------------------------
# backend parity: the pallas slide kernel end to end
# ---------------------------------------------------------------------------

def test_pattern_backend_parity(db):
    plans = [Count(Contains(0, "an")), Count(Suffix(0, "ana")),
             Select(Like(0, "%an%"), strategy="one_round")]
    a = QueryClient(db, key=17, backend="jnp").run_batch(plans)
    b = QueryClient(db, key=17, backend="pallas").run_batch(plans)
    for i, (x, y) in enumerate(zip(a, b)):
        _assert_equal(x, y, i)
