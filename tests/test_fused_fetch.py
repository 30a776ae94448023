"""Cross-relation fetch fusion and shard-aligned tree gathers.

Anchor properties of the fused dataplane path:

* **Transcript identity** — ``QueryClient.run_batch_multi`` over several
  relations returns rows, counts, addresses AND per-query ``CostLedger``s
  bit-identical to back-to-back solo ``run_batch`` calls, for
  S ∈ {1, 2, 4} and across Serial / Threaded(shared pool) / Mesh
  placement. Fusion co-schedules the per-relation fetch ``ss_matmul``
  dispatches as one wave; it never mixes batches, keys, rounds or
  ledgers.
* **Shard-aligned tree Q&A** — ``Select(strategy="tree")`` executes its
  block gathers per shard (each gather stays inside one shard's tuple
  range) while the PUBLIC block partition — and therefore the priced and
  measured ledger — never moves with S.
* **Pricing** — ``QueryClient.explain_multi`` equals the measured fused
  ledgers exactly when the cardinality hints are exact, and prices ONE
  shared dispatch wave for the fused fetch.
"""
import jax
import pytest

from repro.api import (Count, Eq, MeshDispatcher, QueryClient, RangeCount,
                       Select, Between)
from repro.core import Codec, outsource
from repro.core.dataplane import ThreadedDispatcher
from repro.launch.mesh import make_host_mesh

CODEC = Codec(word_length=6)


@pytest.fixture(scope="module")
def alpha_db():
    rows = [[f"id{i}", f"nm{i % 5}", str(500 + 137 * i)] for i in range(16)]
    db = outsource(jax.random.PRNGKey(31), rows,
                   column_names=["Id", "Name", "Val"], codec=CODEC,
                   n_shares=20, degree=1, numeric_columns={2: 14})
    return rows, db


@pytest.fixture(scope="module")
def beta_db():
    rows = [[f"o{i}", f"c{i % 3}", "open" if i % 2 else "done"]
            for i in range(12)]
    db = outsource(jax.random.PRNGKey(32), rows,
                   column_names=["OrderId", "Customer", "Status"],
                   codec=CODEC, n_shares=20, degree=1)
    return rows, db


ALPHA_PLANS = [Select(Eq("Name", "nm2"), strategy="one_round",
                      expected_matches=3),
               Count(Eq("Name", "nm1")),
               Select(Eq("Name", "nm3"), strategy="tree",
                      expected_matches=3),
               RangeCount(Between("Val", 600, 1500))]
BETA_PLANS = [Select(Eq("Status", "open"), strategy="one_round",
                     expected_matches=6),
              Select(Eq("Customer", "c1"), strategy="tree",
                     expected_matches=4),
              Count(Eq("Status", "done"))]


def _results_equal(a, b):
    assert a.strategy == b.strategy
    assert a.rows == b.rows
    assert a.addresses == b.addresses
    assert a.count == b.count
    assert a.ledger == b.ledger


def _solo(db, key, plans, shards, dispatcher=None):
    client = QueryClient(db, key=key)
    client.attach(shards=shards, dispatcher=dispatcher)
    return client.run_batch(plans)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_run_batch_multi_matches_solo_serial(alpha_db, beta_db, shards):
    """Fused multi-batch == back-to-back solo batches, serial plane."""
    _, db_a = alpha_db
    _, db_b = beta_db
    ref_a = _solo(db_a, 51, ALPHA_PLANS, shards)
    ref_b = _solo(db_b, 52, BETA_PLANS, shards)

    client = QueryClient()
    client.attach(db_a, name="alpha", shards=shards, key=51)
    client.attach(db_b, name="beta", shards=shards, key=52)
    got_a, got_b = client.run_batch_multi(
        [("alpha", ALPHA_PLANS), ("beta", BETA_PLANS)])
    for r, g in zip(ref_a, got_a):
        _results_equal(r, g)
    for r, g in zip(ref_b, got_b):
        _results_equal(r, g)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_run_batch_multi_fuses_on_shared_pool(alpha_db, beta_db, shards):
    """On a shared ThreadedDispatcher pool the cross-relation fetch runs
    as ONE fused wave (fused_steps ticks on both planes) and stays
    bit-identical; dispatch fan-out is unchanged (steps x shards)."""
    _, db_a = alpha_db
    _, db_b = beta_db
    ref_a = _solo(db_a, 51, ALPHA_PLANS, shards)
    ref_b = _solo(db_b, 52, BETA_PLANS, shards)

    pool = ThreadedDispatcher(max_workers=4)
    client = QueryClient()
    pa = client.attach(db_a, name="alpha", shards=shards, key=51,
                       dispatcher=pool.handle(weight=2.0))
    pb = client.attach(db_b, name="beta", shards=shards, key=52,
                       dispatcher=pool.handle(weight=1.0))
    got_a, got_b = client.run_batch_multi(
        [("alpha", ALPHA_PLANS), ("beta", BETA_PLANS)])
    pool.close()
    for r, g in zip(ref_a, got_a):
        _results_equal(r, g)
    for r, g in zip(ref_b, got_b):
        _results_equal(r, g)
    # both batches carry fetch traffic, so exactly one fused wave ran
    assert pa.stats.fused_steps == 1
    assert pb.stats.fused_steps == 1
    assert pa.stats.dispatches == pa.stats.steps * shards
    assert pb.stats.dispatches == pb.stats.steps * shards


@pytest.mark.parametrize("shards", [1, 2])
def test_run_batch_multi_mesh_parity(alpha_db, beta_db, shards):
    """A device-resident mesh plane joins a multi-batch without fusion
    (its transfer guards demand its own execution path) and still
    matches the solo transcript bit for bit."""
    _, db_a = alpha_db
    _, db_b = beta_db
    ref_a = _solo(db_a, 51, ALPHA_PLANS, shards)
    ref_b = _solo(db_b, 52, BETA_PLANS, shards)

    client = QueryClient()
    client.attach(db_a, name="alpha", shards=shards, key=51,
                  dispatcher=MeshDispatcher(make_host_mesh(),
                                            strict_transfers=True))
    client.attach(db_b, name="beta", shards=shards, key=52)
    got_a, got_b = client.run_batch_multi(
        [("alpha", ALPHA_PLANS), ("beta", BETA_PLANS)])
    for r, g in zip(ref_a, got_a):
        _results_equal(r, g)
    for r, g in zip(ref_b, got_b):
        _results_equal(r, g)


def test_run_batch_multi_single_and_empty_parts(alpha_db):
    """Degenerate shapes: a one-relation multi equals run_batch; an
    empty plan list contributes an empty result list."""
    _, db_a = alpha_db
    ref = _solo(db_a, 51, ALPHA_PLANS, 2)
    client = QueryClient()
    client.attach(db_a, name="alpha", shards=2, key=51)
    (got,) = client.run_batch_multi([("alpha", ALPHA_PLANS)])
    for r, g in zip(ref, got):
        _results_equal(r, g)
    got_a, got_empty = client.run_batch_multi(
        [("alpha", ALPHA_PLANS), ("alpha", [])])
    assert got_empty == []
    for r, g in zip(_solo(db_a, 51, ALPHA_PLANS, 2), got_a):
        _results_equal(r, g)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_tree_shard_aligned_bit_identity(alpha_db, shards):
    """The tree strategy's Q&A + address gathers execute per shard; the
    public block partition (and so the ledger) must not move with S."""
    _, db = alpha_db
    plan = [Select(Eq("Name", "nm3"), strategy="tree",
                   expected_matches=3)]
    base = _solo(db, 9, plan, 1)[0]
    sharded = _solo(db, 9, plan, shards)[0]
    _results_equal(base, sharded)
    pooled = _solo(db, 9, plan, shards,
                   dispatcher=ThreadedDispatcher(max_workers=shards))[0]
    _results_equal(base, pooled)


def test_explain_multi_exact_on_fused_path(alpha_db, beta_db):
    """With exact cardinality hints, explain_multi == the measured fused
    ledgers: bits sum exactly, dispatch fan-out is unchanged by fusion,
    >= 2 fetch-bearing parts price ONE shared wave, and rounds follow the
    co-scheduling semantics (max over parts — waves overlap, they don't
    serialize). The plan families here (one_round select / count) are the
    ones the planner prices exactly; tree openings depend on how matches
    cluster in blocks, which ``explain`` only bounds.
    """
    _, db_a = alpha_db
    _, db_b = beta_db
    plans_a = [Select(Eq("Name", "nm2"), strategy="one_round",
                      expected_matches=3),
               Count(Eq("Name", "nm1"))]
    plans_b = [Select(Eq("Status", "open"), strategy="one_round",
                      expected_matches=6),
               Count(Eq("Status", "done"))]
    pool = ThreadedDispatcher(max_workers=4)
    client = QueryClient()
    pa = client.attach(db_a, name="alpha", shards=2, key=51,
                       dispatcher=pool.handle())
    pb = client.attach(db_b, name="beta", shards=2, key=52,
                       dispatcher=pool.handle())
    exp = client.explain_multi([("alpha", plans_a), ("beta", plans_b)])
    got_a, got_b = client.run_batch_multi(
        [("alpha", plans_a), ("beta", plans_b)])
    pool.close()
    measured_bits = sum(r.ledger.communication_bits
                        for r in got_a + got_b)
    assert exp.bits == measured_bits
    assert exp.rounds == max(p.rounds for p in exp.parts)
    assert exp.bits == sum(p.bits for p in exp.parts)
    assert exp.fetch_parts == 2
    assert exp.fetch_waves == 1
    assert exp.dispatches == pa.stats.dispatches + pb.stats.dispatches
    assert len(exp.parts) == 2
