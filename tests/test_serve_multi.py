"""Multi-tenant QueryServer: cross-relation routing through one scheduler.

The acceptance property of the multi-tenant frontend: a mixed workload
submitted to ONE server over several attached relations (different shard
counts, different batching policies) returns rows and ``CostLedger``s
bit-identical to running each relation on its own single-relation server —
relations batch independently, key streams are per relation, and the
shared shard pool is pure execution policy. ``ServeStats`` exposes the
per-relation breakdown, and faults stay isolated per request AND per
relation.
"""
import threading
import time

import jax
import pytest

from repro.api import Between, Count, Eq, RangeCount, Select
from repro.core import Codec, outsource
from repro.core.queries import CardinalityError
from repro.launch.serve import QueryRequest, QueryServer

CODEC = Codec(word_length=8)
EMP_COLUMNS = ["EmployeeId", "FirstName", "LastName", "Salary",
               "Department"]
EMPLOYEE = [
    ["E101", "Adam", "Smith", "1000", "Sale"],
    ["E102", "John", "Taylor", "2000", "Design"],
    ["E103", "Eve", "Smith", "500", "Sale"],
    ["E104", "John", "Williams", "5000", "Sale"],
]
ORD_COLUMNS = ["OrderId", "Customer", "Status"]
ORDERS = [
    ["O1", "acme", "open"],
    ["O2", "zeta", "open"],
    ["O3", "acme", "done"],
    ["O4", "gamma", "open"],
    ["O5", "acme", "done"],
    ["O6", "zeta", "done"],
]


@pytest.fixture(scope="module")
def employee_db():
    return outsource(jax.random.PRNGKey(7), EMPLOYEE,
                     column_names=EMP_COLUMNS, codec=CODEC, n_shares=20,
                     degree=1, numeric_columns={3: 14})


@pytest.fixture(scope="module")
def orders_db():
    return outsource(jax.random.PRNGKey(8), ORDERS,
                     column_names=ORD_COLUMNS, codec=CODEC, n_shares=20,
                     degree=1)


EMP_PLANS = [Count(Eq("FirstName", "John")),
             Select(Eq("Department", "Sale"), strategy="tree"),
             RangeCount(Between("Salary", 600, 4000)),
             Count(Eq("LastName", "Smith"))]
ORD_PLANS = [Count(Eq("Customer", "acme")),
             Select(Eq("Status", "open"), strategy="one_round"),
             Count(Eq("Status", "done")),
             Select(Eq("Customer", "zeta"), strategy="tree"),
             Count(Eq("Customer", "gamma"))]


def _results_equal(a, b):
    assert a.count == b.count
    assert a.rows == b.rows
    assert a.addresses == b.addresses
    assert a.ledger == b.ledger
    assert a.strategy == b.strategy


def _solo_results(db, key, plans, shards):
    server = QueryServer(db, key=key, shards=shards)
    reqs = server.serve([QueryRequest(p) for p in plans])
    server.close()
    assert all(r.error is None for r in reqs)
    return [r.result for r in reqs]


def test_mixed_workload_matches_solo_servers(employee_db, orders_db):
    """THE acceptance test: two relations, different shard counts, served
    interleaved by one scheduler == each served alone (rows, ledgers)."""
    solo_emp = _solo_results(employee_db, 11, EMP_PLANS, shards=2)
    solo_ord = _solo_results(orders_db, 13, ORD_PLANS, shards=3)

    server = QueryServer(pool_workers=4)
    server.attach("employees", employee_db, shards=2, key=11)
    server.attach("orders", orders_db, shards=3, key=13)
    assert server.relations == ("employees", "orders")
    assert server.dataplane_of("employees").n_shards == 2
    assert server.dataplane_of("orders").n_shards == 3

    # interleave the two relations' traffic through one scheduler thread
    with server:
        emp_reqs = []
        ord_reqs = []
        for i in range(max(len(EMP_PLANS), len(ORD_PLANS))):
            if i < len(EMP_PLANS):
                emp_reqs.append(
                    server.submit(EMP_PLANS[i], relation="employees"))
            if i < len(ORD_PLANS):
                ord_reqs.append(
                    server.submit(ORD_PLANS[i], relation="orders"))
        for r in emp_reqs + ord_reqs:
            r.wait(timeout=60)

    for solo, req in zip(solo_emp, emp_reqs):
        _results_equal(solo, req.result)
    for solo, req in zip(solo_ord, ord_reqs):
        _results_equal(solo, req.result)

    # per-relation breakdown is exposed and adds up
    snap = server.stats.snapshot()
    emp, ords = snap["relations"]["employees"], snap["relations"]["orders"]
    assert emp["served"] == len(EMP_PLANS)
    assert ords["served"] == len(ORD_PLANS)
    assert server.stats.served == len(EMP_PLANS) + len(ORD_PLANS)
    assert emp["served_by_family"]["count"] == 2
    assert emp["served_by_family"]["range_count"] == 1
    assert ords["served_by_family"]["select"] == 2
    assert sum(emp["batch_fill"].values()) == emp["batches"]
    # one shared pool backs both dataplanes, via separate handles
    assert server._owned_dispatcher is not None
    ha = server.dataplane_of("employees").dispatcher
    hb = server.dataplane_of("orders").dispatcher
    assert ha is not hb
    assert ha._shared_pool is hb._shared_pool is server._owned_dispatcher


def test_tenant_results_independent_of_neighbour_traffic(employee_db,
                                                         orders_db):
    """A relation's transcript never depends on what (or whether) other
    tenants submit: per-relation key streams."""
    alone = QueryServer()
    alone.attach("employees", employee_db, key=5)
    only = alone.serve([QueryRequest(p, relation="employees")
                        for p in EMP_PLANS])

    noisy = QueryServer()
    noisy.attach("employees", employee_db, key=5)
    noisy.attach("orders", orders_db, key=6)
    mixed = []
    for i, p in enumerate(EMP_PLANS):
        mixed.append(noisy.submit(p, relation="employees"))
        noisy.submit(ORD_PLANS[i % len(ORD_PLANS)], relation="orders")
    while noisy.pending():
        noisy.pump()
    for a, b in zip(only, mixed):
        _results_equal(a.result, b.result)


def test_per_relation_batching_policy(employee_db, orders_db):
    """Per-relation max_batch/max_wait_ms overrides shape THAT relation's
    batches only; batches never mix relations."""
    server = QueryServer(max_batch=16, max_wait_ms=10_000)
    server.attach("employees", employee_db, key=1, max_batch=2)
    server.attach("orders", orders_db, key=2, max_batch=4,
                  max_wait_ms=5.0)
    with server:
        emp = [server.submit(Count(Eq("FirstName", "John")),
                             relation="employees") for _ in range(4)]
        ords = [server.submit(Count(Eq("Customer", "acme")),
                              relation="orders") for _ in range(4)]
        for r in emp + ords:
            r.wait(timeout=60)
    snap = server.stats.snapshot()
    emp_s, ord_s = snap["relations"]["employees"], \
        snap["relations"]["orders"]
    # employees: max_batch=2 -> fills of exactly 2, closed by fill
    assert emp_s["batch_fill"].get(2, 0) >= 2
    assert emp_s["closes"].get("full", 0) >= 2
    assert max(emp_s["batch_fill"]) <= 2
    # orders: fills of <= 4, and every one of its requests served
    assert ord_s["served"] == 4
    assert max(ord_s["batch_fill"]) <= 4
    assert all(r.result.count == 2 for r in emp)
    assert all(r.result.count == 3 for r in ords)


def test_fault_isolation_across_relations(employee_db, orders_db):
    """A poisoned plan on one relation fails alone — batch-mates AND the
    other relation's concurrent batch are unaffected."""
    server = QueryServer(max_wait_ms=15)
    server.attach("employees", employee_db, key=3)
    server.attach("orders", orders_db, key=4)
    with server:
        bad = server.submit(                    # ℓ=2 -> CardinalityError
            Select(Eq("FirstName", "John"), strategy="one_tuple"),
            relation="employees")
        good_emp = [server.submit(Count(Eq("FirstName", "John")),
                                  relation="employees") for _ in range(3)]
        good_ord = [server.submit(Count(Eq("Customer", "acme")),
                                  relation="orders") for _ in range(3)]
        for r in [bad] + good_emp + good_ord:
            r.wait(timeout=60)
    assert isinstance(bad.error, CardinalityError)
    assert all(r.error is None and r.result.count == 2 for r in good_emp)
    assert all(r.error is None and r.result.count == 3 for r in good_ord)
    snap = server.stats.snapshot()
    assert snap["relations"]["employees"]["failed"] == 1
    assert snap["relations"]["orders"]["failed"] == 0
    assert server.stats.failed == 1


def test_routing_validation_and_default_relation(employee_db, orders_db):
    server = QueryServer(employee_db, key=9)      # default tenant
    server.attach("orders", orders_db, key=10)
    # unknown relation: loud, listing what IS attached
    with pytest.raises(KeyError, match="unknown relation"):
        server.submit(Count(Eq("Customer", "acme")), relation="nope")
    # no relation: routed to the default tenant
    r_def = server.submit(Count(Eq("FirstName", "Eve")))
    r_ord = server.submit(Count(Eq("Customer", "zeta")),
                          relation="orders")
    while server.pending():
        server.pump()
    assert r_def.relation == "default" and r_def.result.count == 1
    assert r_ord.relation == "orders" and r_ord.result.count == 2
    # an empty server refuses submissions with a clear error
    empty = QueryServer()
    with pytest.raises(ValueError, match="no relation attached"):
        empty.submit(Count(Eq("FirstName", "Eve")))
    # shards=/dispatcher= are per-relation: without a db they would be
    # silently dropped, so the constructor refuses them
    with pytest.raises(ValueError, match="per-relation"):
        QueryServer(shards=4)


def test_derived_key_streams_order_independent_and_collision_loud(
        employee_db, orders_db, monkeypatch):
    """Tenants attached without explicit keys derive their stream from
    the name ALONE (order-independent replay); a derived-stream collision
    — astronomically unlikely, here forced — is refused loudly, never
    silently shared (the protocol's masking randomness must stay
    independent across relations)."""
    from repro.api import QueryClient
    fwd = QueryClient(key=7)
    fwd.attach(employee_db, name="a")
    fwd.attach(orders_db, name="b")
    rev = QueryClient(key=7)
    rev.attach(orders_db, name="b")              # other order, same streams
    rev.attach(employee_db, name="a")
    for name in ("a", "b"):
        assert bool((fwd._relations[name].root_key
                     == rev._relations[name].root_key).all())
    assert not bool((fwd._relations["a"].root_key
                     == fwd._relations["b"].root_key).all())
    # force both 31-bit folds to collide for every name
    import repro.api.client as client_mod
    monkeypatch.setattr(client_mod.zlib, "crc32", lambda data: 123)
    clash = QueryClient(key=7)
    clash.attach(employee_db, name="a")
    with pytest.raises(ValueError, match="collides"):
        clash.attach(orders_db, name="b")
    # an explicit key= sidesteps the derivation entirely
    clash.attach(orders_db, name="b", key=99)


def test_concurrent_submitters_two_relations_stats_monotone(employee_db,
                                                            orders_db):
    """Soak across relations: racing submitters on both tenants; served
    counts stay monotone, every request finishes exactly once, and the
    per-relation slices add up to the aggregate."""
    server = QueryServer(max_batch=4, max_wait_ms=5, pool_workers=4)
    server.attach("employees", employee_db, key=21, shards=2)
    server.attach("orders", orders_db, key=22, shards=3)
    server.start()
    per_thread, reqs, lock = 5, [], threading.Lock()

    def submitter(tid):
        for i in range(per_thread):
            if (tid + i) % 2 == 0:
                r = server.submit(Count(Eq("FirstName", "John")),
                                  relation="employees")
            else:
                r = server.submit(Count(Eq("Customer", "acme")),
                                  relation="orders")
            with lock:
                reqs.append(r)
            time.sleep(0.002)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(4)]
    observed = []
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        snap = server.stats.snapshot()          # torn-read regression
        observed.append((snap["served"],
                         snap["relations"].get("employees",
                                               {}).get("served", 0)))
        time.sleep(0.002)
    for t in threads:
        t.join()
    for r in reqs:
        r.wait(timeout=60)
    server.close()

    assert len(reqs) == 4 * per_thread
    assert server.stats.served == len(reqs) and server.stats.failed == 0
    for r in reqs:
        want = 2 if r.relation == "employees" else 3
        assert r.result.count == want
    assert all(a[0] <= b[0] and a[1] <= b[1]
               for a, b in zip(observed, observed[1:]))
    snap = server.stats.snapshot()
    assert (snap["relations"]["employees"]["served"]
            + snap["relations"]["orders"]["served"]) == len(reqs)
    assert (snap["relations"]["employees"]["batches"]
            + snap["relations"]["orders"]["batches"]) == snap["batches"]
