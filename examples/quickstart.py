"""Quickstart: the paper's Employee example through the unified QueryClient.

A trusted DB owner outsources a relation as Shamir secret-shares to c
simulated clouds; an (authorized) user then holds ONE QueryClient over the
shares and runs oblivious count, selection, join and range queries WITHOUT
the owner being online, and without any cloud learning the data, the query,
or the result. Queries are logical plans (columns by name, predicate
objects, explicit padding policy); per-query keys derive from the client's
root key; the cost-based planner picks the paper-optimal selection strategy.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.api import Aggregate, Count, Eq, Like, Padding, QueryClient, \
    Select
from repro.core import outsource, Codec

EMPLOYEE = [
    ["E101", "Adam", "Smith", "1000", "Sale"],
    ["E102", "John", "Taylor", "2000", "Design"],
    ["E103", "Eve", "Smith", "500", "Sale"],
    ["E104", "John", "Williams", "5000", "Sale"],
]


def main():
    codec = Codec(word_length=8)
    print("== DB owner: create & distribute secret-shares (one-time) ==")
    db = outsource(jax.random.PRNGKey(7), EMPLOYEE,
                   column_names=["EmployeeId", "FirstName", "LastName",
                                 "Salary", "Department"],
                   codec=codec, n_shares=20, degree=1,
                   numeric_columns={3: 14})
    print(f"  {db.n_tuples} tuples x {db.n_attrs} attrs -> "
          f"{db.n_shares} clouds; every value shared with an independent "
          f"degree-{db.base_degree} polynomial\n")

    # one cloud's view of the two 'John's — different shares (no frequency
    # attack possible)
    v0 = np.asarray(db.relation.values[0, 1, 1, 0])  # John #1, first letter
    v1 = np.asarray(db.relation.values[0, 3, 1, 0])  # John #2, first letter
    print(f"  cloud 0's share of 'J' in tuple 2: {v0[:4]}...")
    print(f"  cloud 0's share of 'J' in tuple 4: {v1[:4]}...  (different!)\n")

    print("== User: one QueryClient, per-query keys derived automatically ==")
    client = QueryClient(db, key=42, backend="jnp")

    print("== COUNT (§3.1): how many employees named John? ==")
    res = client.count("FirstName", "John")
    print(f"  -> {res.count}   [{res.ledger}]\n")

    print("== SELECT (§3.2): WHERE FirstName='John', planner-chosen ==")
    plan = Select(Eq("FirstName", "John"))
    for est in client.explain(plan):
        print(f"  planner: {est.strategy:<10} ~{est.bits} bits, "
              f"{est.rounds} rounds")
    res = client.run(plan)
    print(f"  -> chose {res.strategy!r}; addresses {res.addresses}; "
          f"rows: {res.rows}  [rounds={res.ledger.rounds}]\n")

    print("== SELECT forced strategies (§3.2.1 / §3.2.2) ==")
    res = client.select("FirstName", "Eve", strategy="one_tuple")
    print(f"  one_tuple  -> {res.rows[0]}")
    res = client.select("Department", "Sale", strategy="tree")
    print(f"  tree       -> {res.count} rows in {res.ledger.rounds} "
          f"Q&A rounds")
    # fake-row padding hides the true result size from the clouds
    res = client.select("FirstName", "John", strategy="one_round",
                        padding=Padding.to_rows(4))
    print(f"  one_round  -> {len(res.rows)} real rows behind a 4-row "
          f"padded fetch\n")

    print("== PATTERN (LIKE): wildcard predicates on shares ==")
    # LIKE lowers to the accumulating-automata pattern engine: a prefix
    # pattern chains only its k leading positions (cheaper than exact
    # match), a substring slides the tile over every window. The clouds
    # never see the pattern — it ships as secret-shared one-hot tiles.
    res = client.run(Count(Like("FirstName", "Jo%")))
    print(f"  COUNT(FirstName LIKE 'Jo%')        -> {res.count}")
    res = client.run(Select(Like("LastName", "%ith%")))
    print(f"  SELECT WHERE LastName LIKE '%ith%' -> "
          f"{[r[1] + ' ' + r[2] for r in res.rows]}  "
          f"[rounds={res.ledger.rounds}]\n")

    print("== RANGE (§3.4): Salary in [1000, 2000] ==")
    # 14-bit SS-SUB grows the polynomial degree past our 20 clouds -> the
    # planner inserts the paper's degree-reduction (re-sharing) round
    cnt = client.range_count("Salary", 1000, 2000)
    sel = client.range_select("Salary", 1000, 2000)
    print(f"  -> count {cnt.count}; rows {[r[0] for r in sel.rows]}\n")

    print("== AGGREGATE: verified AVG(Salary) WHERE FirstName='John' ==")
    # verify=True buys an OBSCURE-style check: each cloud returns extra
    # redundant shares of the opened sum and the client cross-checks them
    # against the interpolating polynomial — a tampered share raises
    # VerificationError instead of a silently wrong average. explain()
    # prices the overhead (one extra round + c checksum elements) before
    # any share moves.
    plan = Aggregate("avg", "Salary", where=Eq("FirstName", "John"),
                     verify=True)
    est = client.explain([plan]).groups[0].estimate
    print(f"  planner: ~{est.bits} bits, {est.rounds} rounds "
          f"(verification included)")
    res = client.run(plan)
    print(f"  -> AVG = {res.value} over {res.count} matching rows, "
          f"verified  [rounds={res.ledger.rounds}]")
    lo = client.run(Aggregate("min", "Salary"))
    print(f"  -> MIN(Salary) = {lo.value} via the ripple-comparator "
          f"tournament\n")

    print("== PK/FK JOIN (§3.3.1): X(A,B) |x| Y(B,C) ==")
    codec6 = Codec(word_length=6)
    X = [["a1", "b1"], ["a2", "b2"], ["a3", "b3"]]
    Y = [["b1", "c1"], ["b2", "c2"], ["b2", "c3"], ["b2", "c4"]]
    dbX = outsource(jax.random.PRNGKey(8), X, column_names=["A", "B"],
                    codec=codec6, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(9), Y, column_names=["B", "C"],
                    codec=codec6, n_shares=16)
    res = QueryClient(dbX, key=3).join(dbY, on=("B", "B"))
    print(f"  -> {res.rows}")
    print("\nAll queries executed obliviously on shares; the clouds saw "
          "only uniform field elements.")


if __name__ == "__main__":
    main()
