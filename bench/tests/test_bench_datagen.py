"""The seeded generators: the same seed gives the same data, and LINEITEM
keeps the value domains of TPC-H section 4.2.3."""
import datetime

import numpy as np
import pytest

import datagen
import traffic

COLUMNS = ["l_orderkey", "l_returnflag", "l_linestatus", "l_shipmode",
           "l_shipdate", "l_quantity", "l_discount"]
BIG_SEED = 2**31 + 12345


def test_lineitem_is_deterministic_in_the_seed():
    a = datagen.lineitem_rows(512, BIG_SEED, COLUMNS)
    b = datagen.lineitem_rows(512, BIG_SEED, COLUMNS)
    c = datagen.lineitem_rows(512, BIG_SEED + 1, COLUMNS)
    assert a == b
    assert a != c
    assert all(isinstance(v, str) for r in a for v in r)


def test_lineitem_domains_follow_the_specification():
    rows = datagen.lineitem_rows(4096, 7, COLUMNS)
    assert len(rows) == 4096
    col = {c: [r[i] for r in rows] for i, c in enumerate(COLUMNS)}
    keys = [int(k) for k in col["l_orderkey"]]
    assert keys == sorted(keys)
    # sparse keys: of every 32 only the first 8 are used
    assert all(k % 32 < 8 for k in keys)
    lines_per_order = np.unique(keys, return_counts=True)[1]
    assert lines_per_order.max() <= 7 and lines_per_order.min() >= 1
    assert set(lines_per_order[:-1]) == set(range(1, 8))
    assert set(col["l_shipmode"]) == set(datagen.SHIP_MODES)
    assert all(len(m) <= 7 for m in col["l_shipmode"])
    qty = [int(q) for q in col["l_quantity"]]
    assert min(qty) == 1 and max(qty) == 50
    disc = [int(d) for d in col["l_discount"]]
    assert min(disc) == 0 and max(disc) == 10
    ship = [datetime.date.fromisoformat(d) for d in col["l_shipdate"]]
    first = datagen.START_DATE + datetime.timedelta(days=1)
    last = (datagen.START_DATE + datetime.timedelta(
        days=datagen.ORDERDATE_SPAN + 121))
    assert min(ship) >= first and max(ship) <= last
    assert all(len(d) == 10 for d in col["l_shipdate"])
    for s, flag, status in zip(ship, col["l_returnflag"],
                               col["l_linestatus"]):
        assert status == ("O" if s > datagen.CURRENT_DATE else "F")
        if s > datagen.CURRENT_DATE:     # receipt is after ship
            assert flag == "N"
        assert flag in "RAN"


def test_sparse_orderkey_matches_dbgen():
    assert list(datagen.sparse_orderkey(np.arange(1, 10))) == \
        [1, 2, 3, 4, 5, 6, 7, 32, 33]


def test_token_streams_are_deterministic_and_in_range():
    mix = traffic.load("embed.decode")
    config = {"vocab_size": 1000}

    def first(seed, n=5):
        streams = traffic.client_streams(mix, seed, None, config)
        return [[next(s)["tokens"] for _ in range(n)] for s in streams]
    a, b = first(BIG_SEED), first(BIG_SEED)
    assert a == b
    assert a != first(BIG_SEED + 1)
    assert len(a) == mix["clients"]
    toks = np.asarray(a).ravel()
    assert toks.min() >= 0 and toks.max() < 1000
    assert all(len(t) == 64 for client in a for t in client)


def test_lineitem_streams_draw_the_mix():
    mix = traffic.load("lineitem.match")
    rows = datagen.lineitem_rows(256, 3, COLUMNS)
    keys = sorted({r[0] for r in rows})

    def values(name):
        assert name == "l_orderkey"
        return keys
    streams = traffic.client_streams(mix, 11, values, {})
    reqs = [next(streams[0]) for _ in range(400)]
    kinds = [(r["plan"], r["where"]["op"], r["where"].get("pattern", "")[:1])
             for r in reqs]
    share = {k: kinds.count(k) / len(kinds) for k in set(kinds)}
    assert share[("select", "eq", "")] == pytest.approx(0.4, abs=0.08)
    assert share[("count", "eq", "")] == pytest.approx(0.3, abs=0.08)
    assert share[("count", "like", "1")] == pytest.approx(0.2, abs=0.08)
    assert share[("count", "like", "%")] == pytest.approx(0.1, abs=0.06)
    assert all(r["where"]["value"] in keys for r in reqs
               if r["plan"] == "select")
    assert all(r.get("padding_rows") == 7 for r in reqs
               if r["plan"] == "select")


def test_blocks_hold_the_same_work_for_every_seed():
    mix = traffic.load("lineitem.match")
    block = mix["block"]
    want = sorted(f for m in mix["mix"]
                  for f in round(m["weight"] * block) * [m["request"]["family"]])

    def batches(seed):
        streams = traffic.client_streams(mix, seed, lambda name: ["1"], {})
        per_client = [[next(s)["family"] for _ in range(3 * block)]
                      for s in streams]
        for client in per_client:
            for i in range(0, len(client), block):
                assert sorted(client[i:i + block]) == want
        # the i-th requests of all clients meet in one batch
        return [tuple(c[i] for c in per_client) for i in range(3 * block)]
    a, b = batches(BIG_SEED), batches(BIG_SEED + 1)
    assert a != b
    for i in range(0, 3 * block, block):
        assert sorted(a[i:i + block]) == sorted(b[i:i + block])
