"""The roofline's operation and byte counts against hand counts."""
import pytest

import roofline

V5E = roofline.peaks_for("TPU v5 lite")


def test_contraction_counts():
    w = roofline.contraction(c=2, m=3, k=5, n=7)
    assert w.macs == 2 * 3 * 5 * 7
    assert w.bytes == 4 * 2 * (3 * 5 + 5 * 7 + 3 * 7)
    assert w.int8_ops == w.macs * 7 * 2


def test_embedding_lookup_least_time_by_hand():
    # 4 clouds, M = 256 one-hots over a 102,400 x 2,048 table
    w = roofline.contraction(4, 256, 102_400, 2_048)
    macs = 4 * 256 * 102_400 * 2_048
    assert w.macs == macs
    compute_s = macs * 7 * 2 / 393e12
    memory_s = 4 * 4 * (256 * 102_400 + 102_400 * 2_048 + 256 * 2_048) / 819e9
    assert compute_s == pytest.approx(7.652e-3, rel=1e-3)
    assert memory_s == pytest.approx(4.61e-3, rel=1e-2)
    assert roofline.least_seconds(w, V5E) == pytest.approx(compute_s)


def test_match_counts():
    # exact match: b predicates over one (c, n, W, A) column
    w = roofline.match(c=3, b=2, n=10, width=12, alphabet=69)
    assert w.macs == 3 * 2 * 10 * 12 * 69
    assert w.bytes == 4 * 3 * (10 * 12 * 69 + 2 * 12 * 69 + 2 * 10)
    # sliding window: k = 4 over W = 12 is 9 placements over all 12 rows
    s = roofline.match(c=3, b=1, n=10, width=4, alphabet=69, windows=9)
    assert s.macs == 3 * 10 * 9 * 4 * 69
    assert s.bytes == 4 * 3 * (10 * 12 * 69 + 4 * 69 + 10 * 9)


def test_work_adds_and_memory_bound_wins_when_it_is_larger():
    w = roofline.Work(macs=1, bytes=10**9) + roofline.Work(macs=1, bytes=0)
    assert w == roofline.Work(macs=2, bytes=10**9)
    assert roofline.least_seconds(w, V5E) == pytest.approx(1e9 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


def test_relation_batch_work_by_hand():
    import reference
    from systems import relation
    cfg = {"clouds": 3, "rows": 4, "word_length": 12, "alphabet_size": 69,
           "columns": ["k", "mode", "date"]}
    rows = [["1", "AIR", "1995-03-01"], ["1", "RAIL", "1996-03-02"],
            ["2", "AIR", "1995-04-01"], ["3", "SHIP", "1997-01-01"]]
    dep = relation.Deployment.__new__(relation.Deployment)
    dep.config, dep.reference = cfg, reference.Relation(rows, cfg["columns"])
    batch = [
        {"plan": "select", "padding_rows": 7,
         "where": {"op": "eq", "column": "k", "value": "1"}},
        {"plan": "select", "padding_rows": 7,
         "where": {"op": "eq", "column": "k", "value": "2"}},
        {"plan": "count", "where": {"op": "eq", "column": "mode",
                                    "value": "AIR"}},
        {"plan": "count", "where": {"op": "like", "column": "date",
                                    "pattern": "1995-%"}},
        {"plan": "count", "where": {"op": "like", "column": "date",
                                    "pattern": "%-03-%"}},
    ]
    work = dep.batch_work(batch)
    c, n, a = 3, 4, 69
    want_match = (roofline.match(c, 2, n, 12, a)        # the two selects
                  + roofline.match(c, 1, n, 12, a)      # the eq count
                  + roofline.match(c, 1, n, 5, a)       # prefix "1995-"
                  + roofline.match(c, 1, n, 4, a, 9))   # window "-03-"
    assert work["match"] == want_match
    # two selects padded to 7 rows each against (n, 3 columns x W x A)
    assert work["contraction"] == roofline.contraction(c, 14, n, 3 * 12 * a)
