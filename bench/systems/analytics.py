"""TPC-H Q1/Q6-shaped analytics over an outsourced LINEITEM, served by
``QueryServer``: the §3.4 range count (the SS-SUB ripple with its
degree-reduction re-shares), conditional SUM/AVG (the AA match and a
contraction) and MAX (the knockout tournament of ripples).

The configuration names the columns, the bit width of each numeric one,
the codec, the clouds and the degree. The owner's step is the program's
own ``outsource``; requests carry no ``reduce_every``, so the program's
planner picks each ripple's segment width (``choose_reduce_every``), and
the same choice, through the round engine's ``ripple_schedule``, gives
the ripple's work for the roofline; a SUM's contraction reads as many
limb columns as the round engine opens (``sum_limbs``).
"""
from __future__ import annotations

import collections
from typing import Dict, List

import numpy as np

import datagen
import datagen_numeric
import reference_numeric
import roofline
import traffic as traffic_mod
from repro.api.planner import choose_reduce_every
from repro.core.queries.aggregate import sum_limbs
from repro.core.queries.rounds import ripple_schedule


def ripple(c: int, s: int, n: int, k: int, carry_in: bool) -> roofline.Work:
    """One ripple segment over (c, s, n, k) operands: both operands' k bit
    planes read, the incoming carry read (not on the first segment), the
    result bit and the carry written. Elementwise products on the vector
    unit, so no MXU operations are counted: the bytes bound it. Left out:
    the copies that build the operands (the range's ``lhs``/``rhs``
    concatenate and broadcast, the tournament's pairing)."""
    return roofline.Work(macs=0, bytes=roofline.ELEMENT_BYTES * c * s * n
                         * (2 * k + int(carry_in) + 2))


def segments(t_bits: int, every: int) -> List[int]:
    """The bit count of each ripple segment at ``reduce_every`` ``every``."""
    return [s1 - s0 for s0, s1, _ in ripple_schedule(t_bits, every, 1)]


class Deployment:
    """The owner's outsourced columns behind one ``QueryServer``."""

    relation = None                 # the server's sole relation

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro import api
        from repro.core import Codec, outsource
        from repro.launch.serve import QueryServer

        self.api = api
        self.config = config
        columns = config["columns"]
        data = datagen_numeric.lineitem_numeric(config["rows"], seed)
        self.reference = reference_numeric.Table(
            {c: data[c] for c in columns})
        rows = [list(r) for r in zip(*([str(v) for v in data[c]]
                                       for c in columns))]
        numeric = {columns.index(c): t
                   for c, t in config["numeric_columns"].items()}
        k_data, k_query = datagen.sub_seeds(seed, 6)[4:6]
        codec = Codec(word_length=config["word_length"])
        if codec.alphabet_size != config["alphabet_size"]:
            raise ValueError(f"codec alphabet {codec.alphabet_size} != "
                             f"configured {config['alphabet_size']}")
        db = outsource(jax.random.PRNGKey(k_data), rows, column_names=columns,
                       codec=codec, n_shares=config["clouds"],
                       degree=config["degree"], numeric_columns=numeric)
        server = traffic["server"]
        self.server = QueryServer(db, key=k_query,
                                  dispatcher=api.MeshDispatcher(),
                                  max_batch=server["max_batch"],
                                  max_wait_ms=server["max_wait_ms"])

    # -- traffic ------------------------------------------------------------
    def column_values(self, name: str) -> list:
        return sorted({str(v) for v in self.reference.columns[name]})

    def plan(self, request: dict):
        api = self.api
        if request["plan"] == "range_count":
            lo, hi = reference_numeric.bounds(request)
            return api.RangeCount(api.Between(request["column"], lo, hi))
        if request["plan"] == "aggregate":
            where = request.get("where")
            return api.Aggregate(
                request["op"], request["column"],
                where=(api.Eq(where["column"], where["value"])
                       if where else None))
        raise ValueError(f"unknown plan {request['plan']!r}")

    def plane_stats(self):
        return self.server.dataplane.stats

    # -- work of the calls a batch makes (for the rooflines) -----------------
    def batch_work(self, requests: List[dict]) -> Dict[str, roofline.Work]:
        cfg = self.config
        c, n, d = cfg["clouds"], cfg["rows"], cfg["degree"]
        w, a = cfg["word_length"], cfg["alphabet_size"]
        bits = cfg["numeric_columns"]
        work = collections.defaultdict(roofline.Work)
        ranges, tournaments = collections.Counter(), collections.Counter()
        matched = collections.Counter()
        averaged = 0
        for r in requests:
            t = bits[r["column"]]
            if r["plan"] == "range_count":
                ranges[t] += 1
            elif r["op"] in ("min", "max"):
                if r.get("where"):
                    raise ValueError("conditional MIN/MAX work is not "
                                     "counted")
                tournaments[t] += 1
            else:
                matched[t] += 1
                averaged += r["op"] == "avg"
        for t, b in ranges.items():         # one chain of 2B rows a group
            ks = segments(t, choose_reduce_every(c, d, t))
            for i, k in enumerate(ks):
                work["ripple"] += ripple(c, 2 * b, n, k, i > 0)
        for t, b in tournaments.items():    # one chain a level
            ks = segments(t, choose_reduce_every(c, d, t, tournament=True))
            left = n
            while left > 1:
                pairs = left // 2
                for i, k in enumerate(ks):
                    work["ripple"] += ripple(c, b, pairs, k, i > 0)
                left -= pairs
        for t, b in matched.items():    # a match, then bits x limb columns
            work["match"] += roofline.match(c, b, n, w, a)
            work["contraction"] += roofline.contraction(
                c, b, n, len(sum_limbs(n, t)))
        if averaged:    # the denominators ride the count phase
            work["match"] += roofline.match(c, averaged, n, w, a)
        return dict(work)

    # -- correctness --------------------------------------------------------
    def release(self) -> None:
        self.server = None

    def compare(self, records) -> Dict[str, int]:
        """Numbers compared against the reference: answers that differ.
        Counts, sums and maxima exactly; an average as its exact sum and
        count and their float64 quotient."""
        wrong = 0
        for rec in records:
            if rec.result is None:
                continue
            want = self.reference.answer(rec.request)
            got = rec.result
            if want[0] == "count":
                ok = got.count == want[1]
            elif want[0] == "avg":
                _, total, count, mean = want
                ok = (got.count == count and got.value == mean
                      and (mean is None or round(got.value * count) == total))
            else:
                ok = got.value == want[1]
            wrong += not ok
        return {"wrong_answers": wrong}


def warm_requests(traffic: dict, deployment: Deployment) -> List[List[dict]]:
    """Batches that hold every shape the window can close. The clients'
    blocks fix which requests meet in a batch (``schedule_seed``), so each
    family's batch sizes are read off one block of the streams; each
    family is warmed alone at each of its sizes, and the Q1 family as
    averages of each of its columns at every size up to its largest, so
    that each column's sums and the denominators' count phase meet every
    size a run's mix of SUM and AVG and of columns can give them."""
    rng = np.random.default_rng(0)
    streams = traffic_mod.client_streams(traffic, 0, deployment.column_values,
                                         deployment.config)
    block = [[next(s) for _ in range(traffic["block"])] for s in streams]
    sizes = collections.defaultdict(set)
    for batch in zip(*block):
        for family, b in collections.Counter(
                r["family"] for r in batch).items():
            sizes[family].add(b)

    def draw(template, b):
        return [traffic_mod.realize(template, rng, deployment.column_values,
                                    deployment.config) for _ in range(b)]
    batches = []
    for entry in traffic["mix"]:
        template = entry["request"]
        found = sorted(sizes[template["family"]])
        if template["plan"] == "aggregate" and isinstance(
                template["op"], dict):
            columns = template["column"]
            for column in (columns["values"] if isinstance(columns, dict)
                           else [columns]):
                batches += [draw(dict(template, op="avg", column=column), b)
                            for b in range(1, max(found) + 1)]
            continue
        batches += [draw(template, b) for b in found]
    return batches
