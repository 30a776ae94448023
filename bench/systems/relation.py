"""A secret-shared relation served by ``QueryServer``.

The configuration file names the data generator in ``datagen``, the
columns, the codec, the clouds and the sharing degree. The owner's step —
encode on the host, share onto the device — is the program's own
``outsource``; requests run through the server the user calls.
"""
from __future__ import annotations

import collections
from typing import Dict, List

import numpy as np

import datagen
import reference
import roofline
import traffic as traffic_mod


def _where(db_api, where: dict):
    if where["op"] == "eq":
        return db_api.Eq(where["column"], where["value"])
    if where["op"] == "like":
        return db_api.Like(where["column"], where["pattern"])
    raise ValueError(f"unknown predicate {where['op']!r}")


class Deployment:
    """The owner's outsourced relation behind one ``QueryServer``."""

    relation = None                 # the server's sole relation

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro import api
        from repro.core import Codec, outsource
        from repro.launch.serve import QueryServer

        self.api = api
        self.config = config
        make_rows = getattr(datagen, config["generator"])
        columns = config["columns"]
        rows = make_rows(config["rows"], seed, columns)
        self.reference = reference.Relation(rows, columns)
        numeric = {columns.index(c): t
                   for c, t in config["numeric_columns"].items()}
        k_data, k_query = datagen.sub_seeds(seed, 3)[:2]
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
        self._distinct: Dict[str, list] = {}

    # -- traffic ------------------------------------------------------------
    def column_values(self, name: str) -> list:
        if name not in self._distinct:
            col = self.reference.columns.index(name)
            self._distinct[name] = sorted({r[col]
                                           for r in self.reference.rows})
        return self._distinct[name]

    def plan(self, request: dict):
        api = self.api
        where = _where(api, request["where"])
        if request["plan"] == "count":
            return api.Count(where)
        if request["plan"] == "select":
            return api.Select(where, strategy=request.get("strategy", "auto"),
                              padding=api.Padding(
                                  rows=request.get("padding_rows")))
        raise ValueError(f"unknown plan {request['plan']!r}")

    def plane_stats(self):
        return self.server.dataplane.stats

    # -- work of the calls a batch makes (for the rooflines) -----------------
    def batch_work(self, requests: List[dict]) -> Dict[str, roofline.Work]:
        cfg = self.config
        c, n = cfg["clouds"], cfg["rows"]
        w, a = cfg["word_length"], cfg["alphabet_size"]
        m = len(cfg["columns"])
        work = collections.defaultdict(roofline.Work)
        groups = collections.Counter()
        fetch_rows = 0
        for r in requests:
            where = r["where"]
            kind, k = "full", w
            if where["op"] == "like":
                body = where["pattern"].strip("%")
                k = len(body)
                kind = ("full" if "%" not in where["pattern"] else
                        "prefix" if not where["pattern"].startswith("%")
                        else "slide")
            groups[(r["plan"], where["column"], kind, k)] += 1
            if r["plan"] == "select":
                fetch_rows += max(r.get("padding_rows") or 0,
                                  len(self.reference.addresses(where)))
        for (_, _, kind, k), b in groups.items():
            windows = w - k + 1 if kind == "slide" else 1
            work["match"] += roofline.match(c, b, n, k, a, windows)
        if fetch_rows:
            work["contraction"] += roofline.contraction(c, fetch_rows, n,
                                                        m * w * a)
        return dict(work)

    # -- correctness --------------------------------------------------------
    def release(self) -> None:
        self.server = None

    def compare(self, records) -> Dict[str, int]:
        """Numbers compared against the reference: answers that differ."""
        wrong = 0
        for rec in records:
            if rec.result is None:
                continue
            want = self.reference.answer(rec.request)
            got = rec.result
            if want[0] == "count":
                ok = got.count == want[1]
            else:
                ok = got.addresses == want[1] and got.rows == want[2]
            wrong += not ok
        return {"wrong_answers": wrong}


def warm_requests(traffic: dict, deployment: Deployment) -> List[List[dict]]:
    """Batches that hold every shape the window can close, none larger
    than the client count: b requests of each mix entry alone, for b up to
    the client count; and where the mix holds both, b exact-match counts
    beside one pattern count, which moves the exact ones from the engine's
    single-group fast path onto its grouped path."""
    rng = np.random.default_rng(0)
    clients = traffic["clients"]

    def draw(template, b):
        return [traffic_mod.realize(template, rng, deployment.column_values,
                                    deployment.config) for _ in range(b)]
    templates = [m["request"] for m in traffic["mix"]]
    batches = [draw(t, b) for b in range(1, clients + 1) for t in templates]
    counts = [t for t in templates if t["plan"] == "count"]
    exact = [t for t in counts if t["where"]["op"] == "eq"]
    pattern = [t for t in counts if t["where"]["op"] == "like"]
    if exact and pattern:
        batches += [draw(exact[0], b) + draw(pattern[0], 1)
                    for b in range(1, clients)]
    return batches
