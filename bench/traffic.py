"""The one traffic generator: reads a mix file and draws each client's
request stream from the seed.

A mix file (``traffic/<cell>.json``) holds ``clients`` (closed-loop
callers, each waiting for its reply before it sends again), ``server``
(the ``QueryServer`` batching settings) and ``mix``: entries of
``weight`` and ``request``, a template whose ``{"draw": ...}`` values are
drawn per request:

* ``{"draw": "choice", "values": [...]}`` — one of the values, uniform;
* ``{"draw": "column", "column": name}`` — one of the distinct values of a
  column of the configuration's data, uniform;
* ``{"draw": "ints", "count": k, "high": key}`` — ``k`` integers uniform
  in ``[0, config[key])``;

and whose ``"pattern"`` string, if present, takes the drawn ``value`` in
place of ``{}``. Without ``block``, each request's entry is drawn by its
weight. With ``block``, the clients' requests go in blocks of that many
per client, each holding every entry ``weight x block`` times. Closed-loop
clients advance in step, so the i-th requests of all clients meet in one
batch, and a batch's cost follows the entries it holds. So which entries
meet is fixed once, from ``schedule_seed`` (default 0): every run seed
gets the same batches, in an order of its own, with values of its own.
The same seed gives every client the same stream.
"""
from __future__ import annotations

import copy
import json
import os
from typing import Callable, Iterator, List

import numpy as np

from datagen import sub_seeds

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load(cell: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{cell}.json")) as f:
        return json.load(f)


def _draw(spec, rng: np.random.Generator, columns: Callable[[str], list],
          config: dict):
    if isinstance(spec, dict) and "draw" in spec:
        kind = spec["draw"]
        if kind == "choice":
            return spec["values"][int(rng.integers(len(spec["values"])))]
        if kind == "column":
            values = columns(spec["column"])
            return values[int(rng.integers(len(values)))]
        if kind == "ints":
            return [int(t) for t in rng.integers(0, int(config[spec["high"]]),
                                                 int(spec["count"]))]
        raise ValueError(f"unknown draw {kind!r}")
    if isinstance(spec, dict):
        return {k: _draw(v, rng, columns, config) for k, v in spec.items()}
    return copy.deepcopy(spec)


def realize(template: dict, rng: np.random.Generator,
            columns: Callable[[str], list], config: dict) -> dict:
    """One request from a mix entry's template."""
    req = _draw(template, rng, columns, config)
    where = req.get("where")
    if isinstance(where, dict) and "pattern" in where:
        where["pattern"] = where["pattern"].replace("{}", str(where["value"]))
        del where["value"]
    return req


def client_streams(traffic: dict, seed: int, columns: Callable[[str], list],
                   config: dict) -> List[Iterator[dict]]:
    """One endless, seeded request stream per client."""
    mix = traffic["mix"]
    weights = np.asarray([m["weight"] for m in mix], float)
    weights = weights / weights.sum()
    seeds = sub_seeds(seed, 3 + traffic["clients"])[3:]

    block = traffic.get("block")
    if block:
        counts = np.rint(weights * block).astype(int)
        if counts.sum() != block or not np.allclose(counts, weights * block):
            raise ValueError(f"weights {weights} do not split a block of "
                             f"{block} requests")
        entries = np.repeat(np.arange(len(mix)), counts)
        schedule = np.random.default_rng(traffic.get("schedule_seed", 0))
        plan = [schedule.permutation(entries)
                for _ in range(traffic["clients"])]
    order_seed = sub_seeds(seed, 4 + traffic["clients"])[-1]

    def stream(client: int, s: int) -> Iterator[dict]:
        rng = np.random.default_rng(s)
        order = np.random.default_rng(order_seed)   # alike in every client
        while True:
            picks = (plan[client][order.permutation(block)] if block else
                     [rng.choice(len(mix), p=weights)])
            for i in picks:
                yield realize(mix[int(i)]["request"], rng, columns, config)
    return [stream(c, s) for c, s in enumerate(seeds)]
