"""The plain reference for the Q1/Q6 deployment: SQL over the cleartext
integer columns, in numpy. Imports nothing of the system under test.

A request is a range count or an aggregate:

* ``range_count``: ``COUNT(*) WHERE lo <= column <= hi``, the bounds from
  :func:`bounds`: ``year`` Y is Q6's shipping year (Jan 1 to Dec 31 of Y,
  as day numbers since 1992-01-01); ``around`` D ``within`` w is Q6's
  ``BETWEEN D - w AND D + w``; ``below`` Q is Q6's ``< Q`` (from 0: every
  quantity is at least 1);
* ``aggregate``: ``op(column) [WHERE where.column = where.value]`` with
  ``op`` in sum, avg, min, max. A sum is exact; an average is the exact
  (sum, count) pair and their float64 quotient; an aggregate over no row
  is None.
"""
from __future__ import annotations

import datetime
from typing import Dict, Tuple

import numpy as np

START = datetime.date(1992, 1, 1)


def bounds(request: dict) -> Tuple[int, int]:
    """The inclusive ``[lo, hi]`` of a range request."""
    if "year" in request:
        y = int(request["year"])
        return ((datetime.date(y, 1, 1) - START).days,
                (datetime.date(y, 12, 31) - START).days)
    if "around" in request:
        d, w = int(request["around"]), int(request["within"])
        return d - w, d + w
    if "below" in request:
        return 0, int(request["below"]) - 1
    raise ValueError(f"no range in request {request!r}")


class Table:
    """Cleartext columns by name (ints as int64, strings as str)."""

    def __init__(self, columns: Dict[str, np.ndarray]):
        self.columns = {k: np.asarray(v) for k, v in columns.items()}

    def answer(self, request: dict) -> Tuple:
        """('count', n), ('sum', s), ('avg', s, n, s / n), ('min' | 'max',
        v); a value over no row is None."""
        if request["plan"] == "range_count":
            lo, hi = bounds(request)
            x = self.columns[request["column"]]
            return ("count", int(((x >= lo) & (x <= hi)).sum()))
        if request["plan"] != "aggregate":
            raise ValueError(f"unknown plan {request['plan']!r}")
        x = self.columns[request["column"]].astype(np.int64)
        where = request.get("where")
        if where is not None:
            x = x[self.columns[where["column"]] == where["value"]]
        op = request["op"]
        total, n = int(x.sum()), int(x.size)
        if op == "sum":
            return ("sum", total)
        if op == "avg":
            return ("avg", total, n, total / n if n else None)
        if op in ("min", "max"):
            return (op, (int(x.min()) if op == "min" else int(x.max()))
                    if n else None)
        raise ValueError(f"unknown aggregate {op!r}")
