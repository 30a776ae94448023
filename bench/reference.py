"""The plain reference: the answers a request should get, from the
cleartext data alone. Imports nothing of the system under test.

Relation requests follow SQL: ``eq`` is string equality, ``like`` is
LIKE with ``%`` (any run) and ``_`` (one character); a count is the number
of rows that satisfy the predicate, a select the satisfying rows and their
positions, in table order. An embedding lookup returns the table rows
quantized to the configuration's fixed point (round half to even), as
float32.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

import numpy as np


def like_regex(pattern: str) -> "re.Pattern":
    out = []
    for ch in pattern:
        out.append(".*" if ch == "%" else "." if ch == "_" else re.escape(ch))
    return re.compile("".join(out), re.DOTALL)


class Relation:
    """A cleartext table and its column names."""

    def __init__(self, rows: Sequence[Sequence[str]], columns: Sequence[str]):
        self.rows = [list(r) for r in rows]
        self.columns = list(columns)
        self._eq_index: Dict[int, Dict[str, List[int]]] = {}
        self._like: Dict[Tuple[int, str], List[int]] = {}

    def _col(self, name: str) -> int:
        return self.columns.index(name)

    def addresses(self, where: dict) -> List[int]:
        col = self._col(where["column"])
        if where["op"] == "eq":
            index = self._eq_index.get(col)
            if index is None:
                index = self._eq_index[col] = {}
                for i, r in enumerate(self.rows):
                    index.setdefault(r[col], []).append(i)
            return list(index.get(where["value"], []))
        if where["op"] == "like":
            key = (col, where["pattern"])
            if key not in self._like:
                rx = like_regex(where["pattern"])
                self._like[key] = [i for i, r in enumerate(self.rows)
                                   if rx.fullmatch(r[col])]
            return list(self._like[key])
        raise ValueError(f"unknown predicate {where['op']!r}")

    def answer(self, request: dict) -> Tuple:
        """('count', n) or ('select', addresses, rows)."""
        idx = self.addresses(request["where"])
        if request["plan"] == "count":
            return ("count", len(idx))
        if request["plan"] == "select":
            return ("select", idx, [self.rows[i] for i in idx])
        raise ValueError(f"unknown plan {request['plan']!r}")


def quantize_rows(rows: np.ndarray, scale: float) -> np.ndarray:
    """float32 rows -> the fixed-point values they open to, as float32."""
    q = np.round(np.asarray(rows, np.float32) * np.float32(scale))
    return (q.astype(np.int64).astype(np.float32) / np.float32(scale))
