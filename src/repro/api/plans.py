"""Logical query plans — the user-facing half of the OBSCURE-style API.

A plan is a small frozen dataclass describing *what* to compute (predicate,
columns by name, padding policy); the :class:`repro.api.QueryClient` decides
*how* (strategy, backend, keys) and returns a uniform :class:`QueryResult`.
Plans never touch shares: they are plain data, cheap to build, hash and log.

Padding is explicit because it is a security knob, not a tuning knob: the
paper's output-size attack (§3.2.2 / §3.3.2 leakage discussion) is defeated
by fetching ``Padding.rows`` fake rows (selection) or running
``Padding.values`` fake join jobs (equijoin) so the clouds cannot learn the
true result size ℓ.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple, Union

from ..core.costs import CostLedger
from ..core.engine import SecretSharedDB

ColumnRef = Union[str, int]

AUTO = "auto"
SELECT_STRATEGIES = ("one_tuple", "one_round", "tree")
JOIN_KINDS = ("pkfk", "equi")
AGG_OPS = ("sum", "avg", "min", "max")


def resolve_column(db: SecretSharedDB, column: ColumnRef) -> int:
    """Name-or-index -> validated column index of ``db``."""
    names = list(db.column_names)
    if isinstance(column, int):
        if not 0 <= column < db.n_attrs:
            raise IndexError(f"column index {column} out of range "
                             f"(relation has {db.n_attrs} attributes)")
        return column
    try:
        return names.index(column)
    except ValueError:
        raise KeyError(f"unknown column {column!r}; relation has "
                       f"{names}") from None


# ---------------------------------------------------------------------------
# predicates
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Eq:
    """Equality predicate: ``column = pattern`` (exact word, §3.1.2)."""
    column: ColumnRef
    pattern: str


@dataclasses.dataclass(frozen=True)
class Between:
    """Inclusive range predicate: ``lo <= column <= hi`` (§3.4)."""
    column: ColumnRef
    lo: int
    hi: int

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty range: lo={self.lo} > hi={self.hi}")


@dataclasses.dataclass(frozen=True)
class Like:
    """SQL-ish pattern predicate: ``column LIKE pattern`` (§3.1 general AA).

    ``%`` matches any run of characters at either end of the pattern
    (``lit%`` / ``%lit`` / ``%lit%``); ``_`` matches any ONE symbol —
    including the pad terminator, so ``_`` is a don't-care, not a length
    constraint (documented deviation from SQL). A wildcard-free pattern
    lowers to the exact-match :class:`Eq` path; interior ``%`` runs and
    ``_`` under a ``%``-shifted window raise ``PlanNotSupported``.
    """
    column: ColumnRef
    pattern: str


@dataclasses.dataclass(frozen=True)
class Prefix:
    """Prefix predicate: ``column`` starts with ``literal`` (verbatim —
    no wildcard characters; use :class:`Like` for ``_`` don't-cares).
    Lowers to a truncated k-position AA chain."""
    column: ColumnRef
    literal: str


@dataclasses.dataclass(frozen=True)
class Suffix:
    """Suffix predicate: ``column`` ends with ``literal`` (verbatim).
    Lowers to the sliding-window automata step with a terminator factor."""
    column: ColumnRef
    literal: str


@dataclasses.dataclass(frozen=True)
class Contains:
    """Substring predicate: ``column`` contains ``literal`` (verbatim).
    Lowers to the sliding-window automata step + a degree-reduction
    re-share + the window-count zero-test."""
    column: ColumnRef
    literal: str


#: predicate classes the pattern engine lowers (besides plain Eq).
PATTERN_PREDICATES = (Like, Prefix, Suffix, Contains)
#: every predicate class Count/Select accept.
MATCH_PREDICATES = (Eq,) + PATTERN_PREDICATES


# ---------------------------------------------------------------------------
# padding policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Padding:
    """Output-size-attack resistance policy.

    rows:   pad the oblivious fetch to this many rows (≥ true ℓ); the extra
            rows carry all-zero one-hots and fetch nothing.
    values: number of fake (no-op) equijoin jobs, hiding the number of
            common join values k.
    """
    rows: Optional[int] = None
    values: int = 0

    def __post_init__(self):
        if self.rows is not None and self.rows < 0:
            raise ValueError("Padding.rows must be >= 0")
        if self.values < 0:
            raise ValueError("Padding.values must be >= 0")

    @classmethod
    def to_rows(cls, rows: int) -> "Padding":
        return cls(rows=rows)

    @classmethod
    def fake_values(cls, values: int) -> "Padding":
        return cls(values=values)


Padding.NONE = Padding()


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

class Plan:
    """Marker base class for logical plans."""


@dataclasses.dataclass(frozen=True)
class Count(Plan):
    """COUNT(*) WHERE <predicate> (§3.1, Algorithm 2).

    ``where`` is :class:`Eq` or any pattern predicate
    (:class:`Like`/:class:`Prefix`/:class:`Suffix`/:class:`Contains`);
    unknown predicate types raise ``PlanNotSupported`` at plan time.
    """
    where: Union[Eq, Like, Prefix, Suffix, Contains]


@dataclasses.dataclass(frozen=True)
class Select(Plan):
    """SELECT * WHERE col = pattern (§3.2, Algorithms 3 & 4).

    strategy: ``"auto"`` lets the cost-based planner pick among the paper's
    three algorithms using the §3.2 bit/round formulas; or force one of
    ``"one_tuple" | "one_round" | "tree"``. ``expected_matches`` is the
    planner's cardinality hint (ℓ); ``one_tuple`` is only eligible when the
    hint says ℓ = 1 (the algorithm itself verifies and raises otherwise).

    ``where`` may also be a pattern predicate (Like/Prefix/Suffix/Contains);
    pattern selects run ``one_round`` or ``tree`` (``one_tuple`` is the
    §3.2.1 exact-equality special case).
    """
    where: Union[Eq, Like, Prefix, Suffix, Contains]
    strategy: str = AUTO
    expected_matches: Optional[int] = None
    padding: Padding = Padding.NONE
    branching: Optional[int] = None     # tree fan-out override

    def __post_init__(self):
        if self.strategy not in (AUTO,) + SELECT_STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; choose "
                             f"from {(AUTO,) + SELECT_STRATEGIES}")
        if self.expected_matches is not None and self.expected_matches < 0:
            raise ValueError("expected_matches must be >= 0")
        if self.padding.values:
            raise ValueError("selection hides the result size with "
                             "Padding.rows (fake fetch rows); "
                             "Padding.fake_values applies to equijoins")


@dataclasses.dataclass(frozen=True)
class RangeCount(Plan):
    """COUNT(*) WHERE lo <= col <= hi (§3.4, Algorithm 5).

    The planner decides where the ripple re-shares its carry (the paper's
    degree-reduction round): nowhere where the whole width opens within
    the c clouds, else every k SS-SUB bit positions, k the widest segment
    that does (``planner.choose_reduce_every``). A column no segment fits
    is refused at plan time.
    """
    where: Between


@dataclasses.dataclass(frozen=True)
class RangeSelect(Plan):
    """Fetch all tuples with col in [lo, hi] (§3.4 + §3.2 fetch); the
    ripple re-shares as for :class:`RangeCount`."""
    where: Between
    padding: Padding = Padding.NONE

    def __post_init__(self):
        if self.padding.values:
            raise ValueError("selection hides the result size with "
                             "Padding.rows (fake fetch rows); "
                             "Padding.fake_values applies to equijoins")


@dataclasses.dataclass(frozen=True)
class Join(Plan):
    """Oblivious join of the client's relation with ``right`` (§3.3).

    on:   (left column, right column) — names or indices.
    kind: ``"pkfk"`` (§3.3.1, left column is a primary key) or ``"equi"``
          (§3.3.2, join values may repeat on both sides).

    The planner evaluates a PK/FK match matrix by the §3.1.2 chain or its
    aggregate form, whichever takes fewer backend launches
    (``planner.choose_match_method``); both open the same secrets at the
    same degree.
    """
    right: SecretSharedDB
    on: Tuple[ColumnRef, ColumnRef]
    kind: str = "pkfk"
    padding: Padding = Padding.NONE

    def __post_init__(self):
        if self.kind not in JOIN_KINDS:
            raise ValueError(f"unknown join kind {self.kind!r}; choose from "
                             f"{JOIN_KINDS}")
        if len(self.on) != 2:
            raise ValueError("Join.on must be a (left, right) column pair")


@dataclasses.dataclass(frozen=True)
class Aggregate(Plan):
    """SUM/AVG/MIN/MAX(column) [WHERE col = pattern] (OBSCURE-style).

    column: the numeric value column (must have been outsourced in binary
            form via ``numeric_columns``).
    where:  optional equality predicate restricting the aggregate to the
            matching tuples (None = whole relation).
    verify: run the OBSCURE-style consistency round on every opened
            aggregate tensor and raise ``VerificationError`` if a cloud's
            response share is inconsistent. Needs c >= degree + 2 clouds;
            the extra round/bits are priced in ``explain()``.

    A MIN/MAX comparator re-shares its carry where the planner says, as a
    range plan's ripple does.
    """
    op: str
    column: ColumnRef
    where: Optional[Eq] = None
    verify: bool = False

    def __post_init__(self):
        if self.op not in AGG_OPS:
            raise ValueError(f"unknown aggregate op {self.op!r}; choose "
                             f"from {AGG_OPS}")


@dataclasses.dataclass(frozen=True)
class EmbedLookup(Plan):
    """Oblivious embedding lookup of a step's token ids (§3.2.1 as an LM
    layer; the embedding-table relation is attached via
    ``models.private_embed.as_embed_relation``).

    tokens: the step's token ids (batch×seq, flattened to a tuple — plans
            are plain hashable data; the result keeps the flat order).
    verify: OBSCURE-style consistency round over the opened embeddings
            (needs c >= degree+3 clouds); priced in ``explain()``.
    """
    tokens: Tuple[int, ...]
    verify: bool = False

    def __post_init__(self):
        object.__setattr__(self, "tokens",
                           tuple(int(t) for t in self.tokens))
        if not self.tokens:
            raise ValueError("EmbedLookup needs at least one token id")
        if min(self.tokens) < 0:
            raise ValueError("token ids must be >= 0")


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryResult:
    """Uniform result for every query family.

    rows/addresses are None for pure counting queries; count is the number
    of satisfying tuples whenever it is known. ``strategy`` echoes the
    executed algorithm (planner-chosen or forced) and ``plan`` echoes the
    logical plan for logging/replay. ``value`` carries an aggregation
    plan's opened scalar (int for SUM/MIN/MAX, float for AVG; None when a
    conditional MIN/MAX/AVG matched no tuples). ``embeddings`` carries an
    ``EmbedLookup``'s opened float32 ``(n_tokens, D)`` matrix.
    """
    plan: Plan
    ledger: CostLedger
    strategy: str
    rows: Optional[List[List[str]]] = None
    count: Optional[int] = None
    addresses: Optional[List[int]] = None
    value: Optional[float] = None
    embeddings: Optional[object] = None     # np.ndarray; typed loosely to
    #                                         keep plans free of numpy

    def __post_init__(self):
        if self.count is None and self.rows is not None:
            object.__setattr__(self, "count", len(self.rows))
