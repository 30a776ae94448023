"""Pattern-predicate queries (LIKE / prefix / suffix / substring) on
secret-shares — the §3.1 accumulating automaton generalized past exact
equality.

Lowering (``repro.core.encoding.parse_like`` builds the spec):

* wildcard-free LIKE  → **exact** — not handled here at all; the planner
  rewrites it onto the classic Eq path (provably, see planner tests).
* ``J_hn%`` (masked)  → the full-width chain with a masked pattern
  encoding: wildcard positions share the all-ones vector (their alphabet
  dot is identically 1), trailing positions the terminator one-hot. Rides
  the very same ``aa_match_batch`` stack as Eq.
* ``Jo%`` (prefix)    → a truncated k-chain over ``col[..., :k, :]``.
* ``%hn`` (suffix)    → sliding-window products × the terminator factor
  (windows are mutually exclusive for wildcard-free tiles, so the linear
  sum is the exact 0/1 bit).
* ``%oh%`` (contains) → the window count P ∈ {0..M}, one degree-reduction
  re-share (the family's only extra round), then the share-local zero
  test ``1 − Π_{j=1..M}(j−P)/M!``.

All four kinds keep the final match-bit degree ≤ the exact chain's 2tW, so
any database that supports equality selects supports pattern selects. The
free functions here run the batch engine at B = 1; pattern queries inside a
``QueryClient.run_batch`` group execute the same code, fused (match groups
per strategy/width, fetches in the shared cross-group matmul).

Cost model: :func:`match_phase_cost` (re-exported from ``rounds``) is both
what the round engine charges and what the planner prices, so
``explain()`` is exact for pattern counts and one-round selects.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax

from .. import encoding
from ..costs import CostLedger
from ..engine import SecretSharedDB
from . import rounds
from ._common import get_backend
from .rounds import match_phase_cost  # noqa: F401  (re-export)

#: spec kinds that leave the full-width chain (``masked`` does not)
TILE_KINDS = ("prefix", "suffix", "contains")


def like_spec(codec: encoding.Codec, pattern: str
              ) -> Optional[encoding.PatternSpec]:
    """Lower a LIKE pattern string to its :class:`~.encoding.PatternSpec`,
    or ``None`` when it is wildcard-free (→ the exact-equality path).
    Raises ``ValueError`` for unsupported shapes (interior ``%``, ``_``
    under a leading ``%``, empty body, k > word_length)."""
    kind, body, wild = encoding.parse_like(pattern)
    if kind == "exact":
        return None
    spec = encoding.PatternSpec(kind, body, wild, pattern)
    # fail fast at lowering time, not inside the dispatch
    encoding.encode_pattern_tile(codec, spec)
    return spec


def pattern_count(key: jax.Array, db: SecretSharedDB, column: int,
                  spec: encoding.PatternSpec, *,
                  ledger: Optional[CostLedger] = None,
                  backend="jnp") -> Tuple[int, CostLedger]:
    """COUNT(*) WHERE col LIKE pattern — one round (two for CONTAINS)."""
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    cnt = rounds.count_phase(
        be, db, [rounds.MatchJob(column, spec.body, key, ledger, spec)])[0]
    return cnt, ledger


def pattern_select(key: jax.Array, db: SecretSharedDB, column: int,
                   spec: encoding.PatternSpec, *, strategy: str = "one_round",
                   ell: Optional[int] = None,
                   padded_rows: Optional[int] = None,
                   ledger: Optional[CostLedger] = None, backend="jnp"
                   ) -> Tuple[List[List[str]], List[int], CostLedger]:
    """SELECT * WHERE col LIKE pattern via ``one_round`` or ``tree``.

    ``tree`` needs the match cardinality ℓ (run :func:`pattern_count`
    first, exactly like the Eq tree's Phase 0); ``one_round`` does not.
    The §3.2.1 one-tuple special case stays exact-equality-only. Returns
    ``(rows, addresses, ledger)``.
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    k_pat, k_fetch = jax.random.split(key)
    if strategy == "one_round":
        addresses = rounds.match_all_round(
            be, db,
            [rounds.MatchJob(column, spec.body, k_pat, ledger, spec)])[0]
    elif strategy == "tree":
        if ell is None:
            raise ValueError("tree strategy needs ell (run pattern_count)")
        if ell == 0:
            return [], [], ledger
        addresses = rounds.tree_rounds(
            be, db, [rounds.TreeJob(column, spec.body, k_pat, ledger, spec,
                                    ell=ell)])[0]
    else:
        raise ValueError(
            f"pattern selects support one_round/tree, not {strategy!r}")
    rows = rounds.fetch_round(
        be, db, [rounds.FetchJob(k_fetch, addresses, ledger,
                                 padded_rows)])[0]
    return rows, addresses, ledger
