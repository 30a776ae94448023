"""Selection queries on secret-shares (paper §3.2; Algorithms 3 & 4).

Three variants, exactly as the paper structures them:

* ``select_one_tuple``  (§3.2.1, Alg 3) — one value holds one tuple: match-bit
  × tuple, summed over n; only the satisfying tuple survives the sum.
* ``select_one_round``  (§3.2.2 "one-round") — cloud returns all n match bits
  (user interpolates n·c′ values), then a secret-shared ℓ'×n one-hot fetch
  matrix is multiplied against the relation (share-space matmul).
* ``select_tree``       (§3.2.2 "tree-based", Alg 4) — Q&A rounds of
  block-partitioned counts; the user interpolates only O(ℓ) values per round;
  address of a single-hit block via Address_fetch (Σ matchᵢ · i).

All cloud work is oblivious: identical ops on every tuple regardless of data.

These free functions are thin wrappers over the round-structured batch
engine in ``repro.core.queries.rounds`` run at batch size 1 — every protocol
round is one fused device dispatch plus one interpolation (never a per-block
Python loop), and a query run here is bit-identical (rows *and* ledger) to
the same query run inside a ``QueryClient.run_batch`` group. Prefer
``repro.api.QueryClient.select``, which also cost-plans the strategy.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax

from ..costs import CostLedger
from ..engine import SecretSharedDB
from . import rounds
from ._common import get_backend
from .count import count_query


class CardinalityError(ValueError):
    """A selection algorithm's ℓ precondition failed (e.g. one_tuple on a
    multi-match predicate). Carries the true ``count`` the aborted count
    phase learned, so callers can replan without re-counting. Subclasses
    ValueError for backward compat."""

    def __init__(self, message: str, *, count: Optional[int] = None):
        super().__init__(message)
        self.count = count


# ---------------------------------------------------------------------------
# §3.2.1 — one value, one tuple (Algorithm 3)
# ---------------------------------------------------------------------------

def select_one_tuple(key: jax.Array, db: SecretSharedDB, column: int,
                     pattern: str, *, ledger: Optional[CostLedger] = None,
                     skip_count_phase: bool = False,
                     backend="jnp"
                     ) -> Tuple[List[List[str]], CostLedger]:
    """SELECT * WHERE col = pattern, when the predicate hits exactly 1 tuple."""
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    k_count, k_sel = jax.random.split(key)

    if not skip_count_phase:  # Phase 0 (Alg 3 line 1)
        ell, ledger = count_query(k_count, db, column, pattern, ledger=ledger,
                                  backend=be)
        if ell != 1:
            raise CardinalityError(
                f"select_one_tuple needs ℓ=1, predicate has {ell}"
                " — use select_one_round/select_tree", count=ell)

    # Alg 3 lines 3-12: one fused map round + one interpolation
    row = rounds.one_tuple_round(
        be, db, [rounds.MatchJob(column, pattern, k_sel, ledger)])[0]
    return [row], ledger


# ---------------------------------------------------------------------------
# shared Phase-2: oblivious fetch by secret-shared one-hot matrix
# ---------------------------------------------------------------------------

def fetch_by_addresses(key: jax.Array, db: SecretSharedDB,
                       addresses: Sequence[int], *, ledger: CostLedger,
                       padded_rows: Optional[int] = None,
                       backend="jnp"
                       ) -> List[List[str]]:
    """Fetch tuples at known addresses with an ℓ'×n shared one-hot matrix.

    ``padded_rows`` ≥ ℓ hides the true result size (fake-row padding, §3.2.2
    leakage discussion): extra rows are all-zero one-hots and fetch nothing.
    """
    be = get_backend(backend)
    return rounds.fetch_round(
        be, db, [rounds.FetchJob(key, list(addresses), ledger,
                                 padded_rows)])[0]


# ---------------------------------------------------------------------------
# §3.2.2 — one-round algorithm
# ---------------------------------------------------------------------------

def select_one_round(key: jax.Array, db: SecretSharedDB, column: int,
                     pattern: str, *, ledger: Optional[CostLedger] = None,
                     padded_rows: Optional[int] = None,
                     backend="jnp"
                     ) -> Tuple[List[List[str]], List[int], CostLedger]:
    """Phase 1: per-tuple match bits in ONE round (user interpolates n·c′).
    Phase 2: oblivious matrix fetch."""
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    k_pat, k_fetch = jax.random.split(key)

    addresses = rounds.match_all_round(
        be, db, [rounds.MatchJob(column, pattern, k_pat, ledger)])[0]
    rows = rounds.fetch_round(
        be, db, [rounds.FetchJob(k_fetch, addresses, ledger,
                                 padded_rows)])[0]
    return rows, addresses, ledger


# ---------------------------------------------------------------------------
# §3.2.2 — tree-based algorithm (Algorithm 4)
# ---------------------------------------------------------------------------

def select_tree(key: jax.Array, db: SecretSharedDB, column: int, pattern: str,
                *, ledger: Optional[CostLedger] = None,
                branching: Optional[int] = None,
                padded_rows: Optional[int] = None,
                known_count: Optional[int] = None,
                backend="jnp"
                ) -> Tuple[List[List[str]], List[int], CostLedger]:
    """Tree-based multi-round address discovery + oblivious fetch (Alg 4).

    Rounds ≤ ⌊log_ℓ n⌋ + ⌊log₂ ℓ⌋ + 1 (Theorem 4). The user interpolates only
    per-block counts, never the full n-vector; each Q&A round is one padded
    block-matrix device dispatch and one interpolation. ``known_count`` skips
    the Phase-0 count when the caller (e.g. the planner) already ran it.

    On a sharded dataplane the Q&A block gathers execute per shard (each
    gather stays inside one shard's tuple range) — but the block partition
    itself is PUBLIC and fixed by (n, ℓ, branching) alone, so the priced
    and measured ledger never moves with the shard count.
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    k_count, k_pat, k_fetch = jax.random.split(key, 3)

    # Phase 0: count occurrences (unless the caller already did)
    if known_count is None:
        ell, ledger = count_query(k_count, db, column, pattern, ledger=ledger,
                                  backend=be)
    else:
        ell = known_count
    if ell == 0:
        return [], [], ledger

    addresses = rounds.tree_rounds(
        be, db, [rounds.TreeJob(column, pattern, k_pat, ledger,
                                ell=ell, branching=branching)])[0]
    rows = rounds.fetch_round(
        be, db, [rounds.FetchJob(k_fetch, addresses, ledger,
                                 padded_rows)])[0]
    return rows, addresses, ledger
