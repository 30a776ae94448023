"""Oblivious joins on secret-shares (paper §3.3).

``pkfk_join`` (§3.3.1): reducer *j* (one per child tuple) string-matches the
child's join value against ALL parent join values, multiplies the 0/1 share
into each parent tuple and sums — only the unique matching parent survives.
In MapReduce terms the mapper fan-out (each parent tuple keyed 1..n_y) is the
broadcast inside the einsum; the reducer is the contraction over parents.
The match matrix is the ``ss_matmul``/``match_matrix`` hotspot: per word
position a (n_x × A) · (A × n_y) mod-p matmul, chained over positions.

``equijoin`` (§3.3.2): two *layers* of c clouds. The user interpolates both
join columns (2n·c′ values), derives common values + their tuple addresses,
then per common value the first layer obliviously fetches the matching tuples
(one-hot matrix fetch) and hands the *still-shared* results to its same-index
second-layer cloud, which emits the ℓx×ℓy concatenations. Clouds within a
layer never communicate.

Both are thin B = 1 wrappers over the round-structured batch engine
(``repro.core.queries.rounds``): a PK/FK join's reducer contraction is a
row-block of the same fused fetch ``ss_matmul`` the selection/range groups
ride (``join_match_round`` + ``fetch_fusion`` + ``join_emit_round``), and B
equijoins fuse their column-open, layer-1 fetches and layer-2 pair
interpolations per phase (``equijoin_rounds``). A join run here is
bit-identical (rows *and* ``CostLedger``) to the same join inside a
``QueryClient.run_batch`` group.

Prefer ``repro.api.QueryClient.join``; the canonical ``pkfk_join`` signature
is key-first like the rest of the suite (the key re-randomizes the outgoing
joined shares with owner-provisioned zero-sharings so transmitted shares
cannot be linked to the stored relation); the historical key-less positional
form is still accepted.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax

from ..costs import CostLedger
from ..engine import SecretSharedDB
from . import rounds
from ._common import get_backend


# ---------------------------------------------------------------------------
# §3.3.1 — PK/FK oblivious join
# ---------------------------------------------------------------------------

def pkfk_join(*args, **kwargs) -> Tuple[List[List[str]], CostLedger]:
    """X ⋈ Y on X.col_x = Y.col_y, where col_x is a primary key of X.

    Canonical call: ``pkfk_join(key, dbX, dbY, col_x, col_y)`` — key-first
    like every other query. The legacy key-less form
    ``pkfk_join(dbX, dbY, col_x, col_y)`` (positional or with ``col_x=``/
    ``col_y=`` keywords; no output re-randomization) is still accepted.
    """
    if args and isinstance(args[0], SecretSharedDB):  # key-less positional
        args = (kwargs.pop("key", None),) + args
    return _pkfk_join(*args, **kwargs)


def _pkfk_join(key: Optional[jax.Array], dbX: SecretSharedDB,
               dbY: SecretSharedDB, col_x: int, col_y: int, *,
               ledger: Optional[CostLedger] = None,
               backend="jnp"
               ) -> Tuple[List[List[str]], CostLedger]:
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    job = rounds.JoinJob(dbY, col_x, col_y, key, ledger)
    entries = rounds.join_match_round(be, dbX, [job])
    _, fetched = rounds.fetch_fusion(be, dbX, [], entries)
    rows = rounds.join_emit_round(dbX, [job], fetched)[0]
    return rows, ledger


# ---------------------------------------------------------------------------
# §3.3.2 — non-PK/FK oblivious equijoin (two cloud layers)
# ---------------------------------------------------------------------------

def equijoin(key: jax.Array, dbX: SecretSharedDB, dbY: SecretSharedDB,
             col_x: int, col_y: int, *,
             ledger: Optional[CostLedger] = None,
             padded_values: int = 0,
             backend="jnp"
             ) -> Tuple[List[List[str]], CostLedger]:
    """General equijoin; join values may repeat in BOTH relations.

    ``padded_values`` adds fake (no-op) join values to hide k (leakage
    discussion of §3.3.2).
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    rows = rounds.equijoin_rounds(be, dbX, [
        rounds.EquiJob(dbY, col_x, col_y, key, ledger,
                       padded_values=padded_values)])[0]
    return rows, ledger
