"""Count query (paper §3.1, Algorithm 2; Theorem 1).

User sends a secret-shared predicate (O(1) communication — independent of n),
each cloud runs the accumulating automaton over the target attribute (nw work)
and returns ONE share; the user interpolates c' = deg+1 values (O(1) work).

Prefer ``repro.api.QueryClient.count`` — this free function remains as the
protocol implementation the client delegates to.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from ..costs import CostLedger
from ..engine import SecretSharedDB
from . import rounds
from ._common import get_backend


def count_query(key: jax.Array, db: SecretSharedDB, column: int, pattern: str,
                *, ledger: Optional[CostLedger] = None,
                backend="jnp"
                ) -> Tuple[int, CostLedger]:
    """COUNT(*) WHERE col = pattern — oblivious, one round.

    Thin wrapper over the batched count phase at B = 1: user shares the
    predicate, the cloud runs one fused AA dispatch, the user interpolates
    one count share per contacted cloud.
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    cnt = rounds.count_phase(
        be, db, [rounds.MatchJob(column, pattern, key, ledger)])[0]
    return cnt, ledger
