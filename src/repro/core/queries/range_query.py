"""Range queries via 2's-complement subtraction on shares (paper §3.4).

``ss_sub`` is Algorithm 6: a ripple subtract over secret-shared bit vectors
returning the secret-shared sign bit of ``B − A``. The carry chain multiplies
shares, so the polynomial degree grows ~2·t per bit; ``reduce_every`` applies
the paper's degree-reduction (re-sharing, [32]) between bit steps to keep the
required cloud count bounded — each reduction is an explicit protocol round.

``x ∈ [a, b]  ⟺  1 − sign(x−a) − sign(b−x) = 1``           (Eq. 1/2)

``range_count`` is Algorithm 5; ``range_select`` fetches the satisfying
tuples by reusing the selection machinery (§3.2) exactly as the paper says.

Both are thin B = 1 wrappers over the round-structured batch engine
(``repro.core.queries.rounds.range_rounds``): the SS-SUB ripple is
element-wise per bit, so B concurrent range queries stack their bit-vectors
into one carry chain — each bit position is ONE backend ``ripple_carry``
dispatch and each ``reduce_every`` boundary ONE degree-reduction re-share
for the whole batch. A query run here is bit-identical (result *and*
``CostLedger``) to the same query inside a ``QueryClient.run_batch`` group.
``ss_sub`` remains as the reference single-subtraction implementation (and
the parity oracle for the fused engine).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import shamir
from ..costs import CostLedger
from ..engine import SecretSharedDB
from ..shamir import Shares
from . import rounds
from ._common import get_backend


def _xor(a: Shares, b: Shares) -> Shares:
    """a ⊕ b = a + b − 2ab (share space)."""
    two_ab = (a * b).mul_public(2)
    return a + b - two_ab


def ss_sub(key: jax.Array, A: Shares, B: Shares, *,
           reduce_every: int = 0,
           ledger: Optional[CostLedger] = None) -> Shares:
    """Sign bit of B − A (Algorithm 6). A, B: (..., t_bits) LSB-first shares.

    reduce_every > 0 re-shares the carry down to the base degree every that
    many bit positions (degree-reduction rounds, counted in the ledger).
    """
    t_bits = A.shape[-1]
    one = Shares(jnp.ones_like(A.values[..., 0]), 0)

    def bit(s: Shares, i: int) -> Shares:
        return Shares(s.values[..., i], s.degree)

    # line 1-3: LSB handles the +1 of two's complement
    a0 = one - bit(A, 0)                                   # invert LSB
    b0 = bit(B, 0)
    carry = a0 + b0 - a0 * b0                              # OR: carry of +1
    rb = a0 + b0 - carry.mul_public(2)

    # line 4: ripple through the remaining bits
    for i in range(1, t_bits):
        if reduce_every and carry.degree > 1 and i % reduce_every == 0:
            key, sub = jax.random.split(key)
            carry = shamir.reduce_degree(sub, carry, target_degree=1)
            if ledger is not None:
                ledger.round()
                ledger.send(carry.n_shares * carry.n_shares)
        ai = one - bit(A, i)
        bi = bit(B, i)
        rb = _xor(ai, bi)
        new_carry = ai * bi + carry * rb
        rb = rb + carry - (carry * rb).mul_public(2)
        carry = new_carry
    return rb                                              # sign of B − A


def range_count(key: jax.Array, db: SecretSharedDB, column: int,
                lo: int, hi: int, *, ledger: Optional[CostLedger] = None,
                reduce_every: int = 0,
                backend="jnp"
                ) -> Tuple[int, CostLedger]:
    """COUNT(*) WHERE lo <= col <= hi (Algorithm 5, counting phase).

    B = 1 wrapper over the batched ripple engine: the backend's
    ``ripple_carry`` runs the whole carry chain, one dispatch per bit.
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    cnt = rounds.range_rounds(be, db, [
        rounds.RangeJob(column, lo, hi, key, ledger,
                        reduce_every=reduce_every)])[0]
    return cnt, ledger


def range_select(key: jax.Array, db: SecretSharedDB, column: int,
                 lo: int, hi: int, *, ledger: Optional[CostLedger] = None,
                 reduce_every: int = 0, padded_rows: Optional[int] = None,
                 backend="jnp"
                 ) -> Tuple[List[List[str]], List[int], CostLedger]:
    """Fetch all tuples with col ∈ [lo, hi] (Alg 5 "simple solution" path:
    per-tuple indicator bits -> addresses -> oblivious matrix fetch).

    B = 1 wrapper over ``range_rounds`` + the shared ``fetch_round`` — in a
    batch the fetch rides the cross-group fused ``ss_matmul``.
    """
    ledger = ledger if ledger is not None else CostLedger()
    be = get_backend(backend)
    k_ind, k_fetch = jax.random.split(key)
    addresses = rounds.range_rounds(be, db, [
        rounds.RangeJob(column, lo, hi, k_ind, ledger,
                        reduce_every=reduce_every, want_addresses=True)])[0]
    rows = rounds.fetch_round(be, db, [
        rounds.FetchJob(k_fetch, addresses, ledger, padded_rows)])[0]
    return rows, addresses, ledger
