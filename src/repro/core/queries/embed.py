"""Batched oblivious embedding lookup — §3.2.1 selection at LM serving scale.

A token id is a one-hot row over the vocabulary: exactly the paper's unary
encoding. An LM inference step issues batch×seq of these lookups at once, so
the family is built batch-first like every other phase in this package:

* **One share program.** All of a step's one-hots are shared in ONE jitted
  program: per-token keys come from ``jax.random.fold_in`` (vmapped — each
  token keeps its own fresh polynomial, the §2.1 frequency-attack defence)
  and the degree-1 polynomial ``q_i(x) = onehot_i + a1_i·x`` is evaluated at
  all c points in one vectorized pass. No Python loop, no per-token
  ``shamir.share`` dispatch.
* **One contraction.** Every job's share matrix concatenates along the token
  axis and contracts against the shared table in ONE ``ss_matmul`` of shape
  ``(c, ΣB·n, V) · (c, V, D)`` per shard — the same cross-job fusion as
  ``rounds.fetch_fusion``, so a decode step costs exactly one kernel
  dispatch per shard.
* **Opt-in verification.** ``verify=True`` rides the OBSCURE-style
  redundant-share consistency check (``aggregate._verify_openings``) over
  each job's slice of the opened result; needs c >= degree+2 clouds.

Fixed-point codec: table values quantize at scale 2¹² into a signed range of
±2¹⁸ ≪ p/2, so the signed round-trip through F_p is exact; out-of-range
tables raise instead of silently wrapping mod p.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import dataplane, field, shamir
from ..costs import CostLedger
from ..dataplane import RelationLike, span
from ..shamir import Shares
from .aggregate import VerificationError, _verify_openings
from .rounds import stack_onehots

__all__ = [
    "QUANT_SCALE", "QUANT_RANGE", "quantize_to_field",
    "dequantize_from_field", "token_coeffs", "share_tokens", "EmbedJob",
    "embed_phase", "VerificationError",
]

# ---------------------------------------------------------------------------
# fixed-point codec
# ---------------------------------------------------------------------------

QUANT_SCALE = 4096.0                       # 2**12
QUANT_RANGE = float(1 << 18) / QUANT_SCALE  # ±64.0 — signed fixed-point range


def quantize_to_field(x: jax.Array) -> jax.Array:
    """float -> fixed-point F_p element (signed values wrap mod p).

    Raises ``ValueError`` when a value falls outside the signed fixed-point
    range ±2¹⁸/2¹² = ±64.0 — wrapping mod p would silently corrupt the
    table. The guard only runs on concrete (non-traced) inputs; inside a
    jit the caller is responsible for pre-validated tables.
    """
    x = jnp.asarray(x)
    try:
        amax = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) if x.size else 0.0
    except jax.errors.ConcretizationTypeError:  # traced: skip the host check
        amax = None
    if amax is not None and amax > QUANT_RANGE:
        raise ValueError(
            f"value magnitude {amax} exceeds the fixed-point range "
            f"±{QUANT_RANGE} (scale 2^12, signed range ±2^18); refusing to "
            f"wrap mod p — rescale the table first")
    q = jnp.round(x.astype(jnp.float32) * QUANT_SCALE).astype(jnp.int64)
    return (q % jnp.int64(int(field.P))).astype(field.DTYPE)


def dequantize_from_field(x: jax.Array) -> jax.Array:
    return field.from_signed(x).astype(jnp.float32) / QUANT_SCALE


# ---------------------------------------------------------------------------
# fused share generation — ONE jitted program for a whole step
# ---------------------------------------------------------------------------

def _token_coeffs(key: jax.Array, n_tokens: int, vocab: int) -> jax.Array:
    """Per-token degree-1 coefficients a1[i] = uniform(fold_in(key, i), (V,)).

    Traced inline by :func:`_onehot_share_program`; also exposed (jitted, via
    :func:`token_coeffs`) so the Pallas fused share-generation kernel can
    consume bit-identical randomness.
    """
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(n_tokens, dtype=jnp.uint32))
    return jax.vmap(lambda k: field.uniform(k, (vocab,)))(keys)   # (n, V)


@functools.partial(jax.jit, static_argnames=("vocab",))
def token_coeffs(key: jax.Array, tokens: jax.Array, *, vocab: int
                 ) -> jax.Array:
    return _token_coeffs(key, tokens.shape[0], vocab)


@functools.partial(jax.jit, static_argnames=("vocab", "n_shares"))
def _onehot_share_program(key: jax.Array, flat_tokens: jax.Array, *,
                          vocab: int, n_shares: int) -> jax.Array:
    """All one-hots of a step -> degree-1 share tensor (c, n, V), one jit.

    share[k, i, :] = onehot(token_i) + a1_i · x_k  with per-token fold_in
    keys — vectorized polynomial evaluation, no Python loop.
    """
    a1 = _token_coeffs(key, flat_tokens.shape[0], vocab)          # (n, V)
    onehot = jax.nn.one_hot(flat_tokens, vocab, dtype=field.DTYPE)
    xs = shamir.eval_points(n_shares)                             # (c,)
    ax = field.mul(a1[None, :, :], xs[:, None, None])
    return field.add(onehot[None], ax)


def share_tokens(key: jax.Array, tokens, *, vocab: int, n_shares: int,
                 be=None) -> Shares:
    """Share a whole step's token one-hots in one program -> Shares(c, n, V).

    Degree is fixed at 1 (the fast path's design point: the post-contraction
    degree 1 + table_degree must stay interpolatable from c shares). When
    the backend provides a fused ``share_onehot`` kernel (pallas), the
    one-hot build and polynomial evaluation fuse into one launch fed by the
    same ``token_coeffs`` randomness — bit-identical to the jnp program.
    """
    flat = jnp.asarray(tokens).reshape(-1)
    if flat.size == 0:
        raise ValueError("share_tokens needs at least one token")
    flat = flat.astype(jnp.int32)
    fused = getattr(be, "share_onehot", None)
    if fused is not None:
        a1 = token_coeffs(key, flat, vocab=vocab)
        return Shares(fused(flat, a1, n_shares=n_shares), 1)
    vals = _onehot_share_program(key, flat, vocab=vocab, n_shares=n_shares)
    return Shares(vals, 1)


# ---------------------------------------------------------------------------
# the job family
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EmbedJob:
    """One step's worth of lookups: token ids (any shape, flattened), the
    sharing key, the billing ledger, and the OBSCURE-style verify flag."""
    tokens: np.ndarray
    key: jax.Array
    ledger: CostLedger
    verify: bool = False


def embed_phase(be, rel: RelationLike, jobs: Sequence[EmbedJob]
                ) -> List[np.ndarray]:
    """All jobs' lookups fused into one contraction against the table.

    ``rel`` must carry a rank-3 ``(c, V, D)`` relation (see
    ``models.private_embed.as_embed_relation``); sharding splits the vocab
    axis and the per-shard mod-p partials sum exactly, so the result is
    bit-identical for every shard count S. Returns one float32
    ``(n_tokens_j, D)`` embedding matrix per job (dequantized).
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(rel)
    db = plane.db
    vals = db.relation.values
    if vals.ndim != 3:
        raise ValueError(
            f"embed_phase needs a (c, V, D) embedding relation, got a "
            f"rank-{vals.ndim} share tensor; wrap the table with "
            f"models.private_embed.as_embed_relation")
    c, v, d_dim = (int(s) for s in vals.shape)
    t_deg = db.relation.degree
    out_deg = 1 + t_deg
    if c < out_deg + 1:
        raise ValueError(
            f"opening a degree-{out_deg} lookup needs {out_deg + 1} clouds, "
            f"table has {c}")

    mats, spans, pos = [], [], 0
    with span(plane.stats, "user.share"):
        for job in jobs:
            flat = np.asarray(job.tokens).reshape(-1)
            if flat.size and (flat.min() < 0 or flat.max() >= v):
                raise ValueError(
                    f"token id out of range [0, {v}): "
                    f"[{int(flat.min())}, {int(flat.max())}]")
            mats.append(share_tokens(job.key, flat, vocab=v, n_shares=c,
                                     be=be).values)
            spans.append((pos, pos + int(flat.size)))
            pos += int(flat.size)
        stacked = mats[0] if len(mats) == 1 else stack_onehots(tuple(mats))
    # The jnp contraction takes the fixed table as resident int8 digits,
    # split once per shard view rather than on every step; any other
    # ss_matmul (Pallas, the MapReduce split) reads the uint32 shares.
    presplit = be.ss_matmul is field.matmul
    tables = ([plane.table_digits(sh.index) for sh in plane.shards]
              if presplit else None)
    fetched = plane.run_sum(
        lambda view, sh: be.ss_matmul(
            stacked[:, :, sh.lo:sh.hi],
            tables[sh.index] if presplit else view.relation.values),
        phase="embed")                                            # (c, N, D)
    if presplit:
        plane.stats.presplit_contractions += plane.n_shards
    out_sh = Shares(fetched, out_deg)

    # Table-1 billing, per job: one round; the shared one-hots go up, the
    # picked share rows come down, the clouds do the V×D contraction, the
    # user interpolates degree+1 shares per output element.
    for job, (lo, hi) in zip(jobs, spans):
        n_tok = hi - lo
        job.ledger.round()
        job.ledger.send(c * n_tok * v)
        job.ledger.cloud(n_tok * v * d_dim)
        job.ledger.recv(c * n_tok * d_dim)
        job.ledger.user((out_deg + 1) * n_tok * d_dim)
    for job, (lo, hi) in zip(jobs, spans):
        if job.verify:
            _verify_openings(job, [out_sh[lo:hi]], "embedding lookup")

    with span(plane.stats, "user.open"):
        opened = np.asarray(dequantize_from_field(shamir.interpolate(out_sh)))
    return [opened[lo:hi] for lo, hi in spans]
