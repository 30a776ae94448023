"""Verified secret-shared aggregation — SUM / AVG / MIN-MAX (OBSCURE-style).

The paper's query surface stops at count/selection/range/join; OBSCURE
(arXiv 2004.13115) shows the same Shamir-sharing model supports
information-theoretically secure aggregation *with result verification*: a
cheating cloud returning a garbage share is detected rather than silently
interpolated into a wrong answer. This module adds that family to the
round engine, batch-first like everything in :mod:`.rounds`:

  * :func:`agg_sum_phase`     — conditional / unconditional SUM for B jobs:
    the predicate match bits contract against the numeric value column in
    ONE ``ss_matmul`` per shard (per distinct value column), partial sums
    combining additively in F_p, one fused interpolation. AVG rides this
    phase for its numerator; the denominator reuses the §3.1 count phase
    (the client fuses it into the batch's existing count dispatch).
  * :func:`agg_minmax_rounds` — MIN/MAX for B jobs as a knockout tournament
    on the bitwise (two's-complement) column: each level compares candidate
    pairs with the §3.4 SS-SUB ripple-carry comparator (one fused
    ``ripple_segment`` dispatch per ``reduce_every`` boundary interval for
    the whole batch) and obliviously selects each winner as
    ``x₁ + s·(x₂ − x₁)``. Conditional jobs first mask non-matching rows to
    a public sentinel (+/− (2^(t−2) − 1)) so they can never win. Levels
    run on the gathered relation — like the tree engine's Q&A rounds — so
    the transcript is bit-identical for every shard count by construction;
    the match/mask step and the SUM contraction are the sharded cloud
    steps.

Numeric-domain contracts (documented, not enforceable on shares):
  * SUM/AVG open exact field sums. Where ``n · 2^(t−1)`` could pass the
    Mersenne-31 half-range, the value's bit planes are summed in limbs
    narrow enough that no limb's sum can (:func:`sum_limbs`), each limb
    opened apart and the limbs added on the host as integers; a relation
    past 2^29 tuples, where not even a one-bit limb is safe, is refused.
  * MIN/MAX comparisons subtract t-bit values; like the paper's SS-SUB,
    differences must fit in t bits. Conditional jobs additionally compare
    against the ±(2^(t−2) − 1) sentinel, so values should stay within
    one headroom bit of the column's width.

Verification (``verify=True`` per job) runs an OBSCURE-style consistency
round on every opened aggregate tensor: with r = c − (deg+1) redundant
clouds, the user cross-checks that each redundant share lies on the unique
degree-``deg`` polynomial through the first deg+1 shares
(:func:`repro.core.shamir.verify_consistency`) and raises
:class:`VerificationError` on any mismatch. The extra round and the c
checksum elements per opened tensor are billed to the job's ledger (and
priced identically by ``repro.api.planner.estimate_aggregate_cost``).
Scope: verification covers the cloud→user openings — any share tampered
after the last re-sharing round is caught; the cloud↔cloud degree-reduction
rounds themselves assume honest re-share participants (OBSCURE's full
checksum chain per round is future work, see ROADMAP).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dataplane, encoding, field, shamir
from ..costs import CostLedger
from ..dataplane import RelationLike, span
from ..shamir import Shares
from .rounds import (MatchJob, _batched_matcher, _open_on_host, _reshare,
                     _ripple_segmenter, _share_patterns, ripple_schedule,
                     _stack_columns, _stack_numeric, _within_clouds)

AGG_OPS = ("sum", "avg", "min", "max")


class VerificationError(RuntimeError):
    """A cloud's share failed the OBSCURE-style consistency check."""


# ---------------------------------------------------------------------------
# batch job descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AggJob:
    """One aggregation query's slot in a fused aggregation phase.

    ``value_column`` is the numeric (binary-form) column being aggregated;
    ``pred_column``/``pattern`` carry the optional equality predicate
    (None = aggregate over the whole relation). ``verify`` opts the job
    into the consistency round on its opened tensors.
    """
    value_column: int
    key: jax.Array
    ledger: CostLedger
    pred_column: Optional[int] = None
    pattern: Optional[str] = None
    verify: bool = False

    @property
    def conditional(self) -> bool:
        return self.pattern is not None


@dataclasses.dataclass
class SumJob(AggJob):
    """One SUM (or AVG numerator) slot in :func:`agg_sum_phase`."""


@dataclasses.dataclass
class MinMaxJob(AggJob):
    """One MIN/MAX slot in :func:`agg_minmax_rounds`.

    Jobs fused into one tournament must share the column bit-width and
    ``reduce_every`` (the comparator carry chains march in lockstep).
    """
    op: str = "min"
    reduce_every: int = 0

    def __post_init__(self):
        if self.op not in ("min", "max"):
            raise ValueError(f"MinMaxJob.op must be 'min' or 'max', "
                             f"got {self.op!r}")


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def sum_limbs(n: int, t_bits: int) -> List[int]:
    """Widths, LSB first, of the bit-plane limbs that a SUM over ``n``
    tuples of a t-bit column opens apart. Each limb's field sum has to lift
    back to the integer it stands for: the whole width where n·2^(t−1)
    stays inside the Mersenne-31 half-range (one opening), else limbs of
    the widest L with n·2^L ≤ 2^30, the top one carrying the sign bit.
    Raises ``ValueError`` past 2^29 tuples, where no limb fits."""
    if n << (t_bits - 1) < 1 << 30:
        return [t_bits]
    width = 30 - (n - 1).bit_length()
    if width < 1:
        raise ValueError(
            f"SUM over n={n} tuples may exceed the Mersenne-31 half-range "
            f"even one bit plane at a time — the field sum would no longer "
            f"be exact")
    full, rest = divmod(t_bits, width)
    return [width] * full + ([rest] if rest else [])


def _limb_weights(t_bits: int, limbs: Sequence[int]) -> jax.Array:
    """Public (t, limbs) bit weights lifting an LSB-first two's-complement
    bit sharing to a sharing of each limb's (centered) value: 2^(i−lo) for
    bit i of the limb starting at bit lo, the sign bit weighted
    −2^(t−1−lo) mod p. A public linear combination — each cloud applies it
    locally, degree unchanged."""
    w = np.zeros((t_bits, len(limbs)), np.int64)
    lo = 0
    for j, width in enumerate(limbs):
        for i in range(lo, lo + width):
            w[i, j] = 1 << (i - lo)
        lo += width
    w[t_bits - 1, -1] = int(field.P) - w[t_bits - 1, -1]
    return jnp.asarray(w, field.DTYPE)


def _centered(v: int) -> int:
    """Lift a field representative back to the signed integer it encodes."""
    p = int(field.P)
    return v - p if v > p // 2 else v


def _validate_numeric(db, jobs: Sequence[AggJob], what: str) -> int:
    t_all = []
    for j in jobs:
        if j.value_column not in db.numeric:
            raise ValueError(f"column {j.value_column} was not outsourced "
                             f"in binary form")
        t_all.append(db.numeric_bits[j.value_column])
    if len(set(t_all)) != 1:
        raise ValueError(f"a fused {what} needs a uniform value-column "
                         f"bit width across its jobs (group them)")
    return t_all[0]


def _verify_openings(job: AggJob, tensors: Sequence[Shares],
                     what: str) -> None:
    """The OBSCURE-style verification round for one job: one extra round in
    which the user cross-checks every redundant cloud's share of each opened
    tensor against the polynomial the first deg+1 shares determine."""
    job.ledger.round()
    for s in tensors:
        t1 = s.degree + 1
        c = s.n_shares
        if c < t1 + 1:
            raise VerificationError(
                f"verify=True needs at least degree+2 = {t1 + 1} clouds to "
                f"cross-check the {what} opening (degree {s.degree}); "
                f"have {c}")
        n_elems = int(np.prod(s.shape, dtype=np.int64)) if s.shape else 1
        job.ledger.recv(c)
        job.ledger.user((c - t1) * t1 * n_elems)
        ok = np.asarray(shamir.verify_consistency(s))
        if not bool(ok.all()):
            raise VerificationError(
                f"{what} verification failed: a cloud's response share is "
                f"inconsistent with the degree-{s.degree} sharing the "
                f"honest clouds define")


# ---------------------------------------------------------------------------
# SUM / AVG numerator — one fused contraction round
# ---------------------------------------------------------------------------

def agg_sum_phase(be, db: RelationLike, jobs: Sequence[SumJob]
                  ) -> List[int]:
    """Exact signed SUM for B jobs: ONE cloud step (one dispatch per
    shard), partial sums reduced mod p across shards, one fused
    interpolation, optional verification round.

    Conditional jobs match their predicate with the fused AA matcher and
    contract the match bits against the value column via ``ss_matmul``
    (one matmul per distinct value column); unconditional jobs sum the
    value column directly. Both ride the same ``run_sum`` dispatch set.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    c = db.n_shares
    n = db.n_tuples
    t_bits = _validate_numeric(db, jobs, "agg_sum_phase")
    limbs = sum_limbs(n, t_bits)
    cond = [i for i, j in enumerate(jobs) if j.conditional]
    free = [i for i, j in enumerate(jobs) if not j.conditional]
    with span(plane.stats, "user.share"):
        p_all = (_share_patterns(db, [jobs[i] for i in cond]) if cond
                 else None)
    w = db.relation.values.shape[-2]
    match_deg = ((db.relation.degree + p_all.degree) * w if cond else 0)
    weights = _limb_weights(t_bits, limbs)[None, None]     # (1, 1, t, m)

    def limb_planes(v, vc):                                # (c, n_s, m)
        return field.sum_(field.mul(v.numeric[vc].values[..., None],
                                    weights), axis=2)

    # one ss_matmul per distinct value column of the conditional jobs
    by_vcol: dict = {}
    for k, i in enumerate(cond):
        by_vcol.setdefault(jobs[i].value_column, []).append(k)

    def one(v, sh):
        parts = []
        if cond:
            bits = _batched_matcher(be)(
                _stack_columns(v, [jobs[i].pred_column
                                   for i in cond]).values,
                p_all.values)                              # (c, Bc, n_s)
            out: List[Optional[jax.Array]] = [None] * len(cond)
            for vc, ks in by_vcol.items():
                prod = be.ss_matmul(bits[:, jnp.asarray(ks)],
                                    limb_planes(v, vc))    # (c, |ks|, m)
                for r, k in enumerate(ks):
                    out[k] = prod[:, r]
            parts.append(jnp.stack(out, axis=1))           # (c, Bc, m)
        if free:
            cols = jnp.stack([limb_planes(v, jobs[i].value_column)
                              for i in free], axis=1)      # (c, Bf, n_s, m)
            parts.append(field.sum_(cols, axis=2))         # (c, Bf, m)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                axis=1)

    sums_flat = plane.run_sum(one, phase="match")          # (c, Bc+Bf, m)
    per_job: List[Optional[Shares]] = [None] * len(jobs)
    for k, i in enumerate(cond):
        per_job[i] = Shares(sums_flat[:, k],
                            match_deg + db.numeric[jobs[i].value_column]
                            .degree)
    for k, i in enumerate(free):
        per_job[i] = Shares(sums_flat[:, len(cond) + k],
                            db.numeric[jobs[i].value_column].degree)
    opened = _open_on_host(plane.stats, per_job)

    per_q = codec.word_length * codec.alphabet_size
    m = len(limbs)
    for i, j in enumerate(jobs):
        j.ledger.round()
        if j.conditional:
            j.ledger.send(c * per_q)
            j.ledger.cloud(n * (per_q + t_bits))
        else:
            j.ledger.cloud(n * t_bits)
        j.ledger.recv(c * m)
        j.ledger.user((per_job[i].degree + 1) * m)
    for i, j in enumerate(jobs):
        if j.verify:
            _verify_openings(j, [per_job[i]], "SUM")
    offsets = np.cumsum([0] + limbs[:-1])
    return [sum(_centered(int(v)) << int(lo)
                for v, lo in zip(np.ravel(opened[i]), offsets))
            for i in range(len(jobs))]


# ---------------------------------------------------------------------------
# MIN / MAX — sentinel mask + knockout tournament on the SS-SUB comparator
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("pairs",))
def _pair_level(cand, is_min, pairs: int):
    """One tournament level's fixed pairing (2i, 2i+1) of (c, B, k, t)
    candidates. SS-SUB(lhs, rhs) opens [rhs < lhs]: min wants s = [x2 < x1]
    (lhs=x1), max wants s = [x1 < x2] (lhs=x2); either way the winner is
    x1 + s·(x2 − x1)."""
    x1 = cand[:, :, 0:2 * pairs:2]                          # (c,B,pairs,t)
    x2 = cand[:, :, 1:2 * pairs:2]
    return (x1, x2, jnp.where(is_min, x1, x2), jnp.where(is_min, x2, x1))


@jax.jit
def _select_winner(x1, x2, s_bits):
    """The oblivious select x₁ + s·(x₂ − x₁), share-wise."""
    return field.add(x1, field.mul(s_bits[..., None], field.sub(x2, x1)))


def agg_minmax_rounds(be, db: RelationLike, jobs: Sequence[MinMaxJob]
                      ) -> List[Tuple[Optional[int], Optional[int]]]:
    """MIN/MAX for B jobs, every tournament level fused across the batch.

    Returns ``(value, count)`` per job: ``count`` is the opened predicate
    cardinality for conditional jobs (None otherwise); ``value`` is None
    when a conditional job matched nothing (the tournament would open the
    sentinel). The final level's winner opens at its native comparator
    degree — no trailing re-share — so a share tampered anywhere after the
    last reduction fails verification.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    c = db.n_shares
    n = db.n_tuples
    d = db.base_degree
    t_bits = _validate_numeric(db, jobs, "agg_minmax_rounds")
    if t_bits < 2:
        raise ValueError("MIN/MAX needs a >= 2-bit value column")
    if len({j.reduce_every for j in jobs}) != 1:
        raise ValueError("a fused agg_minmax_rounds needs uniform "
                         "reduce_every across its jobs (group them)")
    reduce_every = jobs[0].reduce_every
    b = len(jobs)
    w = codec.word_length
    per_q = w * codec.alphabet_size
    cond = [i for i, j in enumerate(jobs) if j.conditional]

    # round 1: predicates travel up; the final bits come back in the same
    # logical round once the tournament's re-share rounds are done.
    for j in jobs:
        j.ledger.round()
        if j.conditional:
            j.ledger.send(c * per_q)

    # every job's key splits (pattern, reduction-chain); the fused
    # reduction chain seeds from the first job, as in range_phase —
    # re-share randomness never changes opened values.
    split_keys = [jax.random.split(j.key) for j in jobs]
    red_key = split_keys[0][1]

    counts: Optional[Shares] = None
    masked_by_pos: dict = {}
    if cond:
        cond_jobs = [jobs[i] for i in cond]
        with span(plane.stats, "user.share"):
            p_all = _share_patterns(db, [
                MatchJob(j.pred_column, j.pattern, split_keys[i][0],
                         j.ledger) for i, j in zip(cond, cond_jobs)])
        match_deg = (db.relation.degree + p_all.degree) * w
        bits = Shares(plane.run_concat(
            lambda v, sh: _batched_matcher(be)(
                _stack_columns(v, [j.pred_column
                                   for j in cond_jobs]).values,
                p_all.values), axis=2, phase="match"),
            match_deg)                                      # (c, Bc, n)
        counts = Shares(field.sum_(bits.values, axis=2), match_deg)
        # sentinel mask: non-matching rows become the op's losing extreme
        # (a public constant, so masking is cloud-local share arithmetic):
        # masked = m·(x − s) + s.
        bound = (1 << (t_bits - 2)) - 1
        sent = np.stack([encoding.encode_number_bits(
            bound if j.op == "min" else -bound, t_bits)
            for j in cond_jobs])                            # (Bc, t)
        sent_b = jnp.asarray(sent, field.DTYPE)[None, :, None, :]
        x = _stack_numeric(db, [j.value_column for j in cond_jobs])
        delta = field.sub(x.values, jnp.broadcast_to(sent_b,
                                                     x.values.shape))
        masked = field.add(field.mul(bits.values[..., None], delta),
                           jnp.broadcast_to(sent_b, x.values.shape))
        red_key, sub = jax.random.split(red_key)
        masked = _reshare(plane, sub, Shares(masked, match_deg + x.degree),
                          d, "the MIN/MAX sentinel mask")
        for i, j in enumerate(cond_jobs):
            j.ledger.round()                 # the mask re-share round
            j.ledger.send(c * c)
            j.ledger.cloud(n * (per_q + t_bits))
            masked_by_pos[cond[i]] = masked.values[:, i]
    for i, j in enumerate(jobs):
        if not j.conditional:
            j.ledger.cloud(n * t_bits)

    cand = jnp.stack(
        [masked_by_pos[i] if i in masked_by_pos
         else db.numeric[jobs[i].value_column].values
         for i in range(b)], axis=1)                        # (c, B, n, t)
    cand_deg = d

    # knockout tournament: global fixed pairing (2i, 2i+1) per level, odd
    # leftover carried unpaired; each level is one batched SS-SUB ripple
    # (sign s = [loser-side < winner-side]) plus the oblivious select
    # x₁ + s·(x₂ − x₁). Levels run on the gathered relation, like tree
    # Q&A rounds — identical transcript for every shard count.
    segment = _ripple_segmenter(be)
    is_min = jnp.asarray([j.op == "min" for j in jobs],
                         bool)[None, :, None, None]
    k = n
    while k > 1:
        pairs = k // 2
        x1, x2, lhs, rhs = _pair_level(cand, is_min, pairs)
        carry = None
        carry_deg = 0
        s_bits = None
        for s0, s1, seg_deg in ripple_schedule(t_bits, reduce_every,
                                               cand_deg):
            if carry_deg:
                red_key, sub = jax.random.split(red_key)
                carry = _reshare(plane, sub, Shares(carry, carry_deg), 1,
                                 "the comparator's carry").values
                carry_deg = 1
                for j in jobs:
                    j.ledger.round()
                    j.ledger.send(c * c)
            with span(plane.stats, "cloud.ripple"):
                s_bits, carry = segment(lhs[..., s0:s1], rhs[..., s0:s1],
                                        carry)
            plane.stats.ripple_segments += 1
            carry_deg = seg_deg
        win = _select_winner(x1, x2, s_bits)
        win_deg = carry_deg + cand_deg
        for j in jobs:
            j.ledger.cloud(2 * pairs * t_bits)
        if 2 * pairs < k:
            win = jnp.concatenate([win, cand[:, :, 2 * pairs:]], axis=2)
        k = win.shape[2]
        if k > 1:
            # inter-level re-share back to the base degree (one round);
            # the FINAL level opens at its native degree instead, so a
            # post-reduction tamper is visible to verification.
            red_key, sub = jax.random.split(red_key)
            cand = _reshare(plane, sub, Shares(win, win_deg), d,
                            "the tournament's winners").values
            cand_deg = d
            for j in jobs:
                j.ledger.round()
                j.ledger.send(c * c)
        else:
            cand = win
            cand_deg = win_deg

    _within_clouds(cand_deg, c, "the MIN/MAX value")
    val_parts = [Shares(cand[:, i, 0], cand_deg) for i in range(b)]
    cnt_parts = {i: Shares(counts.values[:, kk], counts.degree)
                 for kk, i in enumerate(cond)}
    opened = _open_on_host(plane.stats,
                           val_parts + [cnt_parts[i] for i in cond])

    for i, j in enumerate(jobs):
        j.ledger.recv(c * t_bits)
        j.ledger.user((cand_deg + 1) * t_bits)
        if j.conditional:
            j.ledger.recv(c)
            j.ledger.user(counts.degree + 1)
    for i, j in enumerate(jobs):
        if j.verify:
            tensors = [val_parts[i]]
            if j.conditional:
                tensors.append(cnt_parts[i])
            _verify_openings(j, tensors, j.op.upper())

    out: List[Tuple[Optional[int], Optional[int]]] = []
    cnt_at = {i: b + kk for kk, i in enumerate(cond)}
    for i, j in enumerate(jobs):
        val = encoding.decode_number_bits(np.asarray(opened[i]))
        if j.conditional:
            cnt = int(opened[cnt_at[i]])
            out.append((val if cnt > 0 else None, cnt))
        else:
            out.append((val, None))
    return out
