"""Round-structured, batch-first protocol core for §3.1/§3.2 queries.

Every selection/count protocol is decomposed here into explicit *rounds*,
each round being one pure cloud step (a single fused device dispatch over a
stack of B concurrent queries) followed by one user step (a single Lagrange
interpolation over everything that round returned). The per-query free
functions in ``select.py`` / ``count.py`` are thin wrappers that run these
engines with B = 1, so a batch of B queries and B sequential queries execute
*the same code* — per-query ``CostLedger`` totals and result rows are
bit-identical by construction (asserted by ``tests/test_batch.py``).

Protocol phases (one function per phase; a phase is one round except the
tree engine, which loops):

  * :func:`count_phase`     — §3.1 Alg 2 over B predicates: one
    ``aa_match_batch`` dispatch, one interpolation of the B count shares.
  * :func:`one_tuple_round` — §3.2.1 Alg 3 map round over B (verified ℓ=1)
    predicates: one dispatch, one interpolation of B tuples.
  * :func:`match_all_round` — §3.2.2 one-round Phase 1: one dispatch, one
    interpolation of the B·n match-bit matrix.
  * :func:`tree_rounds`     — §3.2.2 Alg 4 Q&A rounds, *lockstep over the
    batch*: per round, every query's active blocks are padded to a uniform
    height and stacked into one block matrix — a single dispatch and a
    single interpolation replace the historical per-block Python loop.
    Address fetches (Alg 4 line 14) discovered in a round are likewise
    batched into one dispatch + one interpolation.
  * :func:`fetch_round`     — §3.2.2 Phase 2 oblivious fetch: the B padded
    one-hot matrices are stacked row-wise and multiplied against the
    relation in one fused ``ss_matmul``.
  * :func:`range_phase` / :func:`range_rounds` — §3.4 Alg 5/6 over B range
    predicates: the B queries' endpoint/column bit-vectors (×2 directions,
    Eq. 2) stack into ONE ``(c, 2B, n, t)`` SS-SUB carry chain — one
    backend ``ripple_carry`` dispatch per bit-round, one degree-reduction
    re-share per ``reduce_every`` boundary *for the whole batch*.
  * :func:`join_match_round` / :func:`join_emit_round` — §3.3.1 PK/FK joins
    as rounds: the per-join match matrices become :class:`FetchEntry` rows
    of the shared fetch matmul (cross-group fusion), the re-randomized
    outputs interpolate in one fused user step per degree class.
  * :func:`equijoin_rounds` — §3.3.2 over B equijoin jobs: one fused
    column-open interpolation, all layer-1 X-side fetch matrices in one
    ``ss_matmul`` (Y-side fused per distinct right relation), and the
    layer-2 pair interpolations fused per degree class.
  * :func:`fetch_fusion`    — the cross-group fetch: every matrix that
    multiplies the relation this round (one_round / tree / range one-hots
    *and* join match matrices; a zero-match one_round/range query
    contributes a 0-row block) stacks into a single ``ss_matmul``
    dispatch. Tree queries that learned ℓ=0 in the count phase skip the
    fetch entirely, exactly as a solo run does.

Ledgers record *protocol* cost (each query's own blocks/rows, Table 1
units), never the padding the fused dispatch adds — padding is an execution
artifact of batching, invisible to the user↔cloud transcript.

Every function here accepts either a plain :class:`SecretSharedDB` or a
:class:`~repro.core.dataplane.ShardedRelation`. Cloud steps route through
the dataplane: the engine emits one dispatch descriptor per tuple-axis
shard and the relation's placement policy executes and reduces them
(match bits and ripple planes concatenate; count / fetch-matmul partial
sums combine additively in F_p). Reduction is exact modular arithmetic, so
the user↔cloud transcript — rounds, opened values, ledgers — is
bit-identical for every shard count; S is purely an execution knob.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import automata, dataplane, encoding, field, shamir
from ..costs import CostLedger
from ..dataplane import RelationLike, span
from ..engine import SecretSharedDB
from ..partition import split_bounds
from ..shamir import Shares


# ---------------------------------------------------------------------------
# batch job descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MatchJob:
    """One query's slot in a predicate-match phase (count / select).

    ``spec`` selects the matcher strategy: ``None`` is the exact-word
    equality chain; a :class:`~repro.core.encoding.PatternSpec` lowers the
    job onto the pattern engine — ``masked`` rides the very same full-width
    chain (only the pattern encoding differs), ``prefix`` the truncated
    k-chain, ``suffix``/``contains`` the sliding-window step.
    """
    column: int
    pattern: str
    key: jax.Array          # key for sharing this query's predicate
    ledger: CostLedger
    spec: Optional[encoding.PatternSpec] = None


@dataclasses.dataclass
class TreeJob(MatchJob):
    """One query's slot in the tree-selection Q&A engine (ℓ ≥ 1 known)."""
    ell: int = 1
    branching: Optional[int] = None


@dataclasses.dataclass
class FetchJob:
    """One query's slot in the fused oblivious-fetch round."""
    key: jax.Array
    addresses: Sequence[int]
    ledger: CostLedger
    padded_rows: Optional[int] = None


@dataclasses.dataclass
class RangeJob:
    """One query's slot in the batched §3.4 ripple (Algorithms 5/6).

    ``want_addresses`` distinguishes RangeSelect (the user interpolates all
    n indicator bits and learns addresses) from RangeCount (only the summed
    count travels back). Jobs fused into one :func:`range_phase` must share
    the column bit-width and ``reduce_every`` (the carry chains march in
    lockstep).
    """
    column: int
    lo: int
    hi: int
    key: jax.Array
    ledger: CostLedger
    reduce_every: int = 0
    want_addresses: bool = False


@dataclasses.dataclass
class JoinJob:
    """One PK/FK join's slot in the batched §3.3.1 round structure.

    ``match_method`` picks the backend *execution* of the nx×ny match
    matrix: ``"chain"`` multiplies W per-position one-hot dot sets
    sequentially (Table 3 order); ``"aggregate"`` contracts the flattened
    (W·A) encodings in ONE ``ss_matmul`` and applies the §3.1 equality
    indicator share-side. Both produce the same secrets at the same degree
    (2tW), so transcripts and ledgers are identical — the planner prices
    the choice by backend launch count.
    """
    right: SecretSharedDB
    col_x: int
    col_y: int
    key: Optional[jax.Array]
    ledger: CostLedger
    match_method: str = "chain"


@dataclasses.dataclass
class EquiJob:
    """One general-equijoin's slot in the batched §3.3.2 round structure."""
    right: SecretSharedDB
    col_x: int
    col_y: int
    key: jax.Array
    ledger: CostLedger
    padded_values: int = 0


@dataclasses.dataclass
class FetchEntry:
    """One raw row-block of the cross-group fused fetch matmul.

    ``values`` are raw share rows (c, r, n) multiplying the relation;
    ``degree`` is their sharing degree (one-hot fetch rows are base-degree,
    join match-matrix rows carry the AA product degree). The fused dispatch
    is degree-agnostic — degrees matter only when the output is split back.
    """
    values: jax.Array
    degree: int


# ---------------------------------------------------------------------------
# shared user/cloud helpers
# ---------------------------------------------------------------------------

def _batched_matcher(be):
    """Backend's fused stacked-predicate matcher (deferred registry import
    keeps core below ``repro.api`` in the layering)."""
    from ...api import backends as _registry
    return _registry.batched_matcher(be)


def _ripple_stepper(be):
    """Backend's fused SS-SUB bit step (deferred import, as above)."""
    from ...api import backends as _registry
    return _registry.ripple_stepper(be)


def _ripple_segmenter(be):
    """Backend's fused SS-SUB segment (k bit steps, one dispatch)."""
    from ...api import backends as _registry
    return _registry.ripple_segmenter(be)


def _batched_match_matrix(be):
    """Backend's stacked all-pairs matcher (deferred import, as above)."""
    from ...api import backends as _registry
    return _registry.batched_match_matrix(be)


def _slide_matcher(be):
    """Backend's stacked sliding-window matcher (deferred import, as
    above) — raw window-chain products for suffix/substring patterns."""
    from ...api import backends as _registry
    return _registry.slide_matcher(be)


def _aggregate_matcher(be):
    """Backend's aggregation-form all-pairs matcher: the §3.1 "aggregate"
    method promoted to a planner-priced join execution choice."""
    from ...api import backends as _registry
    return _registry.aggregate_match_matrix(be)


def _share_one_hot(key: jax.Array, db: SecretSharedDB,
                   addresses: Sequence[int],
                   n_rows: Optional[int] = None) -> Shares:
    """User step: an ℓ'×n one-hot fetch matrix shared at base degree.

    ``n_rows`` ≥ ℓ pads with all-zero rows (they fetch nothing) — the
    §3.2.2 output-size defence. Every fetch matrix in the suite (selection,
    range, equijoin layer 1) is built here so its sharing stays uniform.
    """
    n = db.n_tuples
    rows = len(addresses) if n_rows is None else max(n_rows, len(addresses))
    m_host = np.zeros((rows, n), dtype=np.uint32)
    for r, a in enumerate(addresses):
        m_host[r, a] = 1
    return encoding.share_encoded(key, m_host, n_shares=db.n_shares,
                                  degree=db.base_degree)


def _open_on_host(sink, parts: Sequence[Shares]) -> List[np.ndarray]:
    """User step: open many share tensors. Only the degree+1 shares the
    user needs leave the clouds, and the Lagrange sum runs on the user's
    host copy of them (``shamir.interpolate_host``), so opening compiles
    no device program. Returns decoded numpy arrays in input order.

    Runs under the span ``user.open`` charged to ``sink`` (a plane's
    ``DispatchStats``); its seconds include the host's wait for the
    device to produce the bytes it copies."""
    with span(sink, "user.open"):
        return [shamir.interpolate_host(s) for s in parts]


def _within_clouds(degree: int, c: int, what: str) -> None:
    """Refuse to re-share or open a sharing that c clouds cannot hold: a
    degree-D polynomial needs D + 1 points."""
    if degree > c - 1:
        raise ValueError(f"{what} reaches degree {degree}; {c} clouds "
                         f"re-share or open at most degree {c - 1}")


@contextlib.contextmanager
def _resharing(plane, degree: int, c: int, what: str):
    """The host's side of one re-sharing round: a sharing above degree
    c − 1 is refused first, the round's dispatch runs under the span
    ``cloud.reshare``, and it counts in the plane's
    ``DispatchStats.reshares``."""
    _within_clouds(degree, c, what)
    with span(plane.stats, "cloud.reshare"):
        yield
    plane.stats.reshares += 1


def _reshare(plane, key: jax.Array, shares: Shares, target_degree: int,
             what: str) -> Shares:
    """The protocol's re-sharing round (``shamir.reduce_degree``)."""
    with _resharing(plane, shares.degree, shares.n_shares, what):
        return shamir.reduce_degree(key, shares, target_degree=target_degree)


@functools.partial(jax.jit, static_argnames=("degree", "target_degree"))
def _split_reshare(key: jax.Array, values: jax.Array, *, degree: int,
                   target_degree: int) -> Tuple[jax.Array, jax.Array]:
    """``key, sub = jax.random.split(key)`` and the re-share of a
    degree-``degree`` sharing with ``sub``, in one program; returns
    ``(values, key)``."""
    key, sub = jax.random.split(key)
    return shamir.reduce_degree(sub, Shares(values, degree),
                                target_degree=target_degree).values, key


def _reshare_next(plane, key: jax.Array, shares: Shares, target_degree: int,
                  what: str) -> Tuple[Shares, jax.Array]:
    """:func:`_reshare` with the key of a reduction chain: the chain's key
    splits, the round takes the second half, and the first comes back as
    the chain's next key, all in the round's one dispatch."""
    with _resharing(plane, shares.degree, shares.n_shares, what):
        values, key = _split_reshare(key, shares.values,
                                     degree=shares.degree,
                                     target_degree=target_degree)
    return Shares(values, target_degree), key


def _share_patterns(db: SecretSharedDB, jobs: Sequence[MatchJob]) -> Shares:
    """User step: encode + share every job's predicate -> (c, B, W|k, A).

    Exact jobs encode the full terminator-padded word; ``masked`` specs the
    full-width masked pattern (wildcard rows are all-ones); tile specs
    (prefix/suffix/contains) the length-k pattern tile. All jobs in one
    stack must share an encoding width — the engine groups them so.
    """
    codec = db.codec
    vals = []
    for j in jobs:
        s = getattr(j, "spec", None)
        if s is None:
            enc = codec.encode_word(j.pattern)
        elif s.kind == "masked":
            enc = encoding.encode_pattern_word(codec, s)
        else:
            enc = encoding.encode_pattern_tile(codec, s)
        vals.append(encoding.share_encoded(
            j.key, enc, n_shares=db.n_shares, degree=db.base_degree).values)
    return Shares(jnp.stack(vals, axis=1), db.base_degree)


def _needs_pattern_engine(jobs: Sequence[MatchJob]) -> bool:
    """True if any job leaves the full-width chain (``masked`` rides the
    classic exact-match stack unchanged; the tile kinds do not)."""
    return any(getattr(j, "spec", None) is not None
               and j.spec.kind in ("prefix", "suffix", "contains")
               for j in jobs)


def match_phase_cost(spec: Optional[encoding.PatternSpec], *, n: int, c: int,
                     w: int, a: int, col_degree: int = 1,
                     pat_degree: int = 1) -> Dict[str, int]:
    """Table-1-style cost atoms for one predicate's match phase.

    ``send``/``cloud`` are the pattern upload and the per-tuple automata
    work; ``degree`` the final match-bit degree (the user interpolates
    ``degree + 1`` shares per opened element); the ``reduce_*`` atoms are
    the CONTAINS degree-reduction re-share round (zero unless M > 1).
    ``spec=None`` (exact equality) and ``masked`` price the full-width
    chain. The round engine charges these atoms verbatim and the planner
    prices with the same function, so ``explain()`` stays exact for the
    pattern family.
    """
    t2 = col_degree + pat_degree
    none = dict(reduce_rounds=0, reduce_send=0, reduce_cloud=0)
    if spec is None or spec.kind == "masked":
        return dict(send=c * w * a, cloud=n * w * a, degree=t2 * w, **none)
    k = spec.length
    m = w - k + 1
    if spec.kind == "prefix" or m == 1:
        # truncated k-chain; a single-window slide degenerates to the same
        return dict(send=c * k * a, cloud=n * k * a, degree=t2 * k, **none)
    if spec.kind == "suffix":
        return dict(send=c * k * a, cloud=n * m * k * a + n * m,
                    degree=t2 * k + col_degree, **none)
    if spec.kind != "contains":
        raise ValueError(f"unknown pattern kind: {spec.kind!r}")
    return dict(send=c * k * a, cloud=n * m * k * a, degree=m,
                reduce_rounds=1, reduce_send=c * c, reduce_cloud=n * m)


def _charge_match_phase(db: SecretSharedDB, job: MatchJob
                        ) -> Dict[str, int]:
    """Charge one job's match-phase atoms (round + send + cloud + the
    CONTAINS reduction round if any); returns the atoms for the caller's
    recv/user charges."""
    codec = db.codec
    cost = match_phase_cost(getattr(job, "spec", None), n=db.n_tuples,
                            c=db.n_shares, w=codec.word_length,
                            a=codec.alphabet_size,
                            col_degree=db.relation.degree,
                            pat_degree=db.base_degree)
    job.ledger.round()
    job.ledger.send(cost["send"])
    job.ledger.cloud(cost["cloud"])
    if cost["reduce_rounds"]:
        job.ledger.round(cost["reduce_rounds"])
        job.ledger.send(cost["reduce_send"])
        job.ledger.cloud(cost["reduce_cloud"])
    return cost


class _MatcherPlan:
    """Strategy layer of the refactored matcher pipeline.

    Groups a mixed batch of :class:`MatchJob` so each group's per-tuple
    match bits cost ONE backend dispatch per round:

      * ``("full", W)``   — exact + masked patterns: the classic full-width
        ``aa_match_batch`` chain;
      * ``("prefix", k)`` — truncated k-chains over ``col[..., :k, :]``,
        the same op at width k;
      * ``("slide", k)``  — suffix + substring patterns of length k: raw
        window products from ONE ``aa_slide_batch`` dispatch. The suffix
        terminator factor and the CONTAINS window count are linear
        share-local post-processing, so both kinds of the same k share the
        dispatch; CONTAINS (M > 1) additionally runs one degree-reduction
        re-share of its window count — the family's only extra
        communication round — before the share-local zero test.
    """

    def __init__(self, plane: "dataplane.ShardedRelation",
                 jobs: Sequence[MatchJob]):
        db = self.db = plane.db
        self.jobs = list(jobs)
        self.w = db.codec.word_length
        full: List[int] = []
        prefix: Dict[int, List[int]] = {}
        slide: Dict[int, List[int]] = {}
        for i, j in enumerate(self.jobs):
            s = getattr(j, "spec", None)
            if s is None or s.kind == "masked":
                full.append(i)
            elif s.kind == "prefix":
                prefix.setdefault(s.length, []).append(i)
            else:
                slide.setdefault(s.length, []).append(i)
        self.groups: List[Tuple[str, int, List[int]]] = []
        if full:
            self.groups.append(("full", self.w, full))
        for k in sorted(prefix):
            self.groups.append(("prefix", k, prefix[k]))
        for k in sorted(slide):
            self.groups.append(("slide", k, slide[k]))
        with span(plane.stats, "user.share"):
            self.pats = [_share_patterns(db, [self.jobs[i] for i in idxs])
                         for _, _, idxs in self.groups]

    def _shard_values(self, be, v: SecretSharedDB, sh):
        """Cloud step on one shard: per group ``(local job idxs, local
        bits, contains job idxs, contains window counts)`` — local bits are
        complete on this shard; window counts still need the cross-shard
        reduction."""
        out = []
        for (kind, k, idxs), pats in zip(self.groups, self.pats):
            cols = _stack_columns(v, [self.jobs[i].column for i in idxs])
            if kind == "full":
                out.append((idxs, _batched_matcher(be)(
                    cols.values, pats.values), [], None))
                continue
            if kind == "prefix":
                out.append((idxs, _batched_matcher(be)(
                    prefix_tile(cols.values, k=k), pats.values), [], None))
                continue
            win = _slide_matcher(be)(cols.values, pats.values)  # (c,Bg,ns,M)
            if self.w - k + 1 == 1:
                # one window: the chain product IS the bit, either kind
                out.append((idxs, window_bits(win, cols.values), [], None))
                continue
            suf = tuple(b for b, i in enumerate(idxs)
                        if self.jobs[i].spec.kind == "suffix")
            con = tuple(b for b, i in enumerate(idxs)
                        if self.jobs[i].spec.kind == "contains")
            win_suf, term, win_con = window_bits(win, cols.values, suf=suf,
                                                 con=con, k=k)
            bits = None
            if suf:
                # suffix ⟺ some window matches AND everything after it is
                # terminator padding. Windows are mutually exclusive (a
                # real pattern char never matches the terminator), so the
                # linear sum of window·terminator products is the exact
                # 0/1 bit.
                ones = jnp.ones(term.shape[:-1] + (1,), field.DTYPE)
                bits = field.sum_(
                    field.mul(win_suf,
                              jnp.concatenate([term, ones], axis=-1)),
                    axis=-1)
            p_cnt = field.sum_(win_con, axis=-1) if con else None
            out.append(([idxs[b] for b in suf], bits,
                        [idxs[b] for b in con], p_cnt))
        return out

    def _local_degree(self, kind: str, k: int) -> int:
        t2 = self.db.relation.degree + self.db.base_degree
        if kind == "full":
            return t2 * self.w
        if kind == "prefix" or self.w - k + 1 == 1:
            return t2 * k
        return t2 * k + self.db.relation.degree      # suffix, M > 1

    def bit_shares(self, be, plane) -> List[Tuple[List[int], Shares]]:
        """Every job's per-tuple match bits: ``[(job idxs, Shares
        (c, Bg, n))]``, bits concatenated across shards. One dataplane
        dispatch wave serves all groups; CONTAINS window counts reassemble
        across shards, reduce ONCE per group (the explicit re-share round,
        mirroring the range engine's carry reduction) and finish with the
        share-local zero test."""
        shard_outs = plane.run_list(
            lambda v, sh: self._shard_values(be, v, sh), phase="match")

        def cat(gi, slot):
            parts = [so[gi][slot] for so in shard_outs]
            return parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=2)

        t2 = self.db.relation.degree + self.db.base_degree
        result: List[Tuple[List[int], Shares]] = []
        for gi, (kind, k, _) in enumerate(self.groups):
            local_idx = shard_outs[0][gi][0]
            con_idx = shard_outs[0][gi][2]
            if local_idx:
                result.append((local_idx, Shares(
                    cat(gi, 1), self._local_degree(kind, k))))
            if con_idx:
                m = self.w - k + 1
                red_key = jax.random.fold_in(self.jobs[con_idx[0]].key, 1)
                p_red = _reshare(plane, red_key, Shares(cat(gi, 3), t2 * k),
                                 1, "the window match")
                z = automata.zero_indicator(p_red.values, m)
                result.append((con_idx, Shares(
                    field.sub(jnp.ones_like(z), z), m)))
        return result


# ---------------------------------------------------------------------------
# cloud-local copies, each one named program (``jit_<name>`` in a trace)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("picks", "axis"))
def stack_columns(src, picks: Tuple[int, ...], axis: int) -> jax.Array:
    """Each job's column, stacked on a new axis 1 -> (c, B, ...).

    ``src`` holds the columns along ``axis`` (the relation's attribute
    axis), or is a tuple of separate column arrays, stacked along ``axis``
    first (binary-form columns). ``picks`` names each job's column. One
    distinct column broadcasts to B copies, several gather.
    """
    if isinstance(src, tuple):
        src = jnp.stack(src, axis=axis)
    b = len(picks)
    if len(set(picks)) == 1:
        one = jax.lax.index_in_dim(src, picks[0], axis, keepdims=False)
        return jnp.broadcast_to(one[:, None],
                                (one.shape[0], b) + one.shape[1:])
    return jnp.moveaxis(jnp.take(src, jnp.asarray(picks), axis=axis),
                        axis, 1)


@functools.partial(jax.jit, static_argnames=("k",))
def prefix_tile(cols: jax.Array, k: int) -> jax.Array:
    """The first ``k`` word positions of stacked columns (c, B, n, W, A),
    which a prefix pattern's truncated chain reads."""
    return cols[..., :k, :]


@functools.partial(jax.jit, static_argnames=("suf", "con", "k"))
def window_bits(win: jax.Array, cols: jax.Array, *, suf=(), con=(), k=0):
    """The slices of a stacked window match ``win`` (c, B, n, M) that the
    pattern finishes read.

    With one window (M = 1) it is every job's bit, ``win[..., 0]``.
    Otherwise ``(suffix windows, suffix terminators, substring windows)``:
    the windows of the suffix jobs ``suf`` with the terminator row of
    their columns ``cols`` past position ``k``, and the windows of the
    substring jobs ``con``; None where that kind is absent.
    """
    if win.shape[-1] == 1:
        return win[..., 0]
    win_suf = term = win_con = None
    if suf:
        s = jnp.asarray(suf, jnp.int32)
        win_suf, term = win[:, s], cols[:, s][..., k:, 0]
    if con:
        win_con = win[:, jnp.asarray(con, jnp.int32)]
    return win_suf, term, win_con


@jax.jit
def fetch_relayout(rel: jax.Array) -> jax.Array:
    """A relation's shares (c, n, m, W, A) as the fetch's right operand
    (c, n, m·W·A); on a TPU a copy, since the tiled layouts differ."""
    return rel.reshape(rel.shape[0], rel.shape[1], -1)


@jax.jit
def stack_onehots(mats: Tuple[jax.Array, ...]) -> jax.Array:
    """Shared one-hot (or match-matrix) row blocks (c, r_i, n) stacked
    into one fetch operand (c, Σr_i, n)."""
    return jnp.concatenate(mats, axis=1)


def _stack_columns(db: SecretSharedDB, columns: Sequence[int]) -> Shares:
    """Cloud-local view: each job's attribute column -> (c, B, n, W, A)."""
    return Shares(stack_columns(db.relation.values, tuple(columns), axis=2),
                  db.relation.degree)


def _stack_numeric(db: SecretSharedDB, columns: Sequence[int]) -> Shares:
    """Cloud-local view of binary-form columns -> (c, B, n, t_bits)."""
    distinct = sorted(set(columns))
    return Shares(stack_columns(
        tuple(db.numeric[c].values for c in distinct),
        tuple(distinct.index(c) for c in columns), axis=1),
        db.numeric[columns[0]].degree)


def _match_stack(be, cols: Shares, pats: Shares) -> Shares:
    """One fused AA dispatch over the stack, with degree bookkeeping."""
    w = cols.values.shape[-2]
    bits = _batched_matcher(be)(cols.values, pats.values)      # (c, B, n)
    return Shares(bits, (cols.degree + pats.degree) * w)


def _block_sums(be, plane: "dataplane.ShardedRelation", p_all: Shares,
                columns: Sequence[int],
                entries: Sequence[Tuple[int, int, int]],
                *, address_weights: bool = False) -> Shares:
    """Shard-aligned block-matrix round for tree Q&A: -> Shares (c, K).

    entries: (job_index, start, end) block jobs, possibly from different
    queries, in GLOBAL tuple coordinates. The ledger-visible block
    partition never changes, but execution fans out per dataplane shard:
    each shard gathers only the slice of every block that intersects its
    [lo, hi) range (local indices into the shard view, padded positions
    masked to a literal 0 so they add nothing), matches, and reduces over
    the block axis — plain block-count sums, or line-number sums weighted
    by ``global index + 1`` when ``address_weights`` is set. Per-shard
    partials combine additively in F_p, so the result is bit-identical to
    the unsharded gather-then-sum for every shard count.
    """
    starts = np.asarray([s for _, s, _ in entries])
    ends = np.asarray([e for _, _, e in entries])
    jidx = np.asarray([i for i, _, _ in entries])
    cols_e = np.asarray([columns[i] for i in jidx])
    rel_degree = plane.db.relation.degree

    def one(v, sh) :
        lo_s = np.clip(starts, sh.lo, sh.hi) - sh.lo           # (K,) local
        hi_s = np.clip(ends, sh.lo, sh.hi) - sh.lo
        h = max(1, int((hi_s - lo_s).max()))
        idx = lo_s[:, None] + np.arange(h)[None, :]            # (K, H_s)
        mask = idx < hi_s[:, None]
        idx = np.where(mask, idx, 0)
        rel = v.relation.values                                # (c,n_s,m,W,A)
        gathered = rel[:, jnp.asarray(idx), jnp.asarray(cols_e)[:, None]]
        pats = Shares(p_all.values[:, jnp.asarray(jidx)], p_all.degree)
        bits = _match_stack(be, Shares(gathered, rel_degree), pats)
        masked = jnp.where(jnp.asarray(mask)[None], bits.values, 0)
        if address_weights:
            # line_number = Σ match_h · (global index + 1); masked
            # positions hold a literal 0 so any weight times them is 0.
            weights = sh.lo + idx + 1                          # (K, H_s)
            masked = field.mul(masked,
                               jnp.asarray(weights, field.DTYPE)[None])
        return field.sum_(masked, axis=2)                      # (c, K)

    w = plane.db.relation.values.shape[-2]
    return Shares(plane.run_sum(one, phase="match"),
                  (rel_degree + p_all.degree) * w)


def _block_sums_cached(cached: Dict[int, Shares],
                       entries: Sequence[Tuple[int, int, int]],
                       *, address_weights: bool = False) -> List[Shares]:
    """Tree Q&A block sums over PRE-COMPUTED per-tuple match bits.

    Pattern jobs run their window match (and the CONTAINS re-share) once in
    the tree prelude and cache the per-tuple bit vector; every later Q&A
    round only sums cached bits over the public block partition — a
    cloud-local linear step charged at one element per tuple instead of a
    fresh W·A automata pass. Plain block-count sums, or line-number sums
    weighted by ``global index + 1`` under ``address_weights`` (the cached
    mirror of :func:`_block_sums`; returns one scalar Shares per entry so
    mixed-degree jobs fuse per degree class at interpolation)."""
    out: List[Shares] = []
    for (i, s, e) in entries:
        vec = cached[i]                                    # (c, n)
        seg = vec.values[:, s:e]
        if address_weights:
            wgt = jnp.arange(s + 1, e + 1, dtype=field.DTYPE)
            seg = field.mul(seg, wgt[None])
        out.append(Shares(field.sum_(seg, axis=1), vec.degree))
    return out


# ---------------------------------------------------------------------------
# §3.1 — batched count phase (Algorithm 2)
# ---------------------------------------------------------------------------

def count_phase(be, db: RelationLike, jobs: Sequence[MatchJob]
                ) -> List[int]:
    """COUNT for B predicates: one cloud dispatch *per shard*, partial
    count sums combining additively, one interpolation."""
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    if not _needs_pattern_engine(jobs):
        # exact + masked only: the classic single-group fast path (one
        # additive-reduce dispatch set, partial sums combine in F_p)
        columns = [j.column for j in jobs]
        with span(plane.stats, "user.share"):
            p_all = _share_patterns(db, jobs)
        w = db.relation.values.shape[-2]
        deg = (db.relation.degree + p_all.degree) * w
        counts = Shares(plane.run_sum(
            lambda v, sh: field.sum_(_batched_matcher(be)(
                _stack_columns(v, columns).values, p_all.values), axis=2),
            phase="match"), deg)                               # (c, B)
        with span(plane.stats, "user.open"):
            out = np.asarray(shamir.interpolate(counts))
        per_q = codec.word_length * codec.alphabet_size
        for j in jobs:
            j.ledger.round()
            j.ledger.send(db.n_shares * per_q)
            j.ledger.cloud(db.n_tuples * per_q)
            j.ledger.recv(db.n_shares)
            j.ledger.user(counts.degree + 1)
        return [int(v) for v in out]

    # mixed / pattern batch: per-group fused match bits, summed and
    # interpolated in one fused user pass per degree class
    mp = _MatcherPlan(plane, jobs)
    parts = mp.bit_shares(be, plane)
    sums = [Shares(field.sum_(sh.values, axis=2), sh.degree)
            for _, sh in parts]
    vals = _open_on_host(plane.stats, sums)
    out = [0] * len(jobs)
    deg_of: Dict[int, int] = {}
    for (idxs, sh), v in zip(parts, vals):
        for b, i in enumerate(idxs):
            out[i] = int(v[b])
            deg_of[i] = sh.degree
    for i, j in enumerate(jobs):
        cost = _charge_match_phase(db, j)
        assert cost["degree"] == deg_of[i], (cost["degree"], deg_of[i])
        j.ledger.recv(db.n_shares)
        j.ledger.user(cost["degree"] + 1)
    return out


# ---------------------------------------------------------------------------
# §3.2.1 — batched single-tuple map round (Algorithm 3 lines 3-12)
# ---------------------------------------------------------------------------

def one_tuple_round(be, db: RelationLike, jobs: Sequence[MatchJob]
                    ) -> List[List[str]]:
    """Fetch the single satisfying tuple for B (ℓ=1-verified) predicates."""
    if not jobs:
        return []
    if _needs_pattern_engine(jobs):
        raise ValueError(
            "one_tuple is the §3.2.1 exact-equality special case; "
            "prefix/suffix/substring selects run one_round or tree")
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    b = len(jobs)
    columns = [j.column for j in jobs]
    with span(plane.stats, "user.share"):
        p_all = _share_patterns(db, jobs)
    c, _, m, w, a = db.relation.values.shape
    match_deg = (db.relation.degree + p_all.degree) * w

    # Σ_n bit·tuple is a share-space matmul of the match bits against the
    # flattened relation — same mod-p result as the elementwise broadcast
    # product, without materializing a B-fold (c,B,n,m,W,A) intermediate.
    # Per shard: match + partial contraction; partials sum additively.
    def one(v: SecretSharedDB, sh):
        bits = _batched_matcher(be)(_stack_columns(v, columns).values,
                                    p_all.values)              # (c,B,n_s)
        return be.ss_matmul(bits, fetch_relayout(v.relation.values))

    sums = Shares(plane.run_sum(one, phase="fetch").reshape(c, b, m, w, a),
                  match_deg + db.relation.degree)              # (c,B,m,W,A)
    with span(plane.stats, "user.open"):
        tup = np.asarray(shamir.interpolate(sums))             # (B, m, W, A)
    per_q = codec.word_length * codec.alphabet_size
    for j in jobs:
        j.ledger.round()
        j.ledger.send(db.n_shares * per_q)
        j.ledger.cloud(db.n_tuples * db.n_attrs * per_q)
        j.ledger.recv(db.n_shares * db.n_attrs * per_q)
        j.ledger.user((sums.degree + 1) * db.n_attrs * codec.word_length)
    return [codec.decode_row(tup[i]) for i in range(b)]


# ---------------------------------------------------------------------------
# §3.2.2 one-round — batched Phase 1 (all n match bits per query)
# ---------------------------------------------------------------------------

def match_all_round(be, db: RelationLike, jobs: Sequence[MatchJob]
                    ) -> List[List[int]]:
    """Per-query satisfying addresses via one fused match-bit round."""
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    if not _needs_pattern_engine(jobs):
        columns = [j.column for j in jobs]
        with span(plane.stats, "user.share"):
            p_all = _share_patterns(db, jobs)
        w = db.relation.values.shape[-2]
        bits = Shares(plane.run_concat(
            lambda v, sh: _batched_matcher(be)(
                _stack_columns(v, columns).values, p_all.values), axis=2,
            phase="match"),
            (db.relation.degree + p_all.degree) * w)           # (c, B, n)
        with span(plane.stats, "user.open"):
            v = np.asarray(shamir.interpolate(bits))           # (B, n)
        per_q = codec.word_length * codec.alphabet_size
        for j in jobs:
            j.ledger.round()
            j.ledger.send(db.n_shares * per_q)
            j.ledger.cloud(db.n_tuples * per_q)
            j.ledger.recv(db.n_shares * db.n_tuples)
            j.ledger.user((bits.degree + 1) * db.n_tuples)
        return [[int(i) for i in np.nonzero(v[b])[0]]
                for b in range(len(jobs))]

    # mixed / pattern batch: grouped dispatches, one fused interpolation
    # pass per degree class — pattern selects then ride the same
    # cross-group fetch_fusion matmul as everything else
    mp = _MatcherPlan(plane, jobs)
    parts = mp.bit_shares(be, plane)
    vals = _open_on_host(plane.stats, [sh for _, sh in parts])
    out: List[List[int]] = [[] for _ in jobs]
    deg_of: Dict[int, int] = {}
    for (idxs, sh), v in zip(parts, vals):
        for b, i in enumerate(idxs):
            out[i] = [int(t) for t in np.nonzero(v[b])[0]]
            deg_of[i] = sh.degree
    n = db.n_tuples
    for i, j in enumerate(jobs):
        cost = _charge_match_phase(db, j)
        assert cost["degree"] == deg_of[i], (cost["degree"], deg_of[i])
        j.ledger.recv(db.n_shares * n)
        j.ledger.user((cost["degree"] + 1) * n)
    return out


# ---------------------------------------------------------------------------
# §3.2.2 tree — lockstep Q&A rounds over the batch (Algorithm 4)
# ---------------------------------------------------------------------------

def tree_rounds(be, db: RelationLike, jobs: Sequence[TreeJob]
                ) -> List[List[int]]:
    """Address discovery for B tree selections, every round fused.

    Each loop iteration performs at most one *count* Q&A round (all active
    blocks of all queries, padded + stacked, one dispatch + one
    interpolation) and at most one *address-fetch* round (all blocks whose
    count came back 1, same fusion). A query stops participating once it has
    no active blocks; its ledger only ever records its own rounds, blocks
    and bits — identical to running it alone.

    Q&A rounds gather *blocks* — a public tuple-axis partition refinement
    that is part of the transcript and never moves with the shard count —
    but their execution is shard-aligned: each dataplane shard gathers only
    the block slices inside its own bounds and the per-shard partial
    count / line-number sums combine additively (:func:`_block_sums`), so
    no Q&A round ever gathers the full relation on one device. The fetch
    that follows rides the sharded :func:`fetch_fusion`.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    per_q = codec.word_length * codec.alphabet_size
    n = db.n_tuples

    # -- prelude: split exact/masked jobs (full-width chain, recomputed
    # per Q&A block) from tile-pattern jobs (window match + CONTAINS
    # re-share run ONCE, per-tuple bits cached for every later round) ----
    pat_pos = [i for i, j in enumerate(jobs)
               if getattr(j, "spec", None) is not None
               and j.spec.kind in ("prefix", "suffix", "contains")]
    exact_pos = [i for i in range(len(jobs)) if i not in set(pat_pos)]
    exact_slot = {i: s for s, i in enumerate(exact_pos)}
    columns = [jobs[i].column for i in exact_pos]
    with span(plane.stats, "user.share"):
        p_all = (_share_patterns(db, [jobs[i] for i in exact_pos])
                 if exact_pos else None)
    cached: Dict[int, Shares] = {}
    if pat_pos:
        mp = _MatcherPlan(plane, [jobs[i] for i in pat_pos])
        for idxs, sh in mp.bit_shares(be, plane):
            for b, local in enumerate(idxs):
                cached[pat_pos[local]] = Shares(sh.values[:, b], sh.degree)
    for i, j in enumerate(jobs):
        cost = match_phase_cost(getattr(j, "spec", None), n=n,
                                c=db.n_shares, w=codec.word_length,
                                a=codec.alphabet_size,
                                col_degree=db.relation.degree,
                                pat_degree=db.base_degree)
        j.ledger.send(cost["send"])
        if i in cached:
            # the one-off window match (amortized into the first Q&A
            # round's dispatch) and the explicit CONTAINS re-share round
            j.ledger.cloud(cost["cloud"])
            if cost["reduce_rounds"]:
                j.ledger.round(cost["reduce_rounds"])
                j.ledger.send(cost["reduce_send"])
                j.ledger.cloud(cost["reduce_cloud"])

    addresses: List[List[int]] = [[] for _ in jobs]
    active: List[List[Tuple[int, int]]] = []
    first = [True] * len(jobs)
    pending_addr: List[Tuple[int, int, int]] = []
    # ℓ=1 queries take the Alg 4 line 2 path: one whole-table address fetch
    # that counts as its own round (the per-query wrapper's legacy
    # behaviour), then straight to Phase 2.
    one_shot = set()
    for i, j in enumerate(jobs):
        if j.ell == 1:
            pending_addr.append((i, 0, n))
            one_shot.add(i)
            active.append([])
        else:
            active.append([(0, n)])

    while any(active) or pending_addr:
        # -- partition every query's active blocks (public, host-side) ------
        entries: List[Tuple[int, int, int]] = []
        for i, blocks in enumerate(active):
            if not blocks:
                continue
            fanout = jobs[i].branching or jobs[i].ell
            k = fanout if first[i] else max(2, fanout)
            first[i] = False
            subs = []
            for (s, e) in blocks:
                subs += split_bounds(s, e, k)
            entries += [(i, s, e) for (s, e) in subs]
            active[i] = []

        # -- count Q&A round: ONE dispatch set + ONE interpolation ----------
        if entries:
            vals_by_entry, deg_by_job = _tree_block_round(
                be, plane, p_all, columns, exact_slot, cached, entries)
            n_blocks: dict = {}
            for (i, s, e) in entries:
                jobs[i].ledger.cloud(
                    (e - s) * (per_q if i in exact_slot else 1))
                n_blocks[i] = n_blocks.get(i, 0) + 1
            for i, k_i in n_blocks.items():
                jobs[i].ledger.round()
                jobs[i].ledger.recv(db.n_shares * k_i)
                jobs[i].ledger.user((deg_by_job[i] + 1) * k_i)
            for (i, s, e) in entries:
                v = vals_by_entry[(i, s, e)]
                if v == 0:                     # Case 1: dead block
                    continue
                if v == 1:                     # Case 2: Address_fetch
                    pending_addr.append((i, s, e))
                elif v == e - s:               # Case 3: whole block matches
                    addresses[i].extend(range(s, e))
                else:                          # Case 4: recurse
                    active[i].append((s, e))

        # -- address-fetch round: ONE dispatch set + ONE interpolation ------
        if pending_addr:
            addr_entries, pending_addr = pending_addr, []
            vals_by_entry, deg_by_job = _tree_block_round(
                be, plane, p_all, columns, exact_slot, cached, addr_entries,
                address_weights=True)
            for (i, s, e) in addr_entries:
                jobs[i].ledger.cloud(
                    (e - s) * (per_q if i in exact_slot else 1))
                jobs[i].ledger.recv(db.n_shares)
                jobs[i].ledger.user(deg_by_job[i] + 1)
                addresses[i].append(vals_by_entry[(i, s, e)] - 1)
                if i in one_shot:
                    jobs[i].ledger.round()
                    one_shot.discard(i)

    return [sorted(a) for a in addresses]


def _tree_block_round(be, plane, p_all, columns, exact_slot, cached,
                      entries, *, address_weights: bool = False
                      ) -> Tuple[Dict[Tuple[int, int, int], int],
                                 Dict[int, int]]:
    """One fused tree Q&A round over mixed exact + cached-pattern entries.

    Exact/masked entries recompute their block match through the classic
    shard-aligned :func:`_block_sums` dispatch; pattern entries sum their
    cached per-tuple bits (:func:`_block_sums_cached`). All results
    interpolate in one fused user pass per degree class. Returns the opened
    value per (job, start, end) entry and each job's bit degree (for the
    caller's user-step charge)."""
    ex_meta = [t for t in entries if t[0] in exact_slot]
    pat_meta = [t for t in entries if t[0] not in exact_slot]
    parts: List[Shares] = []
    if ex_meta:
        parts.append(_block_sums(
            be, plane, p_all, columns,
            [(exact_slot[i], s, e) for (i, s, e) in ex_meta],
            address_weights=address_weights))
    parts += _block_sums_cached(cached, pat_meta,
                                address_weights=address_weights)
    vals = _open_on_host(plane.stats, parts)
    vals_by_entry: Dict[Tuple[int, int, int], int] = {}
    deg_by_job: Dict[int, int] = {}
    vi = 0
    if ex_meta:
        for t, x in zip(ex_meta, np.asarray(vals[0])):
            vals_by_entry[t] = int(x)
            deg_by_job[t[0]] = parts[0].degree
        vi = 1
    for t, x, p in zip(pat_meta, vals[vi:], parts[vi:]):
        vals_by_entry[t] = int(x)
        deg_by_job[t[0]] = p.degree
    return vals_by_entry, deg_by_job


# ---------------------------------------------------------------------------
# §3.4 — batched range predicates (Algorithms 5 & 6)
# ---------------------------------------------------------------------------

def ripple_schedule(t_bits: int, reduce_every: int, degree: int
                    ) -> List[Tuple[int, int, int]]:
    """The SS-SUB ripple as :func:`range_phase` and the MIN/MAX tournament
    run it: one ``(s0, s1, carry_degree)`` per [s0, s1) bit segment
    between degree-reduction boundaries, ``carry_degree`` being the
    carry's (and the result bit's) degree at the segment's end. A bit step
    adds 2·degree; the re-share between segments brings the carry back to
    degree 1. The planner prices and bounds the same schedule
    (``planner.choose_reduce_every``)."""
    edges = (list(range(0, t_bits, reduce_every)) if reduce_every
             else [0]) + [t_bits]
    out, carry = [], 0
    for s0, s1 in zip(edges[:-1], edges[1:]):
        carry = (1 if out else 0) + 2 * degree * (s1 - s0)
        out.append((s0, s1, carry))
    return out


def range_phase(be, db: RelationLike, jobs: Sequence[RangeJob]) -> Shares:
    """Secret-shared in-range indicator for B range predicates: (c, B, n).

    The fused SS-SUB ripple (Algorithm 6): each query contributes two
    subtractions — ``sign(x − a)`` and ``sign(b − x)`` (Eq. 2) — so the B
    queries' bit-vectors stack into one ``(c, 2B, n, t_bits)`` carry chain.
    The bits between two degree-reduction boundaries fuse into ONE backend
    ``ripple_segment`` dispatch per shard (≈ t_bits/reduce_every segment
    dispatches, one chain for the whole batch; a backend without the fused
    segment op transparently steps per bit); each ``reduce_every`` boundary
    is ONE degree-reduction re-share of the whole stacked carry —
    re-sharing is the protocol's explicit communication round, so the carry
    is reassembled across shards, reduced once, and re-sliced. Ledgers
    record every query's own protocol cost exactly as a solo run (a
    reduction is two logical rounds per query: one per subtraction, as in
    the sequential transcript).
    """
    plane = dataplane.as_dataplane(db)
    db = plane.db
    t_bits_all = []
    for j in jobs:
        if j.column not in db.numeric:
            raise ValueError(
                f"column {j.column} was not outsourced in binary form")
        t_bits_all.append(db.numeric_bits[j.column])
    if len(set(t_bits_all)) != 1 or len({j.reduce_every for j in jobs}) != 1:
        raise ValueError("a fused range_phase needs uniform t_bits and "
                         "reduce_every across its jobs (group them)")
    t_bits = t_bits_all[0]
    reduce_every = jobs[0].reduce_every
    b = len(jobs)
    n = db.n_tuples
    c = db.n_shares

    # -- user round: share both endpoints of every job --------------------
    a_vals, b_vals = [], []
    red_key = None
    with span(plane.stats, "user.share"):
        for j in jobs:
            k_a, k_b, k_s1, _ = jax.random.split(j.key, 4)
            if red_key is None:
                red_key = k_s1          # seeds the fused reduction chain
            a_vals.append(encoding.share_encoded(
                k_a, encoding.encode_number_bits(j.lo, t_bits),
                n_shares=c, degree=db.base_degree).values)
            b_vals.append(encoding.share_encoded(
                k_b, encoding.encode_number_bits(j.hi, t_bits),
                n_shares=c, degree=db.base_degree).values)
            j.ledger.round()
            j.ledger.send(c * 2 * t_bits)

    x = _stack_numeric(db, [j.column for j in jobs])       # (c, B, n, t)
    d = db.base_degree
    assert x.degree == d, "binary-form columns share the base degree"
    a_all = jnp.stack(a_vals, axis=1)[:, :, None, :]       # (c, B, 1, t)
    b_all = jnp.stack(b_vals, axis=1)[:, :, None, :]
    shape = x.values.shape
    # rows [0, B) ripple sign(x − a): SS-SUB(A=a, B=x); rows [B, 2B) ripple
    # sign(b − x): SS-SUB(A=x, B=b) — one chain for both directions.
    lhs = jnp.concatenate([jnp.broadcast_to(a_all, shape), x.values], axis=1)
    rhs = jnp.concatenate([x.values, jnp.broadcast_to(b_all, shape)], axis=1)

    segment = _ripple_segmenter(be)
    shards = plane.shards
    lhs_parts = [lhs[:, :, sh.lo:sh.hi] for sh in shards]
    rhs_parts = [rhs[:, :, sh.lo:sh.hi] for sh in shards]
    carries: List[Optional[jax.Array]] = [None] * len(shards)
    rb_parts: List[jax.Array] = []
    carry_deg = 0
    for s0, s1, seg_deg in ripple_schedule(t_bits, reduce_every, d):
        if carry_deg:
            # degree reduction = the explicit re-sharing round: reassemble
            # the carry across shards, reduce ONCE, re-slice per shard.
            carry_full = (carries[0] if len(shards) == 1
                          else jnp.concatenate(carries, axis=2))
            red_key, sub = jax.random.split(red_key)
            carry_full = _reshare(plane, sub, Shares(carry_full, carry_deg),
                                  1, "the range ripple's carry").values
            carry_deg = 1
            carries = [carry_full[:, :, sh.lo:sh.hi] for sh in shards]
            for j in jobs:
                j.ledger.round(2)
                j.ledger.send(2 * c * c)
        # per-shard segment dispatch; the result bit leaves each step at
        # the carry's (post-step) degree, +2d per bit position.
        outs = plane.run_list(
            lambda v, sh, s0=s0, s1=s1: segment(
                lhs_parts[sh.index][..., s0:s1],
                rhs_parts[sh.index][..., s0:s1], carries[sh.index]),
            phase="ripple")
        plane.stats.ripple_segments += 1
        rb_parts = [o[0] for o in outs]
        carries = [o[1] for o in outs]
        carry_deg = seg_deg
    for j in jobs:
        j.ledger.cloud(2 * n * t_bits)

    rb = (rb_parts[0] if len(shards) == 1
          else jnp.concatenate(rb_parts, axis=2))
    # Eq. 2: in-range ⟺ 1 − sign(x−a) − sign(b−x) = 1
    ind = field.sub(field.sub(jnp.ones((c, b, n), field.DTYPE),
                              rb[:, :b]), rb[:, b:])
    return Shares(ind, carry_deg)


def range_rounds(be, db: RelationLike, jobs: Sequence[RangeJob]
                 ) -> List[Union[int, List[int]]]:
    """COUNT / address discovery for B range predicates, rounds fused.

    Returns, aligned with ``jobs``: the count (``want_addresses=False``) or
    the sorted satisfying addresses (``want_addresses=True``, ready for the
    shared :func:`fetch_fusion` matmul). One interpolation serves all count
    jobs and one serves all address jobs.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    ind = range_phase(be, plane, jobs)
    c, n = db.n_shares, db.n_tuples
    _within_clouds(ind.degree, c, "the range indicator")
    out: List[Union[int, List[int], None]] = [None] * len(jobs)
    cnt_idx = [i for i, j in enumerate(jobs) if not j.want_addresses]
    sel_idx = [i for i, j in enumerate(jobs) if j.want_addresses]
    if cnt_idx:
        totals = Shares(field.sum_(ind.values[:, cnt_idx], axis=2),
                        ind.degree)                         # (c, Bc)
        with span(plane.stats, "user.open"):
            vals = np.asarray(shamir.interpolate(totals))
        for i, v in zip(cnt_idx, vals):
            jobs[i].ledger.recv(c)
            jobs[i].ledger.user(ind.degree + 1)
            out[i] = int(v)
    if sel_idx:
        bits = Shares(ind.values[:, sel_idx], ind.degree)   # (c, Bs, n)
        with span(plane.stats, "user.open"):
            vals = np.asarray(shamir.interpolate(bits))
        for k, i in enumerate(sel_idx):
            jobs[i].ledger.recv(c * n)
            jobs[i].ledger.user((ind.degree + 1) * n)
            out[i] = [int(t) for t in np.nonzero(vals[k])[0]]
    return out


# ---------------------------------------------------------------------------
# §3.2.2 Phase 2 — fused oblivious fetch for the whole batch
# ---------------------------------------------------------------------------

#: one relation's slice of a (possibly multi-relation) fused fetch round:
#: ``(db_or_plane, one-hot jobs, extra share-form row blocks)``.
FetchPart = Tuple[RelationLike, Sequence[FetchJob], Sequence["FetchEntry"]]


def _fetch_stack(be, plane, jobs: Sequence[FetchJob],
                 extras: Sequence[FetchEntry]):
    """Build one relation's stacked fetch matmul as a DispatchSet."""
    db = plane.db
    ellps = []
    mats = []
    with span(plane.stats, "user.share"):
        for j in jobs:
            ell = len(j.addresses)
            ellp = max(j.padded_rows or ell, ell)
            ellps.append(ellp)
            m_sh = _share_one_hot(j.key, db, j.addresses, ellp)  # (c,ℓ',n)
            mats.append(m_sh.values)
        stacked = stack_onehots(tuple(mats + [e.values for e in extras]))
    ds = plane.dispatch_set(                        # ONE dispatch per shard
        lambda v, sh: be.ss_matmul(stacked[:, :, sh.lo:sh.hi],
                                   fetch_relayout(v.relation.values)),
        reduce="sum", phase="fetch")
    return ds, ellps


def _fetch_split(plane, fetched_flat, ellps: List[int],
                 jobs: Sequence[FetchJob], extras: Sequence[FetchEntry]
                 ) -> Tuple[List[List[List[str]]], List[Shares]]:
    """User step after the fused matmul: interpolate, decode, charge."""
    db = plane.db
    codec = db.codec
    n = db.n_tuples
    c, _, m, w, a = db.relation.values.shape
    results: List[List[List[str]]] = []
    job_rows = sum(ellps)
    if jobs:
        fetched = Shares(
            fetched_flat[:, :job_rows].reshape(c, job_rows, m, w, a),
            db.base_degree + db.relation.degree)
        with span(plane.stats, "user.open"):
            out = np.asarray(shamir.interpolate(fetched))      # (R,m,W,A)
        off = 0
        for j, ellp in zip(jobs, ellps):
            ell = len(j.addresses)
            j.ledger.round()
            j.ledger.send(db.n_shares * ellp * n)
            j.ledger.cloud(ellp * n * m * w * a)
            j.ledger.recv(db.n_shares * ellp * m * w * a)
            j.ledger.user((fetched.degree + 1) * ellp * m * w)
            results.append([codec.decode_row(out[off + r])
                            for r in range(ell)])
            off += ellp

    extra_out: List[Shares] = []
    off = job_rows
    for e in extras:
        r = e.values.shape[1]
        extra_out.append(Shares(
            fetched_flat[:, off:off + r].reshape(c, r, m, w, a),
            e.degree + db.relation.degree))
        off += r
    return results, extra_out


def fetch_fusion_multi(be, parts: Sequence[FetchPart]
                       ) -> List[Tuple[List[List[List[str]]], List[Shares]]]:
    """Cross-RELATION fetch fusion: one dispatch wave for many fetches.

    Each part is one relation's cross-group fetch (its own stacked one-hot
    matmul — batches never mix across relations; every job matrix stays
    shared under its own query key). The parts' per-shard matmul dispatches
    execute as ONE fused wave when their dataplanes share a dispatch pool
    (:func:`repro.core.dataplane.fused_execute`); transcripts, ledgers and
    results are bit-identical to running each part's fetch alone, because
    fusion only co-schedules the already-independent shard dispatches.
    Returns one ``(rows_per_job, extra_shares)`` pair per part, in order.
    """
    live: List[Tuple[int, Any, Any, List[int]]] = []
    out: List[Tuple[List[List[List[str]]], List[Shares]]] = \
        [([], []) for _ in parts]
    for i, (db, jobs, extras) in enumerate(parts):
        if not jobs and not extras:
            continue
        plane = dataplane.as_dataplane(db)
        ds, ellps = _fetch_stack(be, plane, jobs, extras)
        live.append((i, plane, ds, ellps))
    fetched = dataplane.fused_execute([(plane, ds)
                                       for _, plane, ds, _ in live])
    for (i, plane, _, ellps), flat in zip(live, fetched):
        _, jobs, extras = parts[i]
        out[i] = _fetch_split(plane, flat, ellps, jobs, extras)
    return out


def fetch_fusion(be, db: RelationLike, jobs: Sequence[FetchJob],
                 extras: Sequence[FetchEntry] = ()
                 ) -> Tuple[List[List[List[str]]], List[Shares]]:
    """The cross-group fetch: ONE share-space matmul for everything.

    Each one-hot job's ℓ'×n matrix (``padded_rows`` ≥ ℓ hides the true
    result size, §3.2.2 leakage discussion) is shared under that query's own
    key; all job matrices — a zero-match, unpadded job contributes a 0-row
    block — AND every extra row-block (e.g. a PK/FK join's transposed
    match matrix) are stacked
    row-wise so the cloud performs a single (ΣR × n) @ (n × mWA) fused
    fetch. On a sharded dataplane the contraction axis n splits per shard —
    one (ΣR × n_s) @ (n_s × mWA) dispatch each, partial products summing
    additively in F_p. The user then interpolates all job tuples in one
    pass and splits them back per query; extras come back *still in share
    form* — their protocol (re-randomization, layer-2 hand-off, …)
    continues outside. (The single-relation view of
    :func:`fetch_fusion_multi`.)
    """
    return fetch_fusion_multi(be, [(db, jobs, extras)])[0]


def fetch_round(be, db: SecretSharedDB, jobs: Sequence[FetchJob]
                ) -> List[List[List[str]]]:
    """Fetch every job's tuples with ONE share-space matmul (the one-hot
    jobs-only view of :func:`fetch_fusion`)."""
    return fetch_fusion(be, db, jobs)[0]


# ---------------------------------------------------------------------------
# §3.3.1 — PK/FK joins as rounds (match matrix -> shared fetch -> emit)
# ---------------------------------------------------------------------------

def rerandomize(key: jax.Array, s: Shares) -> Shares:
    """Add a fresh sharing of zero: same secret, unlinkable share values."""
    zero = shamir.share(key, jnp.zeros(s.shape, dtype=s.values.dtype),
                        n_shares=s.n_shares, degree=s.degree)
    return s + zero


def join_match_round(be, db: RelationLike, jobs: Sequence[JoinJob]
                     ) -> List[FetchEntry]:
    """Cloud step 1 of B PK/FK joins: match matrices, transposed into
    :class:`FetchEntry` rows for the shared :func:`fetch_fusion` matmul
    (reducer j's Σ_i M[i,j]·X_i is a row-block of the same fused fetch the
    selection groups ride).

    Jobs whose right relations have equal size (and sharing degree) stack
    into ONE ``(c, B, nx, ny)`` ``match_matrix_batch`` dispatch per shard —
    mirroring ``aa_match_batch`` for predicates — instead of one
    ``match_matrix`` dispatch per job. Left columns slice per tuple-axis
    shard and the match rows concatenate back along nx. A job's
    ``match_method`` joins the group key (chain / aggregate members never
    mix in one dispatch); ledger charges are method-independent — the
    dot-set volume nx·ny·W·A is the protocol cost either way.
    """
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    w_len, a_len = codec.word_length, codec.alphabet_size
    entries: List[Optional[FetchEntry]] = [None] * len(jobs)
    groups: Dict[tuple, List[Tuple[int, Shares]]] = {}
    for i, j in enumerate(jobs):
        if j.match_method not in ("chain", "aggregate"):
            raise ValueError(f"unknown match_method: {j.match_method!r}")
        by = j.right.column(j.col_y)
        groups.setdefault((by.values.shape, by.degree, j.match_method),
                          []).append((i, by))
    for (_, by_deg, method), members in groups.items():
        matcher = (_aggregate_matcher(be) if method == "aggregate"
                   else _batched_match_matrix(be))
        idxs = [i for i, _ in members]
        by_stack = jnp.stack([by.values for _, by in members],
                             axis=1)                    # (c, B, ny, W, A)
        cols_x = [jobs[i].col_x for i in idxs]
        m_vals = plane.run_concat(
            lambda v, sh: matcher(
                jnp.stack([v.column(cx).values for cx in cols_x], axis=1),
                by_stack), axis=2, phase="join")        # (c, B, nx, ny)
        deg = (db.relation.degree + by_deg) * w_len
        for k, i in enumerate(idxs):
            j = jobs[i]
            j.ledger.cloud(db.n_tuples * j.right.n_tuples * w_len * a_len)
            entries[i] = FetchEntry(jnp.swapaxes(m_vals[:, k], -1, -2), deg)
    return entries


def join_emit_round(db: RelationLike, jobs: Sequence[JoinJob],
                    fetched: Sequence[Shares]) -> List[List[List[str]]]:
    """User/cloud step 2 of B PK/FK joins: re-randomize the fetched parent
    halves, ship both halves, interpolate ALL jobs' tuples in one fused user
    step per degree class, decode and drop dangling children."""
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    w_len, a_len = codec.word_length, codec.alphabet_size
    c, nx, mx = db.n_shares, db.n_tuples, db.n_attrs
    xs_parts: List[Shares] = []
    ys_parts: List[Shares] = []
    for j, fx in zip(jobs, fetched):
        ny, my = j.right.n_tuples, j.right.n_attrs
        j.ledger.cloud(nx * ny * mx * w_len)
        y_part = j.right.relation                    # (c, ny, mY, W, A)
        if j.key is not None:
            kx, ky = jax.random.split(j.key)
            fx = rerandomize(kx, fx)
            y_part = rerandomize(ky, y_part)
            j.ledger.cloud(ny * (mx + my) * w_len * a_len)
        j.ledger.round()
        j.ledger.recv(c * ny * (mx + my) * w_len * a_len)
        xs_parts.append(fx)
        ys_parts.append(y_part)
    xs_all = _open_on_host(plane.stats, xs_parts)
    ys_all = _open_on_host(plane.stats, ys_parts)

    results: List[List[List[str]]] = []
    for j, fx, yp, xs, ys in zip(jobs, xs_parts, ys_parts, xs_all, ys_all):
        ny, my = j.right.n_tuples, j.right.n_attrs
        j.ledger.user((fx.degree + 1) * ny * mx * w_len
                      + (yp.degree + 1) * ny * my * w_len)
        rows = []
        for r in range(ny):
            x_row = codec.decode_row(xs[r])
            if all(v == "" for v in x_row):
                continue                  # dangling child (no parent)
            y_row = codec.decode_row(ys[r])
            rows.append(x_row + [v for k, v in enumerate(y_row)
                                 if k != j.col_y])
        results.append(rows)
    return results


# ---------------------------------------------------------------------------
# §3.3.2 — general equijoins as rounds (two cloud layers, fused per phase)
# ---------------------------------------------------------------------------

def _one_hot_fetch_shares(key: jax.Array, db: SecretSharedDB,
                          addresses: Sequence[int], ledger: CostLedger
                          ) -> Shares:
    """Layer-1 fetch matrix (kept in share form); ledger records the send
    and the cloud work exactly as a solo oblivious fetch."""
    n = db.n_tuples
    m_sh = _share_one_hot(key, db, addresses)
    ledger.send(db.n_shares * len(addresses) * n)
    _, _, m, w, a = db.relation.values.shape
    ledger.cloud(len(addresses) * n * m * w * a)
    return m_sh


def equijoin_rounds(be, db: RelationLike, jobs: Sequence[EquiJob]
                    ) -> List[List[List[str]]]:
    """§3.3.2 equijoins over a batch, every phase fused.

    Phase 1 (one round): both join columns of every job travel to the user;
    ONE interpolation pass per degree class opens them all. Phase 2: every
    (job, common-value) pair — including the ``padded_values`` fake jobs
    that hide k — builds its two layer-1 one-hot matrices; all X-side
    matrices multiply the client relation in ONE ``ss_matmul`` per
    tuple-axis shard (partial contractions summing additively), Y-side
    matrices fuse per distinct right relation. Phase 3: layer 2 emits the
    ℓx×ℓy concatenations; the user interpolates all real pairs in one fused
    pass per degree class. Ledgers stay bit-identical to the sequential
    per-value transcript (Thm 6's 2k rounds each)."""
    if not jobs:
        return []
    plane = dataplane.as_dataplane(db)
    db = plane.db
    codec = db.codec
    w_len, a_len = codec.word_length, codec.alphabet_size
    c, nx, mx = db.n_shares, db.n_tuples, db.n_attrs

    # -- phase 1: fused column open ------------------------------------
    col_parts: List[Shares] = []
    for j in jobs:
        bx = db.column(j.col_x)
        by = j.right.column(j.col_y)
        j.ledger.round()
        j.ledger.recv(c * nx * w_len * a_len
                      + j.right.n_shares * j.right.n_tuples * w_len * a_len)
        col_parts += [bx, by]
    opened = _open_on_host(plane.stats, col_parts)
    val_lists: List[Tuple[List[str], List[str]]] = []
    for i, j in enumerate(jobs):
        bx, by = col_parts[2 * i], col_parts[2 * i + 1]
        x_vals = [codec.decode_word(v) for v in opened[2 * i]]
        y_vals = [codec.decode_word(v) for v in opened[2 * i + 1]]
        j.ledger.user((bx.degree + 1) * nx * w_len
                      + (by.degree + 1) * j.right.n_tuples * w_len)
        val_lists.append((x_vals, y_vals))

    # -- phase 2: all layer-1 fetch matrices, X side in ONE matmul -------
    specs = []          # (job, addr_x, addr_y, real, x_mat, y_mat)
    with span(plane.stats, "user.share"):
        for j, (x_vals, y_vals) in zip(jobs, val_lists):
            common = sorted(set(x_vals) & set(y_vals))
            key = j.key
            for idx in range(len(common) + j.padded_values):
                key, kx, ky = jax.random.split(key, 3)
                real = idx < len(common)
                if real:
                    v = common[idx]
                    addr_x = [i for i, t in enumerate(x_vals) if t == v]
                    addr_y = [i for i, t in enumerate(y_vals) if t == v]
                else:   # fake job: all-zero matrices, same traffic (hides k)
                    addr_x, addr_y = [0], [0]
                j.ledger.round(2)   # Thm 6: two rounds per (fake) value
                xm = _one_hot_fetch_shares(kx, db, addr_x, j.ledger)
                ym = _one_hot_fetch_shares(ky, j.right, addr_y, j.ledger)
                specs.append((j, addr_x, addr_y, real, xm, ym))

    if not specs:       # every job had zero common values and no padding
        return [[] for _ in jobs]
    x_stack = stack_onehots(tuple(s[4].values for s in specs))
    x_fetched = plane.run_sum(          # ONE X-side dispatch per shard
        lambda v, sh: be.ss_matmul(x_stack[:, :, sh.lo:sh.hi],
                                   fetch_relayout(v.relation.values)),
        phase="join")
    y_by_right: Dict[int, List[int]] = {}
    for i, s in enumerate(specs):
        y_by_right.setdefault(id(s[0].right), []).append(i)
    y_fetched: Dict[int, jax.Array] = {}
    for _, idxs in y_by_right.items():
        right = specs[idxs[0]][0].right
        y_stack = stack_onehots(tuple(specs[i][5].values for i in idxs))
        with span(plane.stats, "cloud.join"):  # one per right relation
            out = be.ss_matmul(y_stack, fetch_relayout(right.relation.values))
        off = 0
        for i in idxs:
            rows_i = specs[i][5].values.shape[1]
            y_fetched[i] = out[:, off:off + rows_i]
            off += rows_i

    # -- phase 3: layer-2 pairing; fused final interpolation -------------
    xs_parts, ys_parts, metas = [], [], []
    x_off = 0
    for i, (j, addr_x, addr_y, real, xm, ym) in enumerate(specs):
        lx, ly = len(addr_x), len(addr_y)
        my = j.right.n_attrs
        _, _, mw, ww, aw = db.relation.values.shape
        xp = Shares(x_fetched[:, x_off:x_off + lx].reshape(c, lx, mw, ww, aw),
                    xm.degree + db.relation.degree)
        x_off += lx
        ry = j.right.relation
        _, _, mwy, wwy, awy = ry.values.shape
        yp = Shares(y_fetched[i].reshape(j.right.n_shares, ly, mwy, wwy,
                                         awy), ym.degree + ry.degree)
        pairs_x = Shares(jnp.repeat(xp.values, ly, axis=1), xp.degree)
        pairs_y = Shares(jnp.tile(yp.values, (1, lx, 1, 1, 1)), yp.degree)
        j.ledger.cloud(lx * ly * (mx + my) * w_len * a_len)
        if not real:
            continue                # fake-job output discarded at user side
        j.ledger.recv(c * lx * ly * (mx + my) * w_len * a_len)
        j.ledger.user((pairs_x.degree + 1) * lx * ly * mx * w_len
                      + (pairs_y.degree + 1) * lx * ly * my * w_len)
        xs_parts.append(pairs_x)
        ys_parts.append(pairs_y)
        metas.append((j, lx * ly))
    xs_all = _open_on_host(plane.stats, xs_parts)
    ys_all = _open_on_host(plane.stats, ys_parts)

    by_job: Dict[int, List[List[str]]] = {id(j): [] for j in jobs}
    for (j, n_pairs), xs, ys in zip(metas, xs_all, ys_all):
        for r in range(n_pairs):
            x_row = codec.decode_row(xs[r])
            y_row = codec.decode_row(ys[r])
            by_job[id(j)].append(
                x_row + [v for k, v in enumerate(y_row) if k != j.col_y])
    return [by_job[id(j)] for j in jobs]
